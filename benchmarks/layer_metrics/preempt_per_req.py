"""Preemptions (pool ran dry, youngest request swapped out) per finished
request."""


def read(spans, facts, trace, info):
    if "preempts" not in facts:
        return None
    return facts["preempts"] / max(facts.get("finished_all", 0), 1)
