"""Share of the decode slots that hold a decoding request, mean over the
window's ticks."""


def read(spans, facts, trace, info):
    occ = facts.get("occupancy")
    return 100.0 * sum(occ) / len(occ) / facts["slots"] if occ else None
