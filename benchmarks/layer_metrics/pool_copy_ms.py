"""Device milliseconds per scheduler tick in copy and dynamic-(update-)
slice ops on buffers of the KV pool's shape (``[.., num_pages, page_size,
heads, head_dim]``): what moving the pool costs beside attending to it."""

from benchmarks import readers, trace_reduce


def read(spans, facts, trace, info):
    if not readers.on_device(trace) or not facts.get("pool_pages"):
        return None
    needle = f"{facts['pool_pages']},{facts['page_size']},"
    seconds = sum(trace_reduce.ops_matching(trace, p, needle)
                  for p in ("copy:", "dus:", "fusion:"))
    return readers.per_tick(seconds, trace, "engine_step")
