"""Production serving runtime: continuous batching over a paged KV cache.

The training side of the FleetX blueprint has had a request-LESS inference
path since the seed (``core/engine/inference_engine.py`` — stateless batch
predict); this package is the request-LEVEL runtime the ROADMAP's "serve
heavy traffic from millions of users" north star needs (docs/serving.md):

- ``paged_cache``  — fixed-size KV pages in a preallocated pool with
  per-request block tables (the "Compiler-First State Space Duality and
  Portable O(1) Autoregressive Caching" blueprint, PAPERS.md);
- ``decode``       — jitted chunk-prefill + one-token decode steps with
  STATIC batch/page shapes, so continuous batching never retraces;
- ``engine``       — the continuous-batching scheduler: requests join
  in-flight decode at step boundaries, long prompts chunk-prefill without
  stalling the decode batch, admission refuses what the pool cannot hold;
- ``server``       — one engine replica behind a JSON-lines TCP front with
  graceful drain on the PR 4/6 preemption latch;
- ``router``       — round-robin + least-outstanding request router over N
  supervised replicas, re-dispatching on replica loss.
"""

__all__ = ["ServingConfig", "ServingEngine", "PageAllocator", "init_pool",
           "NULL_PAGE"]

#: package export → defining submodule; resolved on first attribute access
#: (PEP 562) so importing ``fleetx_tpu.serving.router`` — the stdlib-only
#: fleet front that must start in <1s — never pays the engine's jax import
_EXPORTS = {
    "ServingConfig": "engine", "ServingEngine": "engine",
    "PageAllocator": "paged_cache", "init_pool": "paged_cache",
    "NULL_PAGE": "paged_cache",
}


def __getattr__(name: str):
    """Lazy package exports (keeps the router import path jax-free)."""
    module = _EXPORTS.get(name)
    if module is None:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    import importlib

    return getattr(importlib.import_module(f"{__name__}.{module}"), name)
