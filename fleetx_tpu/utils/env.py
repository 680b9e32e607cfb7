"""Distributed environment bootstrap and RNG policy.

Re-designs ``ppfleetx/utils/env.py:27-96``. The reference builds NCCL hybrid
process groups (``fleet.init`` + ``DistributedStrategy.hybrid_configs``) and
tracks per-rank RNG state for mp-correct dropout; here the process bootstrap is
``jax.distributed.initialize`` and the RNG policy is functional: one global
seed, split into named streams (params / dropout / data) via
``jax.random.fold_in``.  Dropout inside tensor-parallel regions is made
mp-correct for free because JAX PRNG keys are carried in the traced program and
sharded consistently by GSPMD, unlike the reference's stateful per-rank seed
trackers (``env.py:41-46``).
"""

from __future__ import annotations

import collections
import json
import os
import re
import time

import jax

from fleetx_tpu.utils.log import logger, set_rank_context

#: tri-state: None = never called, True/False = first call's verdict
_initialized: bool | None = None


def init_dist_env(coordinator_address: str | None = None,
                  num_processes: int | None = None,
                  process_id: int | None = None) -> bool:
    """Initialize multi-host JAX if requested via env or args.

    Single-host (the common dev case) is a no-op: ``jax.devices()`` already
    sees the local chips. Multi-host pods set ``FLEETX_COORDINATOR`` etc.
    (``tools/supervise.py --num-procs`` populates exactly these) or rely on
    TPU metadata auto-detection inside ``jax.distributed.initialize``.

    Returns whether the distributed runtime is active after the call, and
    is idempotent: re-entry (a second engine, a tool importing another
    tool) returns the first call's verdict without re-initializing —
    ``jax.distributed.initialize`` raises on double init.

    Env parsing: ``FLEETX_NUM_PROCESSES`` unset/0 and ``FLEETX_PROCESS_ID``
    unset both mean "let JAX auto-detect" (TPU metadata); explicit args
    win over env.
    """
    global _initialized
    if _initialized is not None:
        return _initialized
    coordinator_address = coordinator_address or os.environ.get("FLEETX_COORDINATOR")
    distributed = bool(coordinator_address
                       or os.environ.get("FLEETX_MULTIHOST"))
    if distributed:
        # latch AFTER initialize returns: a raise (coordinator not up yet)
        # must leave the verdict unset so the caller's retry can try again
        # instead of silently running as a 1-process world
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes or int(os.environ.get("FLEETX_NUM_PROCESSES", 0)) or None,
            process_id=process_id if process_id is not None
            else (int(os.environ["FLEETX_PROCESS_ID"]) if "FLEETX_PROCESS_ID" in os.environ else None),
        )
        # tag every later log record with this process's rank — the first
        # thing an interleaved gang log needs (utils/log.py; single-process
        # worlds keep the prefix empty and the output byte-identical)
        set_rank_context(jax.process_index(), jax.process_count())
        logger.info("jax.distributed initialized: process %d/%d",
                    jax.process_index(), jax.process_count())
    _initialized = distributed
    return _initialized


def init_compile_cache() -> None:
    """Point JAX's persistent compilation cache at a path that never moves.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set the operator chose the place
    and JAX reads it itself: nothing is touched. Otherwise the cache lives
    at ``<checkout>/.jax_cache`` — the path is part of every entry's key, so
    it carries no temp name, pid or time. Every entry point that compiles
    (train, serve, eval, finetune) calls this once, next to
    ``init_dist_env``.
    """
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    jax.config.update("jax_compilation_cache_dir",
                      os.path.join(root, ".jax_cache"))


def mosaic_kernels(hlo_text: str) -> dict[str, int]:
    """Mosaic (Pallas TPU) kernel launches in a lowered program's text, by
    the ``name=`` each ``pallas_call`` carries (``ops/``)."""
    return dict(sorted(collections.Counter(
        re.findall(r'kernel_name = "([^"]+)"', hlo_text)).items()))


def log_compile(what: str, jitted, *args) -> None:
    """Compile ``jitted`` for ``args`` ahead of its first call and log the
    seconds it took and the Mosaic kernels in it — one line a reader of
    the log (``chip_smoke.py``) can parse. The call that follows reuses
    the executable, so nothing compiles twice. The optimised program's
    HLO modules (host objects) go to ``observability.trace.keep_compiled``,
    which prints and parses them only if ``compiled_programs()`` is asked
    for the table from instruction to device scope."""
    from fleetx_tpu.observability import trace

    t0 = time.time()
    lowered = jitted.lower(*args)
    kernels = mosaic_kernels(lowered.as_text())
    executable = lowered.compile().runtime_executable()
    logger.info("compiled %s in %.1fs; Mosaic kernels: %s", what,
                time.time() - t0, json.dumps(kernels))
    if executable is not None:
        trace.keep_compiled(executable.hlo_modules())


def set_seed(seed: int) -> jax.Array:
    """Return the root PRNG key for a run (reference ``env.py:27-46``).

    The reference derives distinct numpy/random/paddle seeds per rank plus
    model-parallel RNG trackers; with JAX a single root key suffices — streams
    are split functionally and device placement is handled by sharding.
    """
    import numpy as np
    import random

    random.seed(seed)
    np.random.seed(seed)
    return jax.random.PRNGKey(seed)


STREAMS = ("params", "dropout", "data", "sample")


def rng_streams(root: jax.Array, names: tuple[str, ...] = STREAMS) -> dict[str, jax.Array]:
    """Split the root key into named streams, stable under name ordering.

    Each stream key is derived by folding in a stable hash of the stream
    *name* (not its position), so adding/reordering names never perturbs
    existing streams — a reproducibility property the reference's stateful
    per-rank seed trackers (``env.py:41-46``) cannot offer.
    """
    import zlib

    return {name: jax.random.fold_in(root, zlib.crc32(name.encode()) & 0x7FFFFFFF)
            for name in names}


def get_world_size() -> int:
    return jax.device_count()


def get_local_world_size() -> int:
    return jax.local_device_count()
