"""Keep a rehearsal's compile-cache settings out of the tests that follow.

``run_cell`` sets JAX's persistent-cache thresholds to zero and the program
points the cache at ``<checkout>/.jax_cache``; in a benchmark run the process
ends there, in a test worker the settings outlive the test. Every program a
later test compiles in that worker is then stored, and on the next run of the
tests LOADED, and an 8-device CPU program loaded from the cache deadlocks in
its collectives (``tests/test_ring_attention.py`` aborts at XLA's 40 s
rendezvous timeout; a checkout whose cache is empty never sees it)."""

import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache

SETTINGS = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")


@pytest.fixture(autouse=True)
def compile_cache_as_found():
    before = {k: getattr(jax.config, k) for k in SETTINGS}
    yield
    moved = [k for k in SETTINGS if getattr(jax.config, k) != before[k]]
    for k in moved:
        jax.config.update(k, before[k])
    if moved:
        compilation_cache.reset_cache()     # the choice to use it is latched
