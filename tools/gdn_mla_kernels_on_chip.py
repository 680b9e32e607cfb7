"""On the chip: each Pallas kernel of the hybrid family (``gdn_chunk``,
``gdn_decode``, ``mla_paged_decode``) against its XLA path — and the chunked
rule against the plain recurrence — at the recipe's widths, on random data.

    chiprun --chips 1 -- python3 tools/gdn_mla_kernels_on_chip.py

Why it exists: the cell's ``served_logit_widest_gap`` is a whole-model
number, and the whole model is chaotic through near-tied routing (one held
expert in or out moves a token's logits by ~0.9 in the float32 reference's
own bfloat16 arithmetic: ``docs/gdn_mla.md`` "What the cell's check can
see"), so it sees a lower precision but not a small fault in one kernel.
This holds each kernel to its arithmetic where nothing routes. The CPU tests
(``tests/test_gdn_mla.py``) hold the same at toy widths, interpreted; this
is the compiled kernel at 64 heads of 128, 96 slots, a 512-token chunk and
rows of 900 .. 40,000 latents. Run it after touching ``ops/gated_delta.py``
or ``ops/mla_paged_attention.py``, before reading the cell. One line a
kernel; exit 1 if any is outside its limit, 2 off the chip.
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from fleetx_tpu.ops import gated_delta as G  # noqa: E402
from fleetx_tpu.ops import mla_paged_attention as P  # noqa: E402

SLOTS, HK, HV, DK, DV, CHUNK = 96, 32, 64, 128, 128, 512
HEADS, LANES, VALUE, PAGE, PAGES, PER_ROW = 64, 640, 512, 16, 60000, 2660
#: largest absolute differences allowed (float32 outputs of size ~0.2,
#: states ~0.7; the latent kernel's bfloat16 outputs of size ~0.05)
#: (a regime of the chunked rule, in brackets behind its name, has its
#: name's limit)
LIMITS = {"gdn_chunk kernel vs xla": 1e-5, "gdn_chunk vs recurrence": 1e-4,
          "gdn_decode kernel vs xla": 1e-6,
          "mla_paged_decode vs gathered": 5e-4}
#: `chunk_rule` at these widths before the triangular systems moved into
#: the kernel, us a call (my chip run, PR 50, the same call as the change's)
PARENT_US = 1913


def _gap(*pairs) -> float:
    return max(float(jnp.abs(a - b).max()) for a, b in pairs)


def _recurrence(q, k, v, g, beta, s0):
    def step(s, x):
        q_, k_, v_, g_, b_ = x
        o, s = G.recurrent_step(s[None], q_[None], k_[None], v_[None],
                                jnp.exp(g_)[None], b_[None])
        return s[0], o[0]

    s, o = jax.lax.scan(step, s0, (q, k, v, g, beta))
    return o, s


def _us_a_call(fn, *args, calls: int = 50) -> float:
    jax.block_until_ready(fn(*args))
    start = time.perf_counter()
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - start) / calls * 1e6


def gated_delta(ks) -> dict:
    """The chunked rule (kernel and XLA form) against the token-by-token
    recurrence, a 512-token chunk from a non-zero state in three regimes of
    decay and keys; the one-token kernel against its XLA path."""
    q = G.l2_normalise(jax.random.normal(ks[0], (CHUNK, HK, DK))) * DK ** -0.5
    k = G.l2_normalise(jax.random.normal(ks[1], (CHUNK, HK, DK)))
    v = jax.random.normal(ks[2], (CHUNK, HV, DV))
    g = -0.7 * jax.nn.softplus(jax.random.normal(ks[3], (CHUNK, HV)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (CHUNK, HV)))
    s0 = jax.random.normal(ks[5], (HV, DK, DV))
    rule = {kern: jax.jit(lambda *a, kern=kern: G.chunk_rule(*a, kernel=kern))
            for kern in (False, True)}
    recurrence = jax.jit(_recurrence)
    regimes = {
        "": (q, k, v, g, beta, s0),
        # a chunk of strong decays: differences of the log-decay only
        " (g = -30)": (q, k, v, jnp.full_like(g, -30.0), beta, s0),
        # the ill-conditioned system: one key all through, beta -> 1; no
        # decay, or the recurrence's 512 products of the chip's exp(g)
        # stand ~2e-3 from a chunk's one exp of the sum
        " (one key)": (q, jnp.broadcast_to(k[:1], k.shape), v,
                       jnp.zeros_like(g), jnp.full_like(beta, 0.999), s0),
    }
    read = {}
    for name, args in regimes.items():
        (o_x, s_x), (o_k, s_k) = rule[False](*args), rule[True](*args)
        o_r, s_r = recurrence(*args)
        assert bool(jnp.isfinite(o_k).all() & jnp.isfinite(s_k).all()), name
        read["gdn_chunk kernel vs xla" + name] = _gap((o_k, o_x), (s_k, s_x))
        read["gdn_chunk vs recurrence" + name] = _gap((o_k, o_r), (s_k, s_r))
    print(f"gdn_chunk: {_us_a_call(rule[True], q, k, v, g, beta, s0):.0f} us "
          f"a call of chunk_rule, kernel and the XLA around it (4 to a "
          f"prefill chunk; {PARENT_US} at PR 49, the triangular systems in "
          f"XLA); the XLA form {_us_a_call(rule[False], q, k, v, g, beta, s0):.0f}")
    states = jax.random.normal(ks[6], (4, SLOTS, HV, DK, DV))
    live = jnp.arange(SLOTS) % 5 != 0
    one = {kern: jax.jit(lambda st, kern=kern: G.gdn_decode(
        st, jnp.int32(2), q[:SLOTS], k[:SLOTS], v[:SLOTS],
        jnp.exp(g[:SLOTS]), beta[:SLOTS], live, kernel=kern))
        for kern in (False, True)}
    (o1, st1), (o2, st2) = one[False](states), one[True](states)
    others = jnp.array([0, 1, 3])
    assert bool((st2[2][~live] == states[2][~live]).all()), \
        "gdn_decode wrote a dead row's state"
    assert bool((st2[others] == states[others]).all()), \
        "gdn_decode wrote another layer's states"
    return {**read, "gdn_decode kernel vs xla": _gap((o1, o2), (st1, st2))}


def latent_decode(ks) -> dict:
    pool = (0.3 * jax.random.normal(ks[7], (1, PAGES, PAGE, LANES))
            ).astype(jnp.bfloat16)
    q = jax.random.normal(ks[8], (SLOTS, HEADS, LANES)).astype(jnp.bfloat16)
    lens = np.random.default_rng(0).integers(900, 9000, SLOTS).astype(np.int32)
    lens[::7], lens[3] = -1, 40000      # empty slots, one very long row
    tables, nxt = np.zeros((SLOTS, PER_ROW), np.int32), 1
    for b in range(SLOTS):
        n = 0 if lens[b] < 0 else lens[b] // PAGE + 1
        if nxt + n >= PAGES:
            n, lens[b] = 0, -1
        tables[b, :n] = np.random.default_rng(b).permutation(
            np.arange(nxt, nxt + n))
        nxt += n
    got = jax.jit(lambda *a: P.mla_paged_decode(
        *a, value_width=VALUE, scale=0.05))(
        q, pool, jnp.asarray(tables), jnp.asarray(lens), jnp.int32(0))
    # the gathered view holds every row's keys at once: the short rows only
    want = jax.jit(lambda *a: P.gathered_decode(
        *a, value_width=VALUE, scale=0.05))(
        q, pool, jnp.asarray(tables[:, :640]),
        jnp.asarray(np.minimum(lens, 10000)), jnp.int32(0))
    rows = np.where((lens >= 0) & (lens < 10000))[0]
    assert bool((got[lens < 0] == 0).all()), "an empty slot's output is not 0"
    assert not bool(jnp.isnan(got).any()), "nan (the 40,000-key row?)"
    return {"mla_paged_decode vs gathered": _gap((got[rows].astype(
        jnp.float32), want[rows].astype(jnp.float32)))}


def main() -> int:
    if jax.default_backend() != "tpu":
        print(f"needs the chip (backend {jax.default_backend()}): the CPU "
              "tests hold the interpreted kernels at toy widths")
        return 2
    ks = jax.random.split(jax.random.PRNGKey(1), 9)
    read = {**gated_delta(ks), **latent_decode(ks)}
    outside = 0
    for name, value in read.items():
        limit = LIMITS[name.split(" (")[0]]
        outside += value > limit
        print(f"{name}: {value:.3e}  limit {limit:.0e}  "
              f"{'ok' if value <= limit else 'OUTSIDE'}")
    return int(outside > 0)


if __name__ == "__main__":
    sys.exit(main())
