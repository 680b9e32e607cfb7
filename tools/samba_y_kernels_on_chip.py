"""On the chip: the three kernels the decoder-hybrid-decoder family brings
or bends — the selective scan over a chunk (``ssm_chunk``), its one-token
step over the slots (``ssm_decode``), and the paged decode kernel scoring
two maps a head pair through zero-half queries with a ``scale`` — each
against its plain form, at the recipe's sizes, on random data, with the time
a call takes.

    chiprun --chips 1 -- python3 tools/samba_y_kernels_on_chip.py

Why it exists: the CPU tests (``tests/test_samba_y.py``) hold the kernels to
their plain forms in interpret mode; this is the COMPILED kernels at 512
tokens x 5,120 channels x 16 states, 64 slots, and 64 rows of 40 query heads
over 10 key-value pairs of 128 lanes with contexts to 16k. The cell's
``served_logit_widest_gap`` is a whole-model number; this holds each new
piece of kernel to its arithmetic alone. Run it after touching
``ops/selective_scan.py`` or ``ops/paged_attention.py``, before reading the
cell. One line a kernel; exit 1 if any is outside its limit, 2 off the chip.
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from fleetx_tpu.models.samba_y.model import diff_queries  # noqa: E402
from fleetx_tpu.ops import paged_attention as PA  # noqa: E402
from fleetx_tpu.ops import selective_scan as SS  # noqa: E402
from fleetx_tpu.serving import programs  # noqa: E402

TOKENS, CHANNELS, STATES, SLOTS, LAYERS = 512, 5120, 16, 64, 9
HEADS, HD, PAGE, PER_ROW, PAGES = 40, 64, 16, 1024, 40961
#: largest absolute difference allowed. The scan is float32 on both sides
#: and differs by the order of 16 sums and the exponential's last bits, on
#: outputs of size ~10. The attention kernel's two products take bfloat16
#: operands on both sides, its output is float32: what differs is the order
#: of the softmax's sums (contexts to 16k) and the probabilities' rounding
#: to bfloat16 against different running maxima
LIMITS = {"ssm_chunk": 1e-3, "ssm_decode": 1e-4, "paged_decode two maps": 2e-2,
          "paged_decode_window two maps": 2e-2}


def timed(fn, *args, n=20):
    out = jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return out, 1e3 * (time.perf_counter() - t0) / n


def scan_data(rows):
    k = jax.random.split(jax.random.PRNGKey(48), 7)
    return (jax.random.normal(k[0], (rows, CHANNELS)),
            jax.nn.softplus(jax.random.normal(k[1], (rows, CHANNELS)) - 1.0),
            -jnp.exp(0.5 * jax.random.normal(k[2], (STATES, CHANNELS))),
            jax.random.normal(k[3], (rows, STATES)),
            jax.random.normal(k[4], (rows, STATES)),
            jax.random.normal(k[5], (CHANNELS,)),
            jax.random.normal(k[6], (STATES, CHANNELS)))


def chunk_gap():
    x, delta, a, b, c, d, s = scan_data(TOKENS)
    delta = delta.at[TOKENS - 37:].set(0.0)         # a ragged chunk's tail
    (y, h), ms = timed(jax.jit(SS.scan_chunk), x, delta, a, b, c, d, s)
    (y0, h0), ms0 = timed(jax.jit(SS.scan_rule), x, delta, a, b, c, d, s, n=2)
    gap = max(float(jnp.abs(y - y0).max()), float(jnp.abs(h - h0).max()))
    return gap, f"{ms:.3f} ms a call (the lax.scan {ms0:.1f})"


def step_gap():
    x, delta, a, b, c, d, _ = scan_data(SLOTS)
    buf = jax.random.normal(jax.random.PRNGKey(7),
                            (LAYERS, SLOTS, STATES, CHANNELS))
    live = jnp.asarray(np.arange(SLOTS) % 9 != 4)
    fns = {k: jax.jit(lambda buf, layer, *args, k=k: SS.scan_step(
        buf, layer, *args, kernel=k)) for k in (True, False)}
    out, ms = {}, {}
    for k, fn in fns.items():
        out[k], ms[k] = timed(fn, buf, jnp.int32(3), x, delta, a, b, c, d,
                              live)
    gap = max(float(jnp.abs(out[True][0] - out[False][0]).max()),
              float(jnp.abs(out[True][1] - out[False][1]).max()))
    return gap, f"{ms[True]:.3f} ms a call in place (XLA, whole layer " \
                f"{ms[False]:.3f})"


def attention_gap(window):
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    rng = np.random.default_rng(48)
    lanes = HEADS // 2 * HD
    q = diff_queries(jax.random.normal(ks[0], (SLOTS, HEADS, HD))
                     ).astype(jnp.bfloat16)
    if window is None:
        pages, per_row = PAGES, (PAGES - 1) // SLOTS
        lens = rng.integers(0, per_row * PAGE, size=SLOTS).astype(np.int32)
        lens[2] = per_row * PAGE - 1
    else:
        per_row = (window + 512) // PAGE
        pages = 1 + SLOTS * per_row
        lens = rng.integers(0, 20000, size=SLOTS).astype(np.int32)
    lens[::17], lens[1] = -1, 0
    pool_k = jax.random.normal(ks[1], (2, pages, PAGE, lanes), jnp.bfloat16)
    pool_v = jax.random.normal(ks[2], pool_k.shape, jnp.bfloat16)
    lens = jnp.asarray(lens)
    qpos = jnp.maximum(lens, 0)[:, None]
    if window is None:
        order = rng.permutation(pages - 1)[:SLOTS * per_row] + 1
        tables = np.zeros((SLOTS, PER_ROW), np.int32)
        tables[:, :per_row] = order.reshape(SLOTS, per_row)
        tables = jnp.asarray(tables)
        kernel = jax.jit(lambda *a: PA.paged_attention(*a, scale=0.125))
        args = (q.astype(jnp.float32), pool_k, pool_v, tables, lens,
                jnp.int32(1))
        view = tables[:, :per_row]
        kp = jnp.broadcast_to(jnp.arange(per_row * PAGE),
                              (SLOTS, per_row * PAGE))
    else:
        first = jnp.asarray(1 + np.arange(SLOTS) * per_row, jnp.int32)
        kernel = jax.jit(lambda *a: PA.paged_attention(
            *a, window=window, ring_pages=per_row, scale=0.125))
        args = (q.astype(jnp.float32), pool_k, pool_v, first, lens,
                jnp.int32(1))
        view, kp = programs.ring_view(first, lens, per_row, PAGE)

    def gathered(q, pool_k, pool_v):
        kd = pool_k[1, view].reshape(SLOTS, -1, HEADS // 4, 2 * HD)
        vd = pool_v[1, view].reshape(SLOTS, -1, HEADS // 4, 2 * HD)
        return programs.gathered_attention(
            q[:, None], kd, vd, kp, qpos, window, jnp.bfloat16, scale=0.125,
            out_dtype=jnp.float32)[:, 0]

    got, ms = timed(kernel, *args)
    want = np.asarray(jax.jit(gathered)(q, pool_k, pool_v))
    got = np.asarray(got)
    live = np.asarray(lens) >= 0
    assert got.dtype == np.float32 and not got[~live].any()
    keys = int(np.minimum(np.asarray(lens)[live] + 1,
                          window or 10 ** 9).sum())
    floor = 2 * keys * lanes * 2 / 819e9 * 1e3
    return float(np.abs(got[live] - want[live]).max()), \
        f"{ms:.3f} ms a call, {keys} keys: HBM floor {floor:.3f} ms"


def main() -> int:
    if jax.devices()[0].platform != "tpu":
        print("this holds the COMPILED kernels: it runs on the chip")
        return 2
    bad = 0
    for name, fn in (("ssm_chunk", chunk_gap), ("ssm_decode", step_gap),
                     ("paged_decode two maps", lambda: attention_gap(None)),
                     ("paged_decode_window two maps",
                      lambda: attention_gap(512))):
        got, said = fn()
        ok = got <= LIMITS[name]
        bad += not ok
        print(f"{name} vs its plain form: {got:.3g}  limit "
              f"{LIMITS[name]:.3g}  {'ok' if ok else 'OUTSIDE'}; {said}",
              flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
