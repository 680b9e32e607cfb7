"""On the chip: the three kernels the scan / multi-query family runs, none of
them new, each at a geometry nothing had compiled before — the paged decode
kernel with 20 query heads in ONE block over one key-value head of 128 (a
pool one lane tile wide) at three page sizes, the selective scan's one-token
step at 256 rows of a 26-layer state buffer, and the scan over a chunk —
each against its plain form, at the recipe's sizes, on random data, with the
time a call takes and the share of its HBM floor that is.

    chiprun --chips 1 -- python3 tools/ssm_mqa_kernels_on_chip.py

Why it exists: the CPU tests (``tests/test_ssm_mqa.py``) hold the kernels to
their plain forms in interpret mode; this is the COMPILED kernels. The
cell's ``served_logit_widest_gap`` is a whole-model number; this holds each
piece of kernel to its arithmetic alone, and it is where the page size was
chosen (``docs/ssm_mqa.md`` "The page"): a 16-token page of a one-tile pool
is 4 KB and a fold's cost is a page's, not a byte's. Contexts are capped at
12,287 tokens so that the 16-token pages' block table (256 rows x 768
entries) fits the scalar-prefetch memory at all: at the recipe's
``max_seq_len`` it does not, which the family's refusal says in words. Run
it after touching ``ops/selective_scan.py`` or ``ops/paged_attention.py``,
before reading the cell. One line a kernel; the numbers also go to
``chiprun_out/ssm_mqa_kernels.json``; exit 1 if any is outside its limit, 2
off the chip.
"""

from __future__ import annotations

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from fleetx_tpu.ops import paged_attention as PA  # noqa: E402
from fleetx_tpu.ops import selective_scan as SS  # noqa: E402
from fleetx_tpu.serving import programs  # noqa: E402

TOKENS, CHANNELS, STATES, SLOTS, LAYERS = 512, 5120, 16, 256, 26
HEADS, HD, POOL_TOKENS, LONGEST = 20, 128, 2_621_440, 12_288
PAGE_SIZES = (16, 64, 128)
HBM = 819e9
#: largest absolute difference allowed. The scan is float32 on both sides
#: and differs by the order of 16 sums and the exponential's last bits, on
#: outputs of size ~10. The attention kernel's two products take bfloat16
#: operands on both sides: what differs is the order of the softmax's sums
#: (contexts to 12k), the probabilities' rounding to bfloat16 against
#: different running maxima, and one rounding of the bfloat16 output
LIMITS = {"ssm_chunk": 1e-3, "ssm_decode": 1e-4, "paged_decode": 2e-2}


def timed(fn, *args, n=20):
    out = jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return out, 1e3 * (time.perf_counter() - t0) / n


def scan_data(rows):
    k = jax.random.split(jax.random.PRNGKey(51), 6)
    return (jax.random.normal(k[0], (rows, CHANNELS)),
            jax.nn.softplus(jax.random.normal(k[1], (rows, CHANNELS)) - 1.0),
            -jnp.exp(0.5 * jax.random.normal(k[2], (STATES, CHANNELS))),
            jax.random.normal(k[3], (rows, STATES)),
            jax.random.normal(k[4], (rows, STATES)),
            jax.random.normal(k[5], (CHANNELS,)))


def chunk_gap():
    x, delta, a, b, c, d = scan_data(TOKENS)
    s = jax.random.normal(jax.random.PRNGKey(6), (STATES, CHANNELS))
    delta = delta.at[TOKENS - 37:].set(0.0)         # a ragged chunk's tail
    y, h = jax.jit(SS.scan_chunk)(x, delta, a, b, c, d, s)
    (y0, h0), ms0 = timed(jax.jit(SS.scan_rule), x, delta, a, b, c, d, s, n=2)
    gap = max(float(jnp.abs(y - y0).max()), float(jnp.abs(h - h0).max()))

    # a kernel of a fraction of a millisecond is shorter than a dispatch:
    # LAYERS calls in ONE program, each from the state the last one left
    def layers(s):
        return jax.lax.fori_loop(0, LAYERS, lambda i, s: SS.scan_chunk(
            x, delta, a, b, c, d, s)[1], s)

    _, ms = timed(jax.jit(layers), s)
    floor = 1e3 * (3 * STATES * CHANNELS + TOKENS * (3 * CHANNELS + 2 * STATES)
                   ) * 4 / HBM
    return gap, {"ms": ms / LAYERS, "floor_ms": floor, "lax_scan_ms": ms0}


def step_gap():
    x, delta, a, b, c, d = scan_data(SLOTS)
    buf = jax.random.normal(jax.random.PRNGKey(7),
                            (LAYERS, SLOTS, STATES, CHANNELS))
    live = jnp.asarray(np.arange(SLOTS) % 37 != 4)
    rows = int(live.sum())
    out = {}
    for k in (True, False):
        y, new = jax.jit(lambda buf, k=k: SS.scan_step(
            buf, jnp.int32(3), x, delta, a, b, c, d, live, kernel=k))(buf)
        out[k] = (np.asarray(y), np.asarray(new[3]), np.asarray(new[4]))
        del new
    gap = max(float(np.abs(out[True][i] - out[False][i]).max())
              for i in range(3))

    # what a decode step does: all LAYERS layers' states in ONE program, in
    # place, the casts and the lane copies in front of each call with it
    def step(buf, kernel):
        def layer(i, carry):
            buf, y = carry
            y, buf = SS.scan_step(buf, i, x + y, delta, a, b, c, d, live,
                                  kernel=kernel)
            return buf, y
        return jax.lax.fori_loop(0, LAYERS, layer, (buf, jnp.zeros_like(x)))

    ms = {}
    for k in (True, False):
        fn = jax.jit(lambda buf, k=k: step(buf, k), donate_argnums=0)
        held, y = fn(buf + 0.0)
        jax.block_until_ready(y)
        t0 = time.perf_counter()
        for _ in range(10):
            held, y = fn(held)
        jax.block_until_ready(y)
        ms[k] = 1e3 * (time.perf_counter() - t0) / 10
        del held
    floor = 1e3 * (rows * (2 * STATES * CHANNELS + 3 * CHANNELS + 2 * STATES)
                   + STATES * CHANNELS + CHANNELS) * 4 / HBM
    return gap, {"ms": ms[True] / LAYERS, "floor_ms": floor, "rows": rows,
                 "xla_whole_layer_ms": ms[False] / LAYERS,
                 "a_step_of_26_ms": ms[True]}


def attention_gap(page):
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    rng = np.random.default_rng(51)
    per_row = LONGEST // page
    pages = POOL_TOKENS // page + 1
    q = jax.random.normal(ks[0], (SLOTS, HEADS, HD)).astype(jnp.bfloat16)
    # the cell's contexts: a prompt of its mix and a share of an output
    prompt = rng.choice([1024, 2048, 2048, 4096, 4096, 8192], size=SLOTS)
    out = rng.choice([2048, 4096, 8192], size=SLOTS) * rng.random(SLOTS)
    lens = np.minimum(prompt + out.astype(np.int64), LONGEST - 1
                      ).astype(np.int32)
    lens[::37], lens[1], lens[2] = -1, 0, LONGEST - 1
    pool_k = jax.random.normal(ks[1], (2, pages, page, HD), jnp.bfloat16)
    pool_v = jax.random.normal(ks[2], pool_k.shape, jnp.bfloat16)
    order = rng.permutation(pages - 1)[:SLOTS * ((pages - 1) // SLOTS)] + 1
    tables = np.zeros((SLOTS, per_row), np.int32)
    own = min(per_row, (pages - 1) // SLOTS)
    tables[:, :own] = order.reshape(SLOTS, -1)[:, :own]
    lens = np.minimum(lens, own * page - 1)
    tables, lens = jnp.asarray(tables), jnp.asarray(lens)
    geometry = dict(num_heads=HEADS, head_dim=HD, page_size=page,
                    pages_per_req=per_row, dtype=jnp.bfloat16,
                    num_kv_heads=1)
    assert not PA.paged_attention_refusal(batch=SLOTS, **geometry)
    fold = PA.fold_shape(**geometry)
    got, ms = timed(jax.jit(PA.paged_attention), q, pool_k, pool_v, tables,
                    lens, jnp.int32(1))

    def gathered(q, pool_k, pool_v):
        kd = pool_k[1, tables[:, :own]].reshape(SLOTS, -1, 1, HD)
        vd = pool_v[1, tables[:, :own]].reshape(SLOTS, -1, 1, HD)
        kp = jnp.broadcast_to(jnp.arange(own * page), (SLOTS, own * page))
        return programs.gathered_attention(
            q[:, None], kd, vd, kp, jnp.maximum(lens, 0)[:, None], None,
            jnp.bfloat16)[:, 0]

    want = np.asarray(jax.jit(gathered)(q, pool_k, pool_v), np.float32)
    got = np.asarray(got, np.float32)
    live = np.asarray(lens) >= 0
    assert not got[~live].any()
    keys = int((np.asarray(lens)[live] + 1).sum())
    floor = 1e3 * (2 * keys * HD * 2 + 2 * int(live.sum()) * HEADS * HD * 2
                   ) / HBM
    return float(np.abs(got[live] - want[live]).max()), {
        "ms": ms, "floor_ms": floor, "keys": keys, "pages_a_fold": fold[0],
        "page_bytes": page * HD * 2}


def main() -> int:
    if jax.devices()[0].platform != "tpu":
        print("this holds the COMPILED kernels: it runs on the chip")
        return 2
    bad, record = 0, {}
    runs = [("ssm_chunk", "ssm_chunk", chunk_gap),
            ("ssm_decode", "ssm_decode", step_gap)]
    runs += [(f"paged_decode 20 / 1 x 128, pages of {p}", "paged_decode",
              lambda p=p: attention_gap(p)) for p in PAGE_SIZES]
    for name, limit, fn in runs:
        got, facts = fn()
        ok = got <= LIMITS[limit]
        bad += not ok
        facts["roofline_pct"] = 100.0 * facts["floor_ms"] / facts["ms"]
        record[name] = dict(facts, gap=got, ok=ok)
        print(f"{name} vs its plain form: {got:.3g}  limit "
              f"{LIMITS[limit]:.3g}  {'ok' if ok else 'OUTSIDE'}; "
              + ", ".join(f"{k} {v:.4g}" for k, v in facts.items()),
              flush=True)
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "ssm_mqa_kernels.json"), "w") as f:
        json.dump(record, f, indent=1)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
