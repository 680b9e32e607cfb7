"""On the chip: the paged decode kernel at the short-convolution family's
head geometry — 4 query heads to each of 8 key-value heads of 64, half a
lane tile, a 512-lane pool — against the gathered view, at the recipe's
sizes, on random data.

    chiprun --chips 1 -- python3 tools/conv_moe_kernels_on_chip.py

Why it exists: at this geometry the kernel spreads a 64-wide query over the
block's 512 lanes, and folds each row's own lanes out of the accumulator
again, by two products with 0 / 1 matrices (``ops/paged_attention.py``:
Mosaic has no concatenation at half-tile lane offsets). The CPU tests
(``tests/test_conv_moe.py``) hold the kernel to the gathered view in
interpret mode; this is the COMPILED kernel at 256 rows, a pool of 32,769
pages and contexts of 0 .. 3,583 tokens, in bfloat16 and in float32. The
cell's ``served_logit_widest_gap`` is a whole-model number; this holds the
one new piece of kernel to its arithmetic alone. Run it after touching
``ops/paged_attention.py``, before reading the cell. One line a dtype; exit
1 if either is outside its limit, 2 off the chip.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from fleetx_tpu.ops import paged_attention as PA  # noqa: E402
from fleetx_tpu.serving import programs  # noqa: E402

ROWS, HEADS, KV, HD, PAGE, PER_ROW, LAYERS = 256, 32, 8, 64, 16, 224, 2
#: largest absolute difference allowed. float32 differs by the order of its
#: sums alone (the chip reads 8.5e-7: PERF.md section 6, PR 44) and holds
#: the lanes' spread and fold, which are the same code in both dtypes.
#: bfloat16: both sides round their OUTPUT to 8 bits, and a row with a few
#: keys has outputs of size 2 .. 4, where one step of bfloat16 is 2^-6: the
#: chip reads 2^-7 = 0.0078, one step between 1 and 2; a fault in the lanes
#: would read ~1
LIMITS = {"bfloat16": 2e-2, "float32": 2e-5}
#: pages of the pool: the recipe's in bfloat16; what fits beside the
#: gathered view's float32 copies in float32
PAGES = {"bfloat16": 32769, "float32": 8193}


def gap(dtype) -> float:
    name = jnp.dtype(dtype).name
    assert not PA.paged_attention_refusal(
        num_heads=HEADS, head_dim=HD, page_size=PAGE, pages_per_req=PER_ROW,
        dtype=dtype, num_kv_heads=KV)
    ks = jax.random.split(jax.random.PRNGKey(44), 4)
    rng = np.random.default_rng(44)
    pages = PAGES[name]
    per_row = min(PER_ROW, (pages - 1) // ROWS)
    q = jax.random.normal(ks[0], (ROWS, HEADS, HD)).astype(dtype)
    pool_k = jax.random.normal(ks[1], (LAYERS, pages, PAGE, KV * HD),
                               dtype)
    pool_v = jax.random.normal(ks[2], pool_k.shape, dtype)
    # every row its own pages, in a shuffled order; a few rows empty, one
    # at its first token, one at its table's last
    order = rng.permutation(pages - 1)[:ROWS * per_row] + 1
    tables = np.zeros((ROWS, PER_ROW), np.int32)
    tables[:, :per_row] = order.reshape(ROWS, per_row)
    lens = rng.integers(0, per_row * PAGE, size=ROWS).astype(np.int32)
    lens[::37] = -1
    lens[1], lens[2] = 0, per_row * PAGE - 1
    tables, lens = jnp.asarray(tables), jnp.asarray(lens)
    kernel = jax.jit(PA.paged_attention)

    def gathered(q, pool_k, pool_v, tables, lens, layer):
        view = tables[:, :per_row]
        kd = pool_k[layer, view].reshape(ROWS, -1, KV, HD)
        vd = pool_v[layer, view].reshape(ROWS, -1, KV, HD)
        kp = jnp.broadcast_to(jnp.arange(kd.shape[1]), (ROWS, kd.shape[1]))
        with jax.default_matmul_precision("highest"):
            return programs.gathered_attention(
                q[:, None], kd, vd, kp, jnp.maximum(lens, 0)[:, None], None,
                dtype)[:, 0]

    worst = 0.0
    for layer in range(LAYERS):
        got = np.asarray(kernel(q, pool_k, pool_v, tables, lens,
                                jnp.int32(layer)), np.float32)
        want = np.asarray(jax.jit(gathered)(q, pool_k, pool_v, tables, lens,
                                            jnp.int32(layer)), np.float32)
        live = np.asarray(lens) >= 0
        assert not got[~live].any(), "an empty slot's row is not zero"
        worst = max(worst, float(np.abs(got[live] - want[live]).max()))
    return worst


def main() -> int:
    if jax.devices()[0].platform != "tpu":
        print("this holds the COMPILED kernel: it runs on the chip")
        return 2
    bad = 0
    for dtype in (jnp.bfloat16, jnp.float32):
        name = jnp.dtype(dtype).name
        got = gap(dtype)
        ok = got <= LIMITS[name]
        bad += not ok
        print(f"paged_decode 32 / 8 x 64 vs gathered, {name}: {got:.3g}  "
              f"limit {LIMITS[name]:.3g}  {'ok' if ok else 'OUTSIDE'}",
              flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
