"""On the CPU, at the cell's widths: what the routing does to the logits of
``gigachat3.5-432b-a28b`` when the arithmetic is bfloat16 — why the cell's
``served_logit_widest_gap`` reads 0.8–1.8 and what it can therefore see.

    JAX_PLATFORMS=cpu python3 tools/gdn_mla_routing_flips.py \\
        --seed 4200000701 [--tokens 2048] [--float8] [--program]

One row of random tokens through the plain reference
(``benchmarks/reference/gigachat35_ref.py``, the benchmark's seeded weights)
in float32 and with its products in bfloat16, EVERY position's logits kept,
and beside them the experts each expert layer's router chose in both. One
line: how far the bfloat16 logits leave the float32 ones (median, 90th and
99th percentile, largest position), at how many positions an expert layer
handed the token another set of HELD experts, how far the logits move there
and elsewhere, and the widest gap of the bfloat16 argmax below the float32
best (the cell's number, for the reference in the program's place).
``--float8``: the same for the control's precision. ``--program``: the
serving program's prefill (XLA paths, bfloat16, 512-token chunks, every
row's logits) on the same row, against the same float32 logits.

~5 min a precision for 2,048 tokens on 8 cores (~40 GB); nothing here needs
the chip and no number it prints is a device metric. ``docs/gdn_mla.md``
"What the cell's check can see" has the readings of PR 42's review round.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks import weights  # noqa: E402
from benchmarks.manifest import Manifest, load_module  # noqa: E402

CONFIG, CHUNK, PAGE = "gigachat3.5-432b-a28b", 512, 16


def _quantiles(v) -> str:
    return "median %.3f p90 %.3f p99 %.3f max %.3f" % (
        np.median(v), np.quantile(v, .9), np.quantile(v, .99), v.max())


def _gap_of_argmax(want, got) -> float:
    return float((want.max(-1)
                  - want[np.arange(len(want)), got.argmax(-1)]).max())


def reference(ref, sizes, source, tokens, precision):
    """``(logits [S, vocab], [held experts chosen [S, k] a expert layer])``
    of the reference's forward in ``precision``: ``logits_streamed``'s own
    loop, with each expert layer's choice read off its input on the way."""
    spec, key = ref.weight_spec(sizes), ref._sizes_key(sizes)
    first, held = (int(sizes[name]) for name in ("first_expert_held",
                                                 "n_routed_experts"))
    k = int(sizes["num_experts_per_tok"])

    @jax.jit
    def chosen(x, w_norm, router, bias):
        s = jax.nn.sigmoid(jnp.einsum(
            "sh,he->se", ref._norm(x, w_norm, sizes), router,
            precision=jax.lax.Precision.HIGHEST))
        ids = jax.lax.top_k(s + bias[None], k)[1]
        return jnp.sort(jnp.where((ids >= first) & (ids < first + held),
                                  ids, -1), axis=-1)

    x, routes = source.leaf("emb")[tokens[0]], []
    for p, at, latent, dense in ref._layers(sizes):
        for mixer, names, flag in ((True, ref._MIXER[p[0]], latent),
                                   (False, ref._FEED[p[1]], dense)):
            lw = {n: source.leaf(f"{p}_{n}", at) for n in names
                  if f"{p}_{n}" in spec}
            if not mixer and not dense:
                routes.append(np.asarray(chosen(
                    x, lw["norm_f_pre"], lw["router"], lw["bias"])))
            x = ref._jitted_half(key, mixer, flag, precision)(x, lw)
            del lw
    lg = ref._jitted_head(key, precision)(x, source.leaf("norm_f"),
                                          source.leaf("head"))
    return np.asarray(lg[0]), routes


def program_prefill(sizes, ref, seed, tokens):
    """The serving program's logits at every position of ``tokens``: its
    prefill, a chunk at a time, XLA paths."""
    from fleetx_tpu.serving import gdn_mla as S, registry
    from fleetx_tpu.utils import config as C

    cfg = C.get_config(sizes["serve"]["recipe"], [], num_devices=1)
    mc, tmpl = registry.served_template(cfg)
    paths = sizes["param_paths"]
    made = weights.make(ref.weight_spec(sizes), seed, dtypes={
        n: l.dtype for n, l in weights.program_paths(paths, tmpl).items()})
    params = weights.to_program_tree(made, paths, tmpl)
    del made
    n_pages = len(tokens) // PAGE
    table = jnp.asarray(1 + np.arange(n_pages, dtype=np.int32))[None]

    @jax.jit
    def chunk(params, pool, state, tail, toks, start):
        x, cache, _ = S._forward(
            params, mc, toks, start + jnp.arange(CHUNK, dtype=jnp.int32),
            (pool, state, tail), table, jnp.int32(0), start,
            jnp.int32(CHUNK), decode=False, kernels=False,
            latent_kernel=False, moe_kernel="moe_gmm_prefill")
        return (*cache, S._logits(params, x))

    cache = S.init_cache(mc, num_pages=1 + n_pages, page_size=PAGE,
                         max_batch=1)
    out = []
    for c in range(len(tokens) // CHUNK):
        *cache, lg = chunk(params, *cache,
                           jnp.asarray(tokens[c * CHUNK:(c + 1) * CHUNK]),
                           jnp.int32(c * CHUNK))
        out.append(np.asarray(lg))
    return np.concatenate(out)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--tokens", type=int, default=2048,
                    help="a multiple of 512")
    ap.add_argument("--float8", action="store_true")
    ap.add_argument("--program", action="store_true")
    args = ap.parse_args()
    assert args.tokens % CHUNK == 0
    m = Manifest(ROOT)
    sizes = m.config(CONFIG)
    ref = load_module(m.reference_path("gigachat35_ref"))
    source = weights.Source(ref.weight_spec(sizes), args.seed)
    tokens = np.random.default_rng(args.seed).integers(
        0, sizes["vocab_size"], (1, args.tokens)).astype(np.int32)
    t0 = time.time()
    want, routed = reference(ref, sizes, source, jnp.asarray(tokens),
                             "float32")
    top = np.sort(want, -1)
    print(f"seed {args.seed}, {args.tokens} positions: float32 logits std "
          f"{want.std():.2f}, first over second median "
          f"{np.median(top[:, -1] - top[:, -2]):.3f} "
          f"({time.time() - t0:.0f} s)", flush=True)
    for precision in ("bfloat16",) + (("float8",) if args.float8 else ()):
        got, routes = reference(ref, sizes, source, jnp.asarray(tokens),
                                precision)
        moved = np.abs(got - want).max(-1)
        flipped = np.any([(a != b).any(-1) for a, b in zip(routed, routes)],
                         axis=0)
        print(f"reference in {precision}: max|dlogit| a position "
              f"{_quantiles(moved)}; another set of held experts in some "
              f"layer at {flipped.sum()} positions "
              f"({100 * flipped.mean():.1f} %), max|dlogit| there median "
              f"{np.median(moved[flipped]):.3f}, elsewhere "
              f"{np.median(moved[~flipped]):.3f}; widest gap of its argmax "
              f"{_gap_of_argmax(want, got):.3f} ({time.time() - t0:.0f} s)",
              flush=True)
    if args.program:
        got = program_prefill(sizes, ref, args.seed, tokens[0])
        print(f"program (prefill, XLA paths): max|dlogit| a position "
              f"{_quantiles(np.abs(got - want).max(-1))}; widest gap of its "
              f"argmax {_gap_of_argmax(want, got):.3f} "
              f"({time.time() - t0:.0f} s)", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
