"""On the chip: the decoder-hybrid-decoder family's two programs computed in
FLOAT32 at the configuration's widths — chunked prefill, then decode through
the pool, the rings, the states and the tails, on the kernels — against the
plain float32 reference, position by position, beside the same programs in
the served bfloat16.

    chiprun --chips 1 -- python3 tools/samba_y_float32_on_chip.py

Why it exists: the cell's ``served_logit_widest_gap`` holds a bfloat16
program to a float32 reference through 32 layers, and under seeded weights a
rounding grows on the way. A gap there can be rounding grown large or a fault
at the real widths that a toy never meets (a page walk past some length, a
ring's wrap, a fold's tail): the two look alike in bfloat16. In float32 they
do not — rounding starts at 1e-7 and a fault stays the size it is. So: the
same seeded weights (``benchmarks/weights.py``, one leaf scaled as the
family file and the reference each scale it) on both sides, the tokens
GIVEN (prompt and continuation are random ids: every program follows the same
row, so one reference pass judges them all), logits compared at the prompt's
last row and at every decode step. One line a (case, dtype): the largest
absolute difference of a logit, its root mean square, and the widest gap by
which the program's own first choice lies below the reference's best. Exit 1
if a float32 line is outside ``--limit``, 2 off the chip (``--cpu`` rehearses
at small ``--hidden`` / ``--layers``). ``--as-drawn`` leaves the one scaled
leaf as the harness draws it, on both sides: the regime in which a rounding
grows until bfloat16 reads like float8, and in which float32 still agrees.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

CONFIG = "benchmarks/configs/phi-4-mini-flash-reasoning.json"
CHUNK, PAGE, SLOTS, SLOT = 512, 16, 2, 1


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=4800000901)
    ap.add_argument("--cases", default="700+24,17000+48",
                    help="prompt+steps, comma separated")
    ap.add_argument("--dtypes", default="bfloat16,float32")
    ap.add_argument("--layers", type=int, default=0, help="0: as published")
    ap.add_argument("--hidden", type=int, default=0, help="0: as published")
    ap.add_argument("--vocab", type=int, default=0, help="0: the file's")
    ap.add_argument("--gather", action="store_true",
                    help="the gathered view and lax.scan, not the kernels")
    ap.add_argument("--limit", type=float, default=0.02,
                    help="largest float32 logit difference allowed")
    ap.add_argument("--as-drawn", action="store_true",
                    help="every leaf as the harness draws it: neither side "
                         "applies its WEIGHT_SCALE_LOG2")
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    if jax.default_backend() != "tpu" and not args.cpu:
        print(f"backend {jax.default_backend()!r}: this reads the chip "
              "(--cpu rehearses)", file=sys.stderr)
        return 2
    from benchmarks import weights
    from benchmarks.manifest import load_module
    from fleetx_tpu.models.samba_y import model as M
    from fleetx_tpu.models.samba_y.config import SambaYConfig
    from fleetx_tpu.serving import samba_y as S
    from fleetx_tpu.serving.decode import SamplingParams

    with open(os.path.join(ROOT, CONFIG)) as f:
        sizes = json.load(f)
    if args.hidden:     # a rehearsal's widths: heads of 64 as published
        sizes.update(hidden_size=args.hidden,
                     intermediate_size=4 * args.hidden,
                     num_attention_heads=args.hidden // 64,
                     num_key_value_heads=args.hidden // 128)
        sizes["assumed"] = dict(sizes["assumed"], dt_rank=args.hidden // 16)
    if args.layers:
        sizes["num_hidden_layers"] = args.layers
    if args.vocab:
        sizes["vocab_size"] = args.vocab
    ref = load_module(os.path.join(
        ROOT, "benchmarks/reference", sizes["reference"] + ".py"))
    family = load_module(os.path.join(
        ROOT, "benchmarks/families/SambaYModule.py"))
    if args.as_drawn:
        ref.WEIGHT_SCALE_LOG2 = {}
    spec, paths = ref.weight_spec(sizes), sizes["param_paths"]
    cases = [tuple(map(int, c.split("+"))) for c in args.cases.split(",")]
    pages = -(-(max(p + n for p, n in cases) + 1) // PAGE)
    rng = np.random.default_rng(args.seed % 2 ** 32)
    rows = [rng.integers(0, sizes["vocab_size"], size=p + n + 1).tolist()
            for p, n in cases]

    def serve(cfg, params, row, plen, steps, kernels):
        fns = S.make_step_fns(cfg, prefill_chunk=CHUNK, page_size=PAGE,
                              sampling=SamplingParams(), kernels=kernels)
        cache = S.init_cache(cfg, num_pages=1 + SLOTS * pages, page_size=PAGE,
                             max_batch=SLOTS, prefill_chunk=CHUNK)
        table = np.zeros((SLOTS, pages), np.int32)
        table[SLOT] = 1 + SLOT * pages + np.arange(pages)
        key, got, pos = jax.random.PRNGKey(0), [], 0
        while pos < plen:
            part = row[pos:min(pos + CHUNK, plen)]
            toks = np.zeros((1, CHUNK), np.int32)
            toks[0, :len(part)] = part
            *cache, _, lg = fns["prefill"](
                params, *cache, toks, table[SLOT:SLOT + 1], np.int32(pos),
                np.int32(len(part)), key, np.uint32(0), np.int32(SLOT))
            pos += len(part)
        got.append(np.asarray(lg[0], np.float32))
        lens = np.full((SLOTS,), -1, np.int32)
        last = np.zeros((SLOTS,), np.int32)
        for i in range(steps):      # the GIVEN token, never the sampled one
            lens[SLOT], last[SLOT] = plen + i, row[plen + i]
            *cache, _, lg, _ = fns["decode"](
                params, *cache, last, np.int32(-1), np.zeros((1,), np.int32),
                table, lens, key, np.uint32(0))
            got.append(np.asarray(lg[SLOT], np.float32))
        return np.stack(got)

    served = {}
    for name in args.dtypes.split(","):
        dtype = jnp.dtype(name)
        over = {k: sizes[k] for k in (
            "vocab_size", "hidden_size", "intermediate_size",
            "num_hidden_layers", "num_attention_heads",
            "num_key_value_heads")}
        cfg = SambaYConfig(**over, dt_rank=sizes["assumed"]["dt_rank"],
                           dtype=dtype)
        tmpl = M.served_template(cfg)
        made = weights.make(spec, args.seed, dtypes={
            n: l.dtype for n, l in weights.program_paths(paths, tmpl).items()})
        params = weights.to_program_tree(made, paths, tmpl)
        if not args.as_drawn:
            params = family.seeded(params)
        del made
        kernels = not args.gather and not S.kernel_refusal(
            cfg, page_size=PAGE, pages_per_req=pages, prefill_chunk=CHUNK)
        with jax.default_matmul_precision(
                "highest" if name == "float32" else "default"):
            for (plen, steps), row in zip(cases, rows):
                t0 = time.time()
                served[name, plen] = serve(cfg, params, row, plen, steps,
                                           kernels)
                print(f"served {name} prompt {plen} + {steps} steps, "
                      f"kernels {bool(kernels)}: {time.time() - t0:.0f} s",
                      file=sys.stderr, flush=True)
        del params
        jax.clear_caches()

    source, bad = weights.Source(spec, args.seed), False
    for (plen, steps), row in zip(cases, rows):
        wide = np.zeros((1, -(-len(row) // 128) * 128), np.int32)
        wide[0, :len(row)] = row
        want = np.asarray(ref.logits_streamed(
            source.leaf, sizes, jnp.asarray(wide)))[0, plen - 1:plen + steps]
        best = want.max(-1)
        for name in args.dtypes.split(","):
            got = served[name, plen]
            d = np.abs(got - want)
            gap = best - want[np.arange(len(want)), got.argmax(-1)]
            ok = name != "float32" or d.max() <= args.limit
            bad = bad or not ok
            print(f"prompt {plen} + {steps} steps, {name}: logits of standard "
                  f"deviation {want.std():.3f}; largest |difference| "
                  f"{d.max():.3g} (at the prompt's last row {d[0].max():.3g},"
                  f" over the steps {d[1:].max() if steps else 0:.3g}), rms "
                  f"{float(np.sqrt((d ** 2).mean())):.3g}; its first choices"
                  f" lie at most {gap.max():.3g} below the reference's best"
                  + ("" if name != "float32" else
                     f"  limit {args.limit:g}  {'ok' if ok else 'OUTSIDE'}"),
                  flush=True)
    return int(bad)


if __name__ == "__main__":
    sys.exit(main())
