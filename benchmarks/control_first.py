"""``benchmarks/run.py`` with the control's choice taken FIRST: for a cell
whose check rows are too wide for two logits arrays to stand on the chip.

``check.served_logit_gaps`` with a ``chooser`` runs the float32 forward of a
sample, keeps its ``[width, vocab]`` logits, and then runs the control's
forward beside them. At ``smallthinker-serve-reason-closed``'s 12,288 x
151,936 that is 2 x 7.5 GB of float32, so ``benchmarks/run.py --control
float8`` cannot run there (PERF.md section 7, item 17). Here the control's
forward runs first and only its argmax (``[width]`` ids) is kept; then the
float32 forward; the gaps are ``check._gaps_below_best`` of the same logits
against the same ids, so the numbers are the harness's, in another order (a
test at toy widths holds them equal for both choosers). Everything else is
``run.run_cell`` unedited: the same arguments, the same result line.

    python3 benchmarks/control_first.py --workload <cell> --seed <n> \\
        --seconds 40 --trace 0 --control float8

The driver never runs this file; the builder does, to read the control that
has to fail a serve cell's limit (``check.why`` of the cell's configuration).
After the run it adds one stderr line of the program's own expert counters
(``counters: ...``), where the program has them.
"""

from __future__ import annotations

import os
import sys
import time

T_PROCESS_START = time.monotonic()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

from benchmarks import check, run  # noqa: E402

COUNTERS = ("serving_decode_steps", "serving_moe_passes_total",
            "serving_moe_pairs_held_total", "serving_moe_pairs_total",
            "serving_requests_preempted")


def served_logit_gaps(ref, sizes: dict, source, samples: list,
                      pad_to: int, chooser: str | None = None) -> dict:
    """``check.served_logit_gaps``, the judged tokens settled before the
    float32 forward of a sample: one row's logits on the device at a time
    with a ``chooser`` too."""
    import jax.numpy as jnp

    width = check.row_width(samples, pad_to,
                            int(sizes["max_position_embeddings"]))
    forward = check._forward(ref, sizes, source)
    widest, n_tokens = 0.0, 0
    for prompt, served in samples:
        row = np.zeros((1, width), np.int32)
        row[0, :len(prompt) + len(served)] = list(prompt) + list(served)
        tokens = jnp.asarray(row)
        if chooser is None:
            judged = jnp.roll(tokens, -1, axis=1)   # position p predicts p + 1
        else:
            judged = jnp.argmax(forward(tokens, chooser),
                                axis=-1).block_until_ready()
        lg = forward(tokens, "float32")
        gap = np.asarray(check._gaps_below_best(lg, judged))
        del lg
        first = len(prompt) - 1 if chooser is None else 0
        widest = max(widest, float(
            gap[first:len(prompt) - 1 + len(served)].max()))
        n_tokens += len(served)
    return {"widest_gap": widest, "tokens_compared": n_tokens,
            "width": width}


def _counters_line(err) -> None:
    try:
        from fleetx_tpu.observability.metrics import get_registry
        reg = get_registry()
        print("counters: " + ", ".join(
            f"{n}={reg.counter(n).value:g}" for n in COUNTERS), file=err)
    except Exception as e:                      # a parent without them
        print(f"counters: none ({e})", file=err)


def main(argv=None, **kw) -> None:
    args = run.parse(argv)
    harness = check.served_logit_gaps
    check.served_logit_gaps = served_logit_gaps
    try:
        run.run_cell(args, **kw)
    finally:
        check.served_logit_gaps = harness
    _counters_line(kw.get("err", sys.stderr))


if __name__ == "__main__":
    main(t_start=T_PROCESS_START)
