"""The comparison that decides ``correct``.

What the timed path produced (the program's losses, gradient norms and
parameter changes over its first steps; the tokens the window served) is
held against the plain reference, each number with a limit of its own that
the configuration's file states. Every number compared is printed beside
its limit.
"""

from __future__ import annotations

import statistics
import sys

import jax
import jax.numpy as jnp
import numpy as np


def without_gradient_free(ref, name: str, x):
    """``x`` with the slice zeroed that the architecture gives no gradient
    (``GRADIENT_FREE`` of the reference)."""
    where = getattr(ref, "GRADIENT_FREE", {}).get(name)
    if where is None:
        return x
    idx = [slice(None)] * x.ndim
    idx[where[0]] = where[1]
    return x.at[tuple(idx)].set(0)


def traced_leaf_norms(ref, tree: dict) -> dict:
    return {k: jnp.sqrt(jnp.sum(jnp.square(without_gradient_free(
        ref, k, v.astype(jnp.float32))))) for k, v in tree.items()}


def leaf_norms(ref, tree: dict) -> dict:
    """The norm of every leaf, in one device program."""
    got = jax.jit(lambda t: traced_leaf_norms(ref, t))(tree)
    return {k: float(v) for k, v in jax.device_get(got).items()}


def train_reference(ref, adam, sizes: dict, opt: dict, weights: dict,
                    batches: list, precision: str = "float32",
                    rows_per_block: int = 1) -> dict:
    """Follow the first ``len(batches)`` steps: each step's loss, the norm
    of the first gradient as the optimizer gets it, and the norm of the
    parameters' change after the last step, leaf by leaf."""
    kinds = {k: kind for k, (_, kind) in ref.weight_spec(sizes).items()}
    w, state = dict(weights), adam.init(weights)
    losses, first_grad = [], None
    for i, batch in enumerate(batches, start=1):
        loss, grads = ref.loss_and_grads(w, sizes, batch, precision,
                                         rows_per_block)
        w, state, clipped = adam.step(w, state, grads, opt, kinds, i)
        losses.append(float(loss))
        if first_grad is None:
            first_grad = leaf_norms(ref, clipped)
    delta = leaf_norms(ref, {k: w[k] - weights[k] for k in w})
    return {"losses": losses, "grad_norms": first_grad, "delta_norms": delta}


def worst_leaf_gap(got: dict, want: dict) -> float:
    """The gap between the two norms of a leaf (not the norm of their
    difference), against the reference's norm of that leaf or of the
    median leaf, whichever is larger: some gradients are all but zero."""
    floor = statistics.median(want.values())
    return max(abs(got[k] - want[k]) / max(want[k], floor, 1e-30)
               for k in want)


def train_numbers(program: dict, reference: dict) -> dict:
    """The three numbers of a train cell: the program (or a control) against
    the reference."""
    steps = min(len(program["losses"]), len(reference["losses"]))
    return {
        "loss_rel_gap": max(abs(p - r) / abs(r) for p, r in zip(
            program["losses"][:steps], reference["losses"][:steps])),
        "grad_norm_worst_leaf_gap": worst_leaf_gap(
            program["grad_norms"], reference["grad_norms"]),
        "delta_norm_worst_leaf_gap": worst_leaf_gap(
            program["delta_norms"], reference["delta_norms"]),
    }


def judge(numbers: dict, limits: dict, out=sys.stderr) -> bool:
    """Print each number beside its limit; all have to sit inside."""
    ok = True
    for name, value in numbers.items():
        limit = float(limits[name])
        good = bool(np.isfinite(value)) and value <= limit
        ok = ok and good
        print(f"check: {name} = {value:.6g}  limit {limit:.6g}  "
              f"{'ok' if good else 'NOT CORRECT'}", file=out)
    return ok


# ------------------------------------------------------------------ serving
def sample_served(finished: list, seed: int, n: int) -> list:
    """A seeded sample of the finished requests with the longest in it;
    each item is ``(prompt, served_tokens)``."""
    if not finished:
        return []
    order = sorted(range(len(finished)),
                   key=lambda i: -(len(finished[i][0]) + len(finished[i][1])))
    rng = np.random.default_rng(seed % (2 ** 32))
    rest = [int(i) for i in rng.permutation(order[1:])[:max(n - 1, 0)]]
    return [finished[i] for i in [order[0]] + rest]


WIDTH_STEP = 128    # rows past ``pad_to`` grow by this: few widths to compile


class TooLong(ValueError):
    """A served request holds more tokens than the reference has positions."""


def row_width(samples: list, pad_to: int, positions: int) -> int:
    """How wide the token matrix has to be for ``samples``.

    ``pad_to`` (the traffic file's ``check.pad_to``: longest prompt + longest
    output) is the width for steady-state requests, and the width whenever
    every sample fits it. A closed loop's first-round requests may need
    more: ``traffic.ClosedLoop.first`` lengthens each by the prefill chunks
    still queued behind it, and the longest finished request is always in
    the sample. Then the width follows the samples, the smallest multiple
    of ``WIDTH_STEP`` that holds the longest, and never more than the
    reference's ``positions``: a request longer than those is ``TooLong``
    (indexing past the position table would clamp, not fail)."""
    longest = max(len(prompt) + len(served) for prompt, served in samples)
    if longest > positions:
        raise TooLong(f"request of {longest} tokens exceeds the reference's "
                      f"{positions} positions")
    if longest <= pad_to:
        return int(pad_to)
    return min(-(-longest // WIDTH_STEP) * WIDTH_STEP, positions)


def _forward(ref, sizes: dict, source):
    """``(tokens [1, width], precision) -> float32 logits [1, width,
    vocab]`` of the reference. A reference that defines ``logits_streamed
    (leaf, sizes, tokens, precision)`` walks its layers and asks
    ``leaf(name)`` or ``leaf(name, layer)`` for each weight as it goes
    (``weights.Source.leaf``: drawn alone, so one layer stands on the
    device at a time); any other is handed the whole float32 tree, as
    ``logits(w, sizes, tokens, precision)`` takes it."""
    if hasattr(ref, "logits_streamed"):
        return lambda tokens, precision: ref.logits_streamed(
            source.leaf, sizes, tokens, precision)
    fwd = jax.jit(lambda w, t, p: ref.logits(w, sizes, t, p),
                  static_argnums=2)
    return lambda tokens, precision: fwd(source.tree(), tokens, precision)


@jax.jit
def _gaps_below_best(lg, judged):
    return (lg.max(-1) - jnp.take_along_axis(
        lg, judged[..., None], axis=-1)[..., 0])[0]


def served_logit_gaps(ref, sizes: dict, source, samples: list,
                      pad_to: int, chooser: str | None = None) -> dict:
    """Run the reference once over each prompt with its served tokens, a
    sample at a time: what stands on the device is one row's logits
    beside the reference's weights (``source``: ``weights.Source``).

    ``widest_gap``: the widest gap by which a served token's logit lies
    below the reference's best at its position (valid for greedy tokens).
    With ``chooser`` (a lower precision) the tokens judged are not the
    served ones but those that precision puts first, at each position of
    the same prompts and tokens — the control. Every served token of every
    sample is compared: the rows are ``row_width`` wide (``width`` in the
    result), which raises ``TooLong`` for a sample the configuration's
    position table cannot hold.
    """
    width = row_width(samples, pad_to, int(sizes["max_position_embeddings"]))
    forward = _forward(ref, sizes, source)
    widest, n_tokens = 0.0, 0
    for prompt, served in samples:
        row = np.zeros((1, width), np.int32)
        row[0, :len(prompt) + len(served)] = list(prompt) + list(served)
        tokens = jnp.asarray(row)
        lg = forward(tokens, "float32")
        if chooser is None:
            judged = jnp.roll(tokens, -1, axis=1)   # position p predicts p + 1
        else:
            judged = jnp.argmax(forward(tokens, chooser), axis=-1)
        gap = np.asarray(_gaps_below_best(lg, judged))
        del lg      # or the next sample's logits stand beside this one's
        first = len(prompt) - 1 if chooser is None else 0
        widest = max(widest, float(
            gap[first:len(prompt) - 1 + len(served)].max()))
        n_tokens += len(served)
    return {"widest_gap": widest, "tokens_compared": n_tokens,
            "width": width}
