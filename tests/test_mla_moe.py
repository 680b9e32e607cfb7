"""The latent-attention sparse-expert family on the CPU at small sizes:
program against the plain reference (forward, loss, every gradient leaf),
the attention kernels against plain ``jnp`` (Pallas in interpret mode),
the share test, the bias step, no dropped rows, the rule table and the
normal path (``tools/train.py``)."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks import manifest as manifest_mod  # noqa: E402
from benchmarks import weights  # noqa: E402
from fleetx_tpu.models.mla_moe import model as model_lib  # noqa: E402
from fleetx_tpu.models.mla_moe import moe  # noqa: E402
from fleetx_tpu.models.mla_moe.config import config_from_dict  # noqa: E402
from fleetx_tpu.models.mla_moe.module import train_flops_per_token  # noqa: E402
from fleetx_tpu.ops import mla_attention  # noqa: E402
from fleetx_tpu.optims import optimizer as optim  # noqa: E402

RECIPE = "fleetx_tpu/configs/nlp/mla_moe/pretrain_joyai_flash_share16_synthetic.yaml"
SMALL = dict(hidden_size=64, intermediate_size=96, num_attention_heads=4,
             q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16,
             qk_rope_head_dim=8, v_head_dim=16, n_routed_experts=4,
             router_experts=16, first_expert_held=4, num_experts_per_tok=3,
             moe_intermediate_size=32, vocab_size=128, num_hidden_layers=3,
             rope_theta=10000.0)


@pytest.fixture(scope="module")
def shipped():
    with open(os.path.join(ROOT, "benchmarks/configs/joyai-llm-flash.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def ref():
    return manifest_mod.load_module(
        os.path.join(ROOT, "benchmarks/reference/joyai_ref.py"))


def _sizes(shipped, **over):
    sizes = {k: v for k, v in shipped.items()
             if isinstance(v, (int, float, bool))}
    sizes.update(SMALL)
    sizes.update(over)
    return sizes


def _program_cfg(sizes, **over):
    model = dict(sizes, experts_held=sizes["n_routed_experts"],
                 n_routed_experts=sizes["router_experts"], dtype="float32",
                 moe_chunk_rows=32, moe_tile_rows=8, loss_chunk_rows=32)
    model.update(over)
    return config_from_dict(model)


def _batch(sizes, rows=2, seq=64, seed=0):
    tok = np.random.default_rng(seed).integers(
        0, sizes["vocab_size"], (rows, seq + 1))
    return {"tokens": jnp.asarray(tok[:, :-1]),
            "labels": jnp.asarray(tok[:, 1:]),
            "loss_mask": jnp.ones((rows, seq), jnp.float32),
            "position_ids": jnp.broadcast_to(jnp.arange(seq), (rows, seq))}


@pytest.fixture(scope="module")
def both(shipped, ref):
    """Seeded weights in the reference's names and in the program's tree."""
    sizes = _sizes(shipped)
    cfg = _program_cfg(sizes)
    w = weights.make(ref.weight_spec(sizes), 5)
    template = jax.eval_shape(
        lambda: model_lib.init_params(cfg, jax.random.PRNGKey(0)))
    params = weights.to_program_tree(w, shipped["param_paths"], template)
    return sizes, cfg, w, params


# ------------------------------------------------- program against reference
def test_forward_matches_the_reference(both, ref):
    sizes, cfg, w, params = both
    batch = _batch(sizes)
    got = model_lib.logits(params, cfg, batch["tokens"])
    want = ref.logits(w, sizes, batch["tokens"])
    assert got.shape == (2, 64, sizes["vocab_size"])
    np.testing.assert_allclose(got, want, atol=2e-6)


@pytest.mark.parametrize("rows_per_block", [1, 2])
def test_loss_and_every_gradient_leaf_match_the_reference(
        both, ref, shipped, rows_per_block):
    sizes, cfg, w, params = both
    batch = _batch(sizes, seed=1)
    (loss, metrics), grads = jax.value_and_grad(
        lambda p: model_lib.training_loss(p, cfg, batch), has_aux=True)(
            params)
    want_loss, want = ref.loss_and_grads(w, sizes, batch, "float32",
                                         rows_per_block)
    assert abs(float(loss) - float(want_loss)) < 2e-6 * float(want_loss)
    assert abs(float(metrics["loss_main"] + cfg.mtp_loss_weight
                     * metrics["loss_mtp"]) - float(loss)) < 1e-6
    got = weights.program_paths(shipped["param_paths"], grads)
    assert set(got) == set(want) == set(ref.weight_spec(sizes))
    for name in want:
        scale = float(jnp.abs(want[name]).max())
        assert scale > 0, name
        np.testing.assert_allclose(got[name], want[name], atol=3e-5 * scale,
                                   err_msg=name)
    # the selection biases carry the load less its mean, not a gradient
    for name in ("m_sel_bias", "t_sel_bias"):
        assert float(jnp.abs(got[name].sum(-1)).max()) < 1e-3
        assert float(jnp.abs(got[name]).max()) >= 1.0


def test_kernel_path_of_the_model_matches_the_plain_path(shipped, ref):
    """Real head widths (128 + 64, 128), so the Pallas kernels run
    (interpreted) inside the model; toy everything else."""
    sizes = _sizes(shipped, num_attention_heads=2, qk_nope_head_dim=128,
                   qk_rope_head_dim=64, v_head_dim=128, num_hidden_layers=2)
    cfg = _program_cfg(sizes)
    plain = _program_cfg(sizes, use_flash_attention=False)
    w = weights.make(ref.weight_spec(sizes), 7)
    template = jax.eval_shape(
        lambda: model_lib.init_params(cfg, jax.random.PRNGKey(0)))
    params = weights.to_program_tree(w, shipped["param_paths"], template)
    batch = _batch(sizes, rows=1, seq=128)

    def run(c):
        return jax.value_and_grad(
            lambda p: model_lib.training_loss(p, c, batch)[0])(params)

    (l1, g1), (l2, g2) = run(cfg), run(plain)
    assert abs(float(l1) - float(l2)) < 1e-5
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(a, b, atol=1e-5 * max(
            float(jnp.abs(b).max()), 1e-6))
    want_loss, _ = ref.loss_and_grads(w, sizes, batch, "float32", 1)
    assert abs(float(l1) - float(want_loss)) < 1e-5


# ------------------------------------------------------- attention kernels
@pytest.mark.parametrize("seq,block_q,block_k", [
    (256, 128, 128), (384, 384, 128), (384, 128, 384), (512, 256, 256)])
def test_attention_kernels_match_plain_jnp(seq, block_q, block_k):
    keys = jax.random.split(jax.random.PRNGKey(seq), 6)
    b, heads = 1, 4
    qn = jax.random.normal(keys[0], (b, heads, seq, 128))
    qr2 = jax.random.normal(keys[1], (b, heads // 2, seq, 128))
    kn = jax.random.normal(keys[2], (b, heads, seq, 128))
    kr = jax.random.normal(keys[3], (b, seq, 64))
    v = jax.random.normal(keys[4], (b, heads, seq, 128))
    w = jax.random.normal(keys[5], (b, heads, seq, 128))
    args, scale = (qn, qr2, kn, kr, v), 192 ** -0.5
    assert mla_attention.supported(qn, qr2, v)

    def kernel(*a):
        return mla_attention.mla_flash_attention(
            *a, scale=scale, block_q=block_q, block_k=block_k)

    def plain(*a):
        return mla_attention.reference_attention(*a, scale=scale)

    np.testing.assert_allclose(kernel(*args), plain(*args), atol=2e-5)
    got = jax.grad(lambda *a: (kernel(*a) * w).sum(), range(5))(*args)
    want = jax.grad(lambda *a: (plain(*a) * w).sum(), range(5))(*args)
    for g, r, name in zip(got, want, ("qn", "qr2", "kn", "kr", "v")):
        np.testing.assert_allclose(g, r, atol=3e-5, err_msg=name)


def test_attention_kernels_refuse_what_they_do_not_tile():
    qn = jnp.zeros((1, 4, 200, 128))
    assert not mla_attention.supported(qn, jnp.zeros((1, 2, 200, 128)), qn)
    ok = jnp.zeros((1, 4, 256, 128))
    assert not mla_attention.supported(ok, jnp.zeros((1, 4, 256, 64)), ok)
    with pytest.raises(ValueError):
        mla_attention.mla_flash_attention(
            ok, jnp.zeros((1, 2, 256, 128)), ok, jnp.zeros((1, 256, 64)), ok,
            scale=1.0, block_q=96)


@pytest.mark.parametrize("n_tiles", [6, 4, 0])
def test_grouped_products_match_plain_jnp(n_tiles):
    """``moe_gmm``, its transposed form and ``moe_tgmm`` against per-tile
    ``jnp`` products; tiles past ``n_tiles`` give zeros and add nothing,
    an expert without rows keeps its accumulator."""
    from fleetx_tpu.ops import grouped_matmul as gm

    tile, k, n, experts = 8, 32, 48, 5
    tile_expert = jnp.array([0, 0, 2, 3, 3, 4], jnp.int32)   # none for 1
    keys = jax.random.split(jax.random.PRNGKey(3), 4)
    xs = jax.random.normal(keys[0], (6 * tile, k))
    dy = jax.random.normal(keys[1], (6 * tile, n))
    w = jax.random.normal(keys[2], (experts, k, n))
    acc = jax.random.normal(keys[3], (experts, k, n))
    live = (jnp.arange(6) < n_tiles)[:, None, None]
    xt, dt_ = xs.reshape(6, tile, k), dy.reshape(6, tile, n)
    want = jnp.where(live, jnp.einsum("tmk,tkn->tmn", xt, w[tile_expert]), 0)
    got = gm.moe_gmm(xs, w, tile_expert, n_tiles, tile=tile)
    np.testing.assert_allclose(got.reshape(6, tile, n), want, atol=1e-5)
    want_t = jnp.where(live, jnp.einsum("tmn,tkn->tmk", dt_, w[tile_expert]),
                       0)
    got_t = gm.moe_gmm(dy, w, tile_expert, n_tiles, tile=tile,
                       transpose_rhs=True)
    np.testing.assert_allclose(got_t.reshape(6, tile, k), want_t, atol=1e-5)
    per_tile = jnp.where(live, jnp.einsum("tmk,tmn->tkn", xt, dt_), 0)
    want_acc = acc + jax.ops.segment_sum(per_tile, tile_expert, experts)
    got_acc = gm.moe_tgmm(xs, dy, acc, tile_expert, n_tiles, tile=tile,
                          block_k=16, block_n=16)
    np.testing.assert_allclose(got_acc, want_acc, atol=1e-4)
    np.testing.assert_array_equal(got_acc[1], acc[1])


# ------------------------------------------------------------ the share test
def test_the_shares_add_up_to_the_uncut_layer(shipped, ref):
    """One expert layer: the parts that all four shares of 4 experts give,
    the shared expert counted once, add up to what the uncut reference
    gives for the whole layer of 16."""
    sizes = _sizes(shipped, n_routed_experts=16, first_expert_held=0)
    spec = {k[2:]: v for k, v in ref.weight_spec(sizes).items()
            if k.startswith("m_")}
    lw = {k: v[0] for k, v in weights.make(spec, 3).items()}
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 48, sizes["hidden_size"]))
    whole, load = ref._moe(x, lw, sizes, "float32")
    shared = ref._gated_mlp(x, lw["s_gate"], lw["s_up"], lw["s_down"],
                            "float32")
    total = jnp.zeros_like(x)
    for share in range(4):
        cfg = _program_cfg(_sizes(shipped, n_routed_experts=4,
                                  first_expert_held=4 * share))
        held = slice(4 * share, 4 * share + 4)
        p = {"router": lw["router"], "selection_bias": lw["sel_bias"],
             "experts_gate": lw["e_gate"][held],
             "experts_up": lw["e_up"][held],
             "experts_down": lw["e_down"][held], "shared_gate": lw["s_gate"],
             "shared_up": lw["s_up"], "shared_down": lw["s_down"]}
        part, stats = moe.moe_layer(x, p, cfg)
        total = total + (part - shared)
        # every share routes over all 16 and sees the same load
        assert abs(float(stats["held_share"])
                   - float(load[held].sum() / load.sum())) < 1e-6
    np.testing.assert_allclose(total + shared, whole, atol=2e-6)
    assert float(load.sum()) == 2 * 48 * sizes["num_experts_per_tok"]


# ----------------------------------------------------------------- no drops
@pytest.mark.parametrize("chunk,tile", [(32, 8), (64, 16), (512, 8)])
def test_no_row_is_dropped_when_the_router_picks_one_expert(shipped, chunk,
                                                            tile):
    """A selection bias that sends every token to the same three held
    experts: every (token, expert) pair is a row here, none is dropped,
    and the result is the plain weighted sum."""
    sizes = _sizes(shipped, first_expert_held=0)
    cfg = _program_cfg(sizes, moe_chunk_rows=chunk, moe_tile_rows=tile)
    k, h, f = 3, sizes["hidden_size"], sizes["moe_intermediate_size"]
    keys = jax.random.split(jax.random.PRNGKey(2), 8)
    p = {"router": 0.02 * jax.random.normal(keys[0], (h, 16)),
         "selection_bias": jnp.zeros((16,)).at[jnp.array([0, 2, 3])].set(9.0),
         "experts_gate": 0.1 * jax.random.normal(keys[1], (4, h, f)),
         "experts_up": 0.1 * jax.random.normal(keys[2], (4, h, f)),
         "experts_down": 0.1 * jax.random.normal(keys[3], (4, f, h)),
         "shared_gate": jnp.zeros((h, f)), "shared_up": jnp.zeros((h, f)),
         "shared_down": jnp.zeros((f, h))}
    x = jax.random.normal(keys[4], (2, 40, h))
    x2d = x.reshape(-1, h)
    ids, weights_, load = moe.route(x2d, p["router"], p["selection_bias"], k,
                                    2.5, True)
    assert sorted(np.unique(np.asarray(ids)).tolist()) == [0, 2, 3]
    plan = moe.plan_rows(ids, 0, 4, tile, min(chunk, -(-80 * k // tile) * tile))
    assert int(plan["rows_held"].sum()) == 80 * k == int(load.sum())
    assert int(plan["row_valid"].sum()) == 80 * k
    y, stats = moe.moe_layer(x, p, cfg)
    want = jnp.zeros_like(x2d)
    for j in range(k):
        e = ids[:, j]
        out = jnp.einsum("nf,nfh->nh", jax.nn.silu(jnp.einsum(
            "nh,nhf->nf", x2d, p["experts_gate"][e])) * jnp.einsum(
                "nh,nhf->nf", x2d, p["experts_up"][e]), p["experts_down"][e])
        want = want + weights_[:, j:j + 1] * out
    np.testing.assert_allclose(y.reshape(-1, h), want, atol=2e-6)
    assert float(stats["held_share"]) == 1.0
    # and the hand-written backward agrees with plain autodiff of the sum
    def dense(xv):
        out = jnp.zeros_like(xv)
        for j in range(k):
            e = ids[:, j]
            out = out + weights_[:, j:j + 1] * jnp.einsum(
                "nf,nfh->nh", jax.nn.silu(jnp.einsum(
                    "nh,nhf->nf", xv, p["experts_gate"][e])) * jnp.einsum(
                        "nh,nhf->nf", xv, p["experts_up"][e]),
                p["experts_down"][e])
        return (out ** 2).sum()

    def held(xv):
        c = plan["row_pair"].shape[0] if chunk > 80 * k else chunk
        pl = moe.plan_rows(ids, 0, 4, tile, c)
        gate_up = jnp.concatenate([p["experts_gate"], p["experts_up"]], -1)
        return (moe.grouped_experts(xv, weights_.reshape(-1), gate_up,
                                    p["experts_down"], pl, k, c, tile)
                ** 2).sum()

    np.testing.assert_allclose(jax.grad(held)(x2d), jax.grad(dense)(x2d),
                               atol=1e-5)


# ----------------------------------------------------------------- the plan
# (tokens, k, held, tile, pass rows, first held, routed experts): the six
# serve programs' plans as the recipes size them, and a reduced training
# layer whose share starts past the first expert
PLAN_SHAPES = {
    "laguna.decode": (64, 10, 32, 16, 512, 0, 256),
    "laguna.chunk": (512, 10, 32, 16, 1024, 0, 256),
    "smallthinker.decode": (48, 6, 64, 16, 1024, 0, 64),
    "smallthinker.chunk": (512, 6, 64, 16, 4096, 0, 64),
    "lfm2.decode": (256, 4, 64, 16, 2048, 0, 64),
    "lfm2.chunk": (512, 4, 64, 16, 3072, 0, 64),
    "joyai.train.reduced": (1024, 8, 16, 256, 2048, 32, 256),
}
JOYAI_TRAIN_PLAN = (16384, 8, 16, 256, 16384, 32, 256)


def _plan_route(route, shape):
    """``ids`` [tokens, k] of one of the four routes the plan must bear."""
    n, k, held, _, _, first, routed = shape
    rng = np.random.default_rng(49)
    ids = np.stack([rng.permutation(routed)[:k] for _ in range(n)])
    if route == "one_held_expert":  # every pair of every token
        ids[:] = first + held // 2
    elif route == "no_pair_held":
        ids[:] = (first + held) % routed if routed > held else -1
    elif route == "half_nowhere":   # empty decode slots, a ragged chunk's tail
        ids[rng.permutation(n)[: n // 2]] = -1
    return ids.astype(np.int32)


def _plan_oracle(ids, first, held, tile, chunk):
    """The docstring's definition, spelled out: a stable sort of the pairs
    by local expert, each expert's run padded to whole tiles."""
    n, k = ids.shape
    local = ids.reshape(-1) - first
    is_held = (local >= 0) & (local < held)
    order = np.argsort(np.where(is_held, local, held), kind="stable")
    rows = moe.buffer_rows(n * min(k, held), held, tile, chunk)
    row_pair = np.zeros(rows, np.int32)
    row_valid = np.zeros(rows, bool)
    pair_row = np.zeros(n * k, np.int32)
    count = np.zeros(held, np.int32)
    tile_expert, at, taken = [], 0, 0
    for e in range(held):
        count[e] = (is_held & (local == e)).sum()
        run = order[taken:taken + count[e]]
        row_pair[at:at + count[e]] = run
        row_valid[at:at + count[e]] = True
        pair_row[run] = at + np.arange(count[e])
        tiles = -(-count[e] // tile)
        tile_expert += [e] * tiles
        at, taken = at + tiles * tile, taken + count[e]
    return {"row_pair": row_pair, "row_valid": row_valid,
            "tile_expert": np.asarray(tile_expert, np.int32),
            "pair_row": pair_row, "pair_held": is_held, "rows_held": count,
            "n_tiles": np.int32(at // tile),
            "n_passes": np.int32(-(-at // chunk))}


@pytest.mark.parametrize("route", ["random", "one_held_expert",
                                   "no_pair_held", "half_nowhere"])
@pytest.mark.parametrize("program", sorted(PLAN_SHAPES))
def test_the_plan_is_the_stable_sort_padded_to_tiles(program, route):
    """Every field of ``plan_rows``' dict against the oracle, at the sizes
    the served programs and the training layer run it at."""
    shape = PLAN_SHAPES[program]
    _, _, held, tile, chunk, first, _ = shape
    ids = _plan_route(route, shape)
    got = jax.jit(moe.plan_rows, static_argnums=(1, 2, 3, 4))(
        jnp.asarray(ids), first, held, tile, chunk)
    want = _plan_oracle(ids, first, held, tile, chunk)
    assert sorted(got) == sorted(want)
    n_tiles = int(want["n_tiles"])
    if route == "no_pair_held":
        assert n_tiles == 0 and not want["pair_held"].any()
    else:
        assert n_tiles > 0
        assert want["row_valid"].sum() == want["pair_held"].sum()
    for name, value in want.items():
        have = np.asarray(got[name])
        if name == "tile_expert":   # past the last tile nothing reads it
            assert have.shape == (len(want["row_pair"]) // tile,)
            have = have[:n_tiles]
        assert have.dtype == value.dtype and have.shape == value.shape, name
        np.testing.assert_array_equal(have, value, err_msg=name)


def _primitives(jaxpr) -> set:
    """Names of every primitive of ``jaxpr``, the nested ones' too."""
    names = set()
    for eqn in jaxpr.eqns:
        names.add(eqn.primitive.name)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            names |= _primitives(sub)
    return names


@pytest.mark.parametrize("shape", [PLAN_SHAPES["smallthinker.decode"],
                                   JOYAI_TRAIN_PLAN],
                         ids=["serve", "train"])
def test_the_plan_holds_no_loop_and_no_sort(shape):
    """A library's default method (``searchsorted`` is a ``while`` of
    gathers and selects) cannot bring a loop back unseen."""
    n, k, held, tile, chunk, first, _ = shape
    jaxpr = jax.make_jaxpr(
        lambda ids: moe.plan_rows(ids, first, held, tile, chunk))(
            jax.ShapeDtypeStruct((n, k), jnp.int32))
    names = _primitives(jaxpr.jaxpr)
    assert "cumsum" in names and "scatter" in names, sorted(names)
    assert not names & {"while", "scan", "sort", "gather"}, sorted(names)


# ------------------------------------------------------------ the bias step
def test_the_bias_step_follows_its_rule_on_a_hand_made_load():
    params = {"moe": {"selection_bias": jnp.array([0.5, -0.25, 0.0, 0.1]),
                      "router": jnp.ones((3, 4))}}
    load = jnp.array([10.0, 2.0, 4.0, 0.0])
    grads = {"moe": {"selection_bias": load - load.mean(),
                     "router": jnp.full((3, 4), 0.5)}}
    tx = optim.adamw(1e-2, grad_clip=1.0, selection_bias_rate=0.001)
    tx = optax.with_extra_args_support(tx)
    state = tx.init(params)
    norm = optim.global_norm(grads)
    # the load is no gradient: it stays out of the norm the clip uses
    assert abs(float(norm) - float(jnp.sqrt(12 * 0.25))) < 1e-6
    updates, _ = tx.update(grads, state, params, grad_norm=norm)
    new = optax.apply_updates(params, updates)
    # overloaded experts (10 > mean 4) go down by the rate, the idle up;
    # one exactly at the mean stays
    np.testing.assert_allclose(
        new["moe"]["selection_bias"],
        jnp.array([0.5 - 0.001, -0.25 + 0.001, 0.0, 0.1 + 0.001]), atol=1e-7)
    # every other leaf takes AdamW's step (first step: -lr * (sign + decay))
    np.testing.assert_allclose(new["moe"]["router"],
                               1.0 - 1e-2 * (1.0 + 0.01), atol=1e-5)
    fused = optim.adamw(1e-2, fused_clip=True)
    _, _, fused_norm = fused.update(grads, fused.init(params), params)
    assert abs(float(fused_norm) - float(norm)) < 1e-6


def test_the_reference_optimizer_takes_the_same_bias_step(shipped):
    adam = manifest_mod.load_module(
        os.path.join(ROOT, "benchmarks/reference/adamw_noaux_ref.py"))
    w = {"b": jnp.array([0.5, -0.25, 0.0]), "m": jnp.ones((2, 3))}
    kinds = {"b": "selection_bias", "m": "matrix"}
    grads = {"b": jnp.array([6.0, -2.0, 0.0]), "m": jnp.full((2, 3), 0.5)}
    opt = dict(shipped["train"]["optimizer"], max_lr=1e-2, min_lr=1e-2)
    new, state, clipped = adam.step(w, adam.init(w), grads, opt, kinds, 1)
    np.testing.assert_allclose(new["b"], [0.499, -0.249, 0.0], atol=1e-7)
    assert set(clipped) == {"m"}
    # norm sqrt(6 * 0.25) > 1: clipped to norm 1, without the load in it
    np.testing.assert_allclose(
        np.sqrt((np.asarray(clipped["m"]) ** 2).sum()), 1.0, atol=1e-6)
    np.testing.assert_allclose(new["m"], 1.0 - 1e-2 * (1.0 + 0.01), atol=1e-5)
    assert isinstance(state[0]["m"], np.ndarray)      # moments rest on the host


# -------------------------------------------------- sizes, rules, entry point
def test_share_bytes_and_required_operations(shipped):
    from fleetx_tpu.utils import config as config_mod

    cfg = config_mod.get_config(os.path.join(ROOT, RECIPE), [], num_devices=1)
    mc = config_from_dict(dict(cfg["Model"]))
    shapes = model_lib.param_shapes(mc)
    n = sum(int(np.prod(s)) for s in jax.tree.leaves(
        shapes, is_leaf=lambda s: isinstance(s, tuple)))
    assert n == shipped["bytes"]["parameters"] == 680441088
    assert shipped["bytes"]["at_16_bytes_a_parameter"] == 16 * n
    # the widths of the recipe are the catalog's, key for key
    for key in ("hidden_size", "intermediate_size", "num_attention_heads",
                "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
                "qk_rope_head_dim", "v_head_dim", "moe_intermediate_size",
                "num_experts_per_tok", "n_shared_experts", "rope_theta",
                "routed_scaling_factor", "first_k_dense_replace"):
        assert getattr(mc, key) == shipped[key], key
    assert mc.n_routed_experts == shipped["router_experts"] == 256
    assert mc.experts_held == shipped["n_routed_experts"] == 16
    counts = manifest_mod.load_module(os.path.join(
        ROOT, "benchmarks/kernels/joyai_share_model.py"))
    per_token = counts.train_flops_per_token(shipped, 8192)
    assert abs(per_token - train_flops_per_token(mc, 8192)) < 1e-6 * per_token
    assert abs(per_token / 1e9 - 3.398) < 0.001


def test_rule_table_covers_the_tree_and_shardcheck_is_green():
    from fleetx_tpu.parallel import rules

    cfg = config_from_dict(dict(SMALL, experts_held=4, n_routed_experts=16))
    tree = jax.eval_shape(
        lambda: model_lib.init_params(cfg, jax.random.PRNGKey(0)))
    specs = rules.registry_specs("mla_moe", tree)
    assert len(jax.tree.leaves(specs, is_leaf=lambda s: hasattr(
        s, "index"))) == len(jax.tree.leaves(tree))
    used = set()
    for name, leaf in rules.tree_leaf_names(tree):
        hits = rules._matches("mla_moe", name)
        assert len(hits) == 1, (name, hits)
        used.add(hits[0][0])
    assert used == set(range(len(rules.PARTITION_RULES["mla_moe"])))
    out = subprocess.run(
        [sys.executable, "tools/shardcheck.py", RECIPE], cwd=ROOT,
        capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]


def test_train_py_trains_the_recipe_at_toy_widths(tmp_path):
    tiny = [f"Model.{k}={v}" for k, v in dict(
        SMALL, n_routed_experts=16, experts_held=4, moe_chunk_rows=64,
        moe_tile_rows=8, loss_chunk_rows=64, dtype="float32").items()
        if k != "router_experts"]
    tiny += ["Engine.max_steps=3", "Global.max_seq_len=64",
             "Global.local_batch_size=2", "Global.micro_batch_size=2",
             "Data.Train.dataset.seq_length=64",
             "Data.Train.dataset.vocab_size=128",
             f"Engine.save_load.output_dir={tmp_path}"]
    cmd = [sys.executable, "tools/train.py", "-c", RECIPE]
    for o in tiny:
        cmd += ["-o", o]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True,
                         env=env, timeout=600)
    text = out.stdout + out.stderr
    assert out.returncode == 0, text[-3000:]
    assert "latent-attention expert model: 1 dense + 2 expert layers" in text
    losses = [float(line.split("loss: ")[1].split(",")[0])
              for line in text.splitlines() if "[train] global step" in line]
    # first-step loss: ln(vocab) for the main head + 0.3 x the same
    assert len(losses) == 3 and abs(losses[0] - 1.3 * np.log(128)) < 0.2
