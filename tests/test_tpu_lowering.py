"""Every program the chip runs lowers for the TPU — checked from the CPU.

Tier-1 runs the Pallas kernels in interpret mode, which emits plain HLO
that GSPMD partitions freely, so a kernel (or a mesh wrapper around one)
that JAX refuses to lower for a TPU passes every other test. Here the ONE
interpret switch (``fleetx_tpu.ops.interpret``) is forced off and the
train step — on one device and under each four-device layout — and the
serving decode program are cross-lowered for ``lowering_platforms=("tpu",)``
on the virtual CPU mesh. That runs JAX's own Pallas→Mosaic lowering and
its rule that a Mosaic call under a mesh sits in a ``shard_map`` manual
over every axis; the expected kernels must then be in the text by name.
It is not the Mosaic compiler: what only the chip (or an ahead-of-time
compile for its topology) can refuse — VMEM, tiling — is ``chip_smoke.py``.

The serving programs are ALSO compiled, by the chip's own compiler against
a described v5e topology (no chip attached), to pin the mechanism that
keeps the KV pool one buffer: both pools aliased input to output, and no
instruction that holds a second pool or a layer of it.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fleetx_tpu import ops
from fleetx_tpu.core.engine import EagerEngine
from fleetx_tpu.core.module import GPTModule
from fleetx_tpu.optims.lr_scheduler import build_lr_scheduler
from fleetx_tpu.optims.optimizer import build_optimizer
from fleetx_tpu.parallel.mesh import build_mesh
from fleetx_tpu.utils.env import mosaic_kernels

# kernel-admitted shapes: lane-aligned hidden, 64-wide heads, and a sequence
# whose per-device block is one 128-row flash tile
VOCAB, HIDDEN, HEADS, LAYERS = 512, 128, 2, 2

FLASH = {"flash_fwd", "flash_bwd_fused"}
RING = {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"}
NORM = {"fused_norm_fwd", "fused_norm_bwd"}

TRAIN_CASES = {
    "one_device": (1, {}, {}, 128, FLASH | NORM),
    "dp2_mp2_sp": (4, {"dp_degree": 2, "mp_degree": 2,
                       "sequence_parallel": True},
                   {"sequence_parallel": True}, 128, FLASH | NORM),
    "fsdp4_stage2": (4, {"fsdp_degree": 4, "sharding": {
        "sharding_stage": 2, "sharding_degree": 4}}, {}, 128, FLASH | NORM),
    "dp2_seq2_ring": (4, {"dp_degree": 2, "seq_degree": 2},
                      {"use_ring_attention": True,
                       "attention_probs_dropout_prob": 0.0}, 256,
                      RING | NORM),
}


def _lower_for_tpu(monkeypatch, jitted, *args) -> dict:
    """Cross-lower ``jitted`` for the TPU with interpret forced off; the
    Mosaic kernels in the program, by name."""
    monkeypatch.setattr(ops, "interpret", lambda: False)
    lowered = jitted.trace(*args).lower(lowering_platforms=("tpu",))
    return mosaic_kernels(lowered.as_text())


@pytest.mark.parametrize("case", list(TRAIN_CASES))
def test_train_step_lowers_for_tpu(devices8, monkeypatch, case):
    n_dev, dist, model_over, seq, want = TRAIN_CASES[case]
    model = dict(vocab_size=VOCAB, hidden_size=HIDDEN, num_layers=LAYERS,
                 num_attention_heads=HEADS, max_position_embeddings=seq,
                 use_recompute=True, recompute_granularity="dots")
    model.update(model_over)
    cfg = {"Model": model, "Distributed": dist, "Global": {"seed": 7},
           "Engine": {"max_steps": 1}}
    mesh = build_mesh(dist, devices=devices8[:n_dev])
    lr = build_lr_scheduler({"max_lr": 1e-3, "warmup_steps": 2,
                             "decay_steps": 100})
    engine = EagerEngine(cfg, GPTModule(cfg), lr_schedule=lr, mesh=mesh,
                         optimizer=build_optimizer({"name": "AdamW"}, lr))
    batch_size = 2 * n_dev
    tokens = np.zeros((batch_size, seq), np.int32)
    batch = {"tokens": tokens, "position_ids": tokens, "labels": tokens,
             "loss_mask": np.ones((batch_size, seq), np.float32)}
    engine.prepare(batch)
    with engine._ctx():
        found = _lower_for_tpu(monkeypatch, engine._train_step, engine.state,
                               engine.shard_batch(batch))
    assert set(found) == want, found


@pytest.mark.parametrize("dist", [None, {"fsdp_degree": 2, "mp_degree": 2}],
                         ids=["one_device", "fsdp2_mp2"])
def test_serving_decode_lowers_for_tpu(devices8, monkeypatch, dist):
    from fleetx_tpu.models.gpt.model import (GPTForPretraining,
                                             config_from_dict)
    from fleetx_tpu.serving.engine import ServingConfig, ServingEngine

    model_cfg = config_from_dict(dict(
        vocab_size=VOCAB, hidden_size=HIDDEN, num_layers=LAYERS,
        num_attention_heads=HEADS, max_position_embeddings=64))
    params = GPTForPretraining(model_cfg).init(
        {"params": jax.random.PRNGKey(0)}, jnp.zeros((1, 8), jnp.int32),
        None, deterministic=True)["params"]
    mesh = build_mesh(dist, devices=devices8[:4]) if dist else None
    engine = ServingEngine(model_cfg, params, ServingConfig(
        max_batch=4, page_size=16, num_pages=34, max_seq_len=64,
        prefill_chunk=16), mesh=mesh)
    assert engine.paged_kernel_active
    found = _lower_for_tpu(
        monkeypatch, engine._fns["decode"], engine.params, engine.pool_k,
        engine.pool_v, engine._tokens, np.int32(-1),
        np.zeros((1,), np.int32), engine._block_tables, engine._lens,
        *engine._draw())
    assert set(found) == {"paged_decode"}, found


# ------------------------------------------------- the pool stays one buffer

@pytest.fixture(scope="module")
def topo():
    """A described (not attached) v5e 2x2: the chip's own XLA and Mosaic
    compilers run against it in this process. Described here, inside a
    fixture, so only the worker that runs this file loads libtpu."""
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here: nothing to compile with
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


#: opcodes that may carry the pool: what enters, the loop carry, and the
#: in-place row scatter (alone, or as the root of its fusion)
_POOL_CARRIERS = {"parameter", "get-tuple-element", "scatter", "fusion"}


def _pool_holders(hlo: str, shape: tuple) -> list:
    """``(opcode, line)`` of every instruction of a compiled program whose
    result has the pool's (per-device) shape or the shape of one layer of
    it, with or without the leading 1."""
    pool = ",".join(map(str, shape))
    layer = ",".join(map(str, shape[1:]))
    want = re.compile(
        r"= \w+\[(?:%s|1,%s|%s)\]\S* ([\w-]+)\(" % (pool, layer, layer))
    return [(m.group(1), line.strip()) for line in hlo.splitlines()
            if (m := want.search(line))]


def _compile_serving_programs(topo, monkeypatch, model, dist, kernel, *,
                              pages, page, batch, per_req, chunk):
    """``decode`` and ``prefill`` of a GPT of widths ``model`` at the
    recipes' dtypes (bfloat16 compute, float32 parameters), compiled for
    the described v5e — one device, or the mesh ``dist`` with the pool on
    its shardings — from abstract arguments, the parameters as the engine
    passes them (``serving_params``). Returns ``(params, what one device
    holds of a pool, {program: compiled text})``."""
    from flax.core import meta
    from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

    from fleetx_tpu.models.gpt.model import (GPTForPretraining,
                                             config_from_dict)
    from fleetx_tpu.serving.decode import (SamplingParams, kernel_refusal,
                                           make_step_fns, serving_params)
    from fleetx_tpu.serving.paged_cache import init_pool, pool_shardings

    monkeypatch.setattr(ops, "interpret", lambda: False)
    cfg = config_from_dict(model)
    assert (cfg.dtype, cfg.param_dtype) == (jnp.bfloat16, jnp.float32)
    if dist:
        mesh = build_mesh(dist, devices=topo.devices)
        pool_sh, rep = pool_shardings(mesh), NamedSharding(
            mesh, PartitionSpec())
    else:
        pool_sh, rep = None, SingleDeviceSharding(topo.devices[0])

    def arr(shape, dtype, sharding=rep):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    model_tree = meta.unbox(jax.eval_shape(lambda: GPTForPretraining(
        cfg).init({"params": jax.random.PRNGKey(0)},
                  jnp.zeros((1, 8), jnp.int32), None,
                  deterministic=True)["params"]))
    params = jax.tree.map(
        lambda a: arr(a.shape, a.dtype),
        jax.eval_shape(lambda tree: serving_params(tree, cfg), model_tree))
    pool_k, _ = jax.eval_shape(lambda: init_pool(cfg, pages, page))
    pool = arr(pool_k.shape, pool_k.dtype, pool_sh or rep)
    if kernel:
        assert not kernel_refusal(cfg, page_size=page, num_pages=pages,
                                  pages_per_req=per_req,
                                  pool_sharding=pool_sh)
    fns = make_step_fns(cfg, max_batch=batch, pages_per_req=per_req,
                        prefill_chunk=chunk, sampling=SamplingParams(),
                        pool_sharding=pool_sh, paged_kernel=kernel)
    i32, rng = jnp.int32, arr((2,), jnp.uint32)
    programs = {
        "prefill": (params, pool, pool, arr((1, chunk), i32),
                    arr((1, per_req), i32), arr((), i32), arr((), i32), rng,
                    arr((), jnp.uint32)),
        "decode": (params, pool, pool, arr((batch,), i32), arr((), i32),
                   arr((1,), i32), arr((batch, per_req), i32),
                   arr((batch,), i32), rng, arr((), jnp.uint32)),
    }
    texts = {name: fns[name].lower(*args).compile().as_text()
             for name, args in programs.items()}
    assert ("paged_decode" in texts["decode"]) == kernel
    return params, pool.sharding.shard_shape(pool.shape), texts


SERVING_CASES = pytest.mark.parametrize(
    "dist", [None, {"fsdp_degree": 2, "mp_degree": 2}],
    ids=["one_device", "fsdp2_mp2"])
SERVING_FORMS = pytest.mark.parametrize("kernel", [True, False],
                                        ids=["kernel", "gather"])


@SERVING_CASES
@SERVING_FORMS
def test_serving_programs_keep_the_pool_one_buffer(topo, monkeypatch, dist,
                                                   kernel):
    """Compiled for the v5e, ``decode`` and ``prefill`` alias both pools
    from input to output and hold no other buffer of the pool's shape or
    of one layer's: no ``copy``, no ``dynamic-slice`` of a layer, no
    stacked output. A pool that is a scanned input fails every clause."""
    # a pool too large for the compiler to stage in on-chip memory, as the
    # real one is (abstract shapes: nothing is allocated)
    params, local, texts = _compile_serving_programs(
        topo, monkeypatch, dict(
            vocab_size=VOCAB, hidden_size=256, num_layers=3,
            num_attention_heads=4, max_position_embeddings=64),
        dist, kernel, pages=4098, page=16, batch=4, per_req=4, chunk=16)
    n_params = len(jax.tree.leaves(params))
    for name, hlo in texts.items():
        alias = re.search(r"input_output_alias=\{(.*?)\}, entry", hlo)
        assert alias, f"{name}: no input-output aliasing at all"
        for out, arg in ((0, n_params), (1, n_params + 1)):
            assert f"{{{out}}}: ({arg}, {{}}," in alias.group(1), \
                (name, alias.group(1))
        holders = _pool_holders(hlo, local)
        assert holders, f"{name}: the pool {local} is not in the program"
        stray = [h for h in holders if h[0] not in _POOL_CARRIERS]
        assert not stray, (name, stray)
        fused = [line for op, line in holders if op == "fusion"]
        for line in fused:  # a fusion may hold the pool only to scatter
            root = re.search(r"calls=(%[\w.-]+)", line).group(1)
            body = hlo.split(f"{root} ", 1)[1].split("\n}", 1)[0]
            assert re.search(r"ROOT \S+ = \S+ scatter\(", body), (name, line)
        # one in-place write a pool a program, and no layer-sized result
        assert len(fused) == 2, (name, fused)
        layer_sized = ",".join(map(str, local[1:]))
        assert not re.search(
            r"= \w+\[(?:1,)?%s\]" % layer_sized, hlo), name


# --------------------------------------------- the weights are cast ONCE

def _materialised(hlo: str) -> list:
    """``(shape, layout, opcode, line)`` of every array-valued instruction
    of a compiled program that is a buffer of its own: the instructions of
    the entry computation and of the loop bodies, fusions by their result —
    not what a fusion computes on the way (its body's lines)."""
    fused = set(re.findall(r" fusion\(.*?calls=(%[\w.-]+)", hlo))
    out, inside = [], None
    for line in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?(%[\w.-]+) \(.*\{$", line)
        if head:
            inside = head.group(1)
        elif inside not in fused and (m := re.search(
                r"= \w+\[([\d,]*)\](\{\S*)? ([\w-]+)\(", line)):
            out.append((m.group(1), m.group(2) or "", m.group(3),
                        line.strip()))
    return out


@SERVING_CASES
@SERVING_FORMS
def test_serving_programs_convert_no_weight(topo, monkeypatch, dist, kernel):
    """At GPT-345M's widths and the serve cells' geometry, the tree the
    engine passes holds no float32 leaf but the layer norms', and neither
    compiled program makes a second buffer of a weight stack or of the
    vocabulary matrix: no ``convert`` (the per-call cast this replaces),
    no ``copy`` or ``transpose`` (a leaf stored in a layout the products
    do not read), no fusion with such a result. The one thing allowed is
    the compiler's own prefetch of an argument into on-chip memory
    (``copy-start`` / ``copy-done`` to ``S(1)``, the layout unchanged),
    which takes the place of the product's read from HBM."""
    from fleetx_tpu.parallel.rules import tree_leaf_names

    params, _, texts = _compile_serving_programs(
        topo, monkeypatch, dict(
            vocab_size=50304, hidden_size=1024, num_layers=24,
            num_attention_heads=16, max_position_embeddings=1024),
        dist, kernel, pages=3586, page=16, batch=64, per_req=64, chunk=128)
    named = tree_leaf_names(params)
    f32 = {name for name, a in named if a.dtype == jnp.float32}
    assert f32 == {f"gpt/{g}/{leaf}" for g in ("layers/ln1", "layers/ln2",
                                               "ln_f")
                   for leaf in ("scale", "bias")}, f32
    assert all(a.dtype == jnp.bfloat16 for name, a in named
               if name not in f32)
    # the four stacked kernels and the vocabulary matrix, by shape (the
    # position table is as small as one layer's kernel; biases are vectors)
    stacks = {",".join(map(str, a.shape)): name for name, a in named
              if name.endswith("_kernel") or name.endswith("word_embeddings")}
    assert len(stacks) == 5, stacks
    for name, hlo in texts.items():
        held = [m for m in _materialised(hlo) if m[0] in stacks]
        assert {m[0] for m in held} == set(stacks), (name, held)
        for shape, layout, op, line in held:
            if op == "copy-done":
                assert "S(1)" in layout, (name, line)
                source = [m for m in held if m[0] == shape
                          and m[2] == "parameter"]
                assert source and source[0][1] == layout.replace(
                    "S(1)", ""), (name, line, source)
            else:
                assert op in ("parameter", "get-tuple-element"), (name, line)


def test_latent_attention_and_grouped_kernels_compile_for_the_v5e(
        topo, monkeypatch):
    """The kernels of the latent-attention sparse-expert family at the
    widths of ``joyai-flash-train-b2s8192`` (128 + 64 scores, 128 values,
    8,192 positions; 256-row tiles of 2,048 x 1,536 expert products)
    through the chip's own Mosaic compiler: VMEM, tiling and the aliased
    float32 accumulator are accepted, and the trace will find them by name."""
    from jax.sharding import SingleDeviceSharding

    from fleetx_tpu.ops import grouped_matmul, mla_attention

    monkeypatch.setattr(ops, "interpret", lambda: False)
    one = SingleDeviceSharding(topo.devices[0])

    def arr(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    heads, seq = 4, 8192
    attn = jax.jit(jax.grad(
        lambda *a: mla_attention.mla_flash_attention(
            *a, scale=192 ** -0.5).astype(jnp.float32).sum(),
        argnums=(0, 1, 2, 3, 4))).lower(
            arr((1, heads, seq, 128)), arr((1, heads // 2, seq, 128)),
            arr((1, heads, seq, 128)), arr((1, seq, 64)),
            arr((1, heads, seq, 128))).compile().as_text()
    for name in ("mla_flash_fwd", "mla_flash_bwd_dq", "mla_flash_bwd_dkv"):
        assert name in attn, name

    rows, tile, held, h, f2 = 4096, 256, 16, 2048, 1536

    def expert_products(xs, dy, w, acc, experts, n):
        out = grouped_matmul.moe_gmm(xs, w, experts, n, tile=tile,
                                     out_dtype=jnp.float32)
        dxs = grouped_matmul.moe_gmm(dy, w, experts, n, tile=tile,
                                     transpose_rhs=True)
        return out, dxs, grouped_matmul.moe_tgmm(xs, dy, acc, experts, n,
                                                 tile=tile)

    text = jax.jit(expert_products, donate_argnums=(3,)).lower(
        arr((rows, h)), arr((rows, f2)), arr((held, h, f2)),
        arr((held, h, f2), jnp.float32), arr((rows // tile,), jnp.int32),
        arr((), jnp.int32)).compile().as_text()
    for name in ("moe_gmm", "moe_gmm_t", "moe_tgmm"):
        assert name in text, name


@pytest.mark.parametrize("head_dim", [64, 128], ids=["gpt345m", "gpt1p3b"])
def test_gpt_flash_kernels_compile_at_the_train_cells_geometry(
        topo, monkeypatch, head_dim):
    """The forward and the fused backward at the geometry of the two GPT
    train cells (8 sequences of 1,024 a chip, 16 heads of 64 / of 128,
    bfloat16) through the chip's own Mosaic compiler: bfloat16 operands go
    to the products as they arrive, the lane-dense row statistics and the
    full-sequence dq window fit VMEM, and the trace will find both kernels
    by name."""
    from jax.sharding import SingleDeviceSharding

    from fleetx_tpu.ops import flash_attention as FA

    monkeypatch.setattr(ops, "interpret", lambda: False)
    qkv = jax.ShapeDtypeStruct((8, 1024, 16, head_dim), jnp.bfloat16,
                               sharding=SingleDeviceSharding(topo.devices[0]))
    assert FA.fused_backward_supported(qkv, qkv)
    text = jax.jit(jax.grad(
        lambda q, k, v: FA.flash_attention(q, k, v, causal=True).astype(
            jnp.float32).sum(), argnums=(0, 1, 2))).lower(
                qkv, qkv, qkv).compile().as_text()
    for name in ("flash_fwd", "flash_bwd_fused"):
        assert name in text, name
    assert "flash_bwd_dq" not in text


def test_paged_decode_compiles_at_the_serve_cells_geometry(topo, monkeypatch):
    """``_paged_call`` at the geometry of both serve cells (64 rows, 16
    heads of 64, both pools ``[24, 3585, 16, 1024]`` bfloat16, 64 table
    columns) through the chip's own Mosaic compiler: the two slots a pool
    fit VMEM, the page copies and the lane slice are tiled as the chip
    wants them, and the program holds ONE kernel call, under the name the
    trace finds it by, with each pool handed to it once."""
    from jax.sharding import SingleDeviceSharding

    from fleetx_tpu.ops import paged_attention as PA

    monkeypatch.setattr(ops, "interpret", lambda: False)
    one = SingleDeviceSharding(topo.devices[0])

    def arr(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    pool = arr((24, 3585, 16, 1024), jnp.bfloat16)
    text = jax.jit(PA._paged_call).lower(
        arr((64, 16, 64), jnp.bfloat16), pool, pool, arr((64, 64)),
        arr((64,)), arr(())).compile().as_text()
    calls = [line for line in text.splitlines()
             if "custom-call(" in line and "paged_decode" in line]
    assert len(calls) == 1, calls
    assert calls[0].count("bf16[24,3585,16,1024]") == 2, calls[0]


#: member -> (slots, query heads of a window layer, key-value heads, full
#: pool pages, table columns, ring pages, window, pages a fold of the pool,
#: pages a fold of a ring): both caches of each shipped recipe of the
#: second family, bfloat16, pages of 16 tokens
_SWA_CACHES = {
    "laguna_s": (64, 72, 8, 18001, 608, 64, 512, 16, 16),
    "smallthinker": (48, 28, 4, 28001, 816, 288, 4096, 32, 32),
}


@pytest.mark.parametrize("member", sorted(_SWA_CACHES))
def test_ring_fetch_compiles_at_the_second_familys_geometries(
        topo, monkeypatch, member):
    """Both decode calls of each member of the second family at its
    recipe's cache shapes, through the chip's own Mosaic compiler: the
    full layers' walk through the block table at the pages a fold its
    pool's page bytes give, and the window layers' with the ring fetch
    (each row's first ring page in the table's place, a run of pages one
    copy a buffer into ``[2, pages, 16, lanes]`` slots, read as one
    ``[pages · 16, lanes]`` tile): each fits VMEM, is ONE kernel call under
    the name the trace finds it by, and is handed each buffer once."""
    from jax.sharding import SingleDeviceSharding

    from fleetx_tpu.ops import paged_attention as PA

    monkeypatch.setattr(ops, "interpret", lambda: False)
    one = SingleDeviceSharding(topo.devices[0])

    def arr(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    slots, heads, kv, pages, cols, rp, window, fold, ring_fold = \
        _SWA_CACHES[member]
    geometry = dict(num_heads=heads, head_dim=128, page_size=16,
                    dtype=jnp.bfloat16, num_kv_heads=kv)
    assert PA.fold_shape(pages_per_req=cols, **geometry) == (fold, fold)
    assert PA.fold_shape(pages_per_req=rp, ring_pages=rp, **geometry) \
        == (ring_fold, 1)
    q = arr((slots, heads, 128), jnp.bfloat16)
    pool = arr((3, pages, 16, kv * 128), jnp.bfloat16)
    ring = arr((6, 1 + slots * rp, 16, kv * 128), jnp.bfloat16)
    calls = {
        "paged_decode": (pool, jax.jit(PA._paged_call).lower(
            q, pool, pool, arr((slots, cols)), arr((slots,)), arr(()))),
        "paged_decode_window": (ring, jax.jit(
            lambda *a: PA._paged_call(*a, window, rp)).lower(
            q, ring, ring, arr((slots,)), arr((slots,)), arr(()))),
    }
    for name, (buffer, lowered) in calls.items():
        assert set(mosaic_kernels(lowered.as_text())) == {name}
        found = [line for line in lowered.compile().as_text().splitlines()
                 if "custom-call(" in line and name in line]
        assert len(found) == 1, (name, found)
        shape = "bf16[%s]" % ",".join(map(str, buffer.shape))
        assert found[0].count(shape) == 2, found[0]


# ------------------------- the second serving family: two caches, one buffer
# each (serving/swa_moe.py)

_SWA_MEMBERS = {
    # the first member's shape: gated heads, 2 or 3 query heads to a
    # key-value head, a dense layer, a shared expert, a share of the experts
    "gated_share": dict(
        intermediate_size=512, num_hidden_layers=9, num_key_value_heads=2,
        num_attention_heads_per_layer=[4, 6, 6, 6, 4, 6, 6, 6, 4],
        layer_types=(["full_attention"] + ["sliding_attention"] * 3) * 2
        + ["full_attention"], mlp_only_layers=[0], num_experts=16,
        experts_held=4, num_experts_per_tok=2,
        shared_expert_intermediate_size=128, moe_routed_scaling_factor=2.5,
        gating="per-head", router_input="post_attention",
        router_scoring="softmax_topk", hidden_act="silu",
        rope_parameters={
            "full_attention": {"rope_theta": 500000, "rope_type": "yarn",
                               "factor": 128, "beta_slow": 1, "beta_fast": 32,
                               "original_max_position_embeddings": 8192,
                               "partial_rotary_factor": 0.5},
            "sliding_attention": {"rope_type": "default",
                                  "rope_theta": 10000}}),
    # the second's: 7 query heads to each of 4 key-value heads (28 query
    # rows fill no sublane tile evenly: one block of all the heads), every
    # expert held, the router before attention, full layers not rotated
    "whole_group7": dict(
        intermediate_size=0, num_hidden_layers=8, num_key_value_heads=4,
        num_attention_heads_per_layer=[28] * 8,
        layer_types=(["full_attention"] + ["sliding_attention"] * 3) * 2,
        mlp_only_layers=[], num_experts=16, experts_held=16,
        num_experts_per_tok=2, shared_expert_intermediate_size=0,
        moe_routed_scaling_factor=1.0, gating="none",
        router_input="pre_attention", router_scoring="topk_softmax",
        hidden_act="relu", rope_parameters={
            "full_attention": "none",
            "sliding_attention": {"rope_type": "default",
                                  "rope_theta": 1500000}}),
}


@pytest.mark.parametrize("member", sorted(_SWA_MEMBERS))
def test_swa_moe_programs_keep_both_caches_one_buffer(topo, monkeypatch,
                                                      member):
    """``decode`` and ``prefill`` of the windowed-attention sparse-expert
    family, compiled for the v5e at widths its kernels admit, in the shape
    of each of its two members: all four cache buffers (the paged pool's K
    and V, the rings' K and V) aliased from input to output, none held by
    anything but what enters, the loops' carries and the in-place row
    scatters, no layer of a cache cut out; the Mosaic kernels are in the
    programs under the names the trace finds them by (a gather fallback is
    not support: both decode kernels ENGAGE at 7 query heads to a
    key-value head)."""
    from jax.sharding import SingleDeviceSharding

    from fleetx_tpu.models.swa_moe import model as M
    from fleetx_tpu.models.swa_moe.config import config_from_dict
    from fleetx_tpu.serving import swa_moe as S
    from fleetx_tpu.serving.decode import SamplingParams

    monkeypatch.setattr(ops, "interpret", lambda: False)
    cfg = config_from_dict(dict(
        vocab_size=VOCAB, hidden_size=256, head_dim=128, sliding_window=1024,
        moe_intermediate_size=128, **_SWA_MEMBERS[member]))
    one = SingleDeviceSharding(topo.devices[0])

    def arr(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one)

    # caches too large for the compiler to stage in on-chip memory, as the
    # real ones are (abstract shapes: nothing is allocated)
    batch, page, per_req, chunk, pages = 64, 16, 128, 64, 8194
    assert not S.kernel_refusal(cfg, page_size=page, pages_per_req=per_req)
    params = jax.tree.map(lambda a: arr(a.shape, a.dtype),
                          M.served_template(cfg))
    full, ring = S.cache_shapes(cfg, num_pages=pages, page_size=page,
                                max_batch=batch, prefill_chunk=chunk)
    cache = [arr(full, jnp.bfloat16)] * 2 + [arr(ring, jnp.bfloat16)] * 2
    fns = S.make_step_fns(cfg, prefill_chunk=chunk, page_size=page,
                          sampling=SamplingParams(), paged_kernel=True)
    rng = arr((2,), jnp.uint32)
    programs = {
        "prefill": (params, *cache, arr((1, chunk)), arr((1, per_req)),
                    arr(()), arr(()), rng, arr((), jnp.uint32), arr(())),
        "decode": (params, *cache, arr((batch,)), arr(()), arr((1,)),
                   arr((batch, per_req)), arr((batch,)), rng,
                   arr((), jnp.uint32)),
    }
    kernels = {"prefill": {"moe_gmm_prefill"},
               "decode": {"moe_gmm_decode", "paged_decode",
                          "paged_decode_window"}}
    n_params = len(jax.tree.leaves(params))
    for name, args in programs.items():
        lowered = fns[name].lower(*args)
        assert set(mosaic_kernels(lowered.as_text())) == kernels[name], name
        hlo = lowered.compile().as_text()
        alias = re.search(r"input_output_alias=\{(.*?)\}, entry", hlo)
        assert alias, f"{name}: no input-output aliasing at all"
        for out in range(4):
            assert f"{{{out}}}: ({n_params + out}, {{}}," in alias.group(1), \
                (name, out, alias.group(1))
        for shape in (full, ring):
            holders = _pool_holders(hlo, shape)
            assert holders, f"{name}: the cache {shape} is not in the program"
            stray = [h for h in holders if h[0] not in _POOL_CARRIERS]
            assert not stray, (name, stray)
            for line in (ln for op, ln in holders if op == "fusion"):
                root = re.search(r"calls=(%[\w.-]+)", line).group(1)
                body = hlo.split(f"{root} ", 1)[1].split("\n}", 1)[0]
                assert re.search(r"ROOT \S+ = \S+ scatter\(", body), \
                    (name, line)
            layer_sized = ",".join(map(str, shape[1:]))
            assert not re.search(
                r"= \w+\[(?:1,)?%s\]" % layer_sized, hlo), (name, shape)


def test_gdn_mla_programs_keep_every_cache_one_buffer(topo, monkeypatch):
    """``decode`` and ``prefill`` of the linear-attention / latent-attention
    family, compiled for the v5e at widths its kernels admit (the published
    head sizes, a narrow hidden state): the latent pool, the recurrent state
    and the convolution's tail each aliased from input to output; the pool
    and the state held by nothing but what enters, the loops' carries, the
    in-place row writes and — the state — the ``gdn_decode`` kernel that
    updates it in place (no copy, no layer of either cut out); the three
    new Mosaic kernels in the programs under the names the trace finds them
    by."""
    from jax.sharding import SingleDeviceSharding

    from fleetx_tpu.models.gdn_mla import model as M
    from fleetx_tpu.models.gdn_mla.config import GDNMLAConfig
    from fleetx_tpu.serving import gdn_mla as S
    from fleetx_tpu.serving.decode import SamplingParams

    monkeypatch.setattr(ops, "interpret", lambda: False)
    cfg = GDNMLAConfig(
        vocab_size=VOCAB, hidden_size=256, intermediate_size=512,
        moe_intermediate_size=128, num_hidden_layers=5,
        first_k_dense_replace=1, full_attention_layers=(1,),
        num_attention_heads=8, q_lora_rank=128, kv_lora_rank=128,
        linear_num_key_heads=2, linear_num_value_heads=4,
        n_routed_experts=16, experts_held=8, num_experts_per_tok=3,
        rope_scaling={"type": "yarn", "factor": 8, "beta_fast": 32,
                      "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
                      "original_max_position_embeddings": 32768})
    one = SingleDeviceSharding(topo.devices[0])

    def arr(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one)

    # caches too large for the compiler to stage in on-chip memory, as the
    # real ones are (abstract shapes: nothing is allocated)
    batch, page, per_req, chunk, pages = 64, 16, 256, 128, 8194
    assert not S.kernel_refusal(cfg, page_size=page)
    params = jax.tree.map(lambda a: arr(a.shape, a.dtype),
                          M.served_template(cfg))
    pool, state, tail = S.cache_shapes(cfg, num_pages=pages, page_size=page,
                                       max_batch=batch)
    assert pool[-1] == 256 and state == (4, batch, 4, 128, 128)
    cache = [arr(pool, jnp.bfloat16), arr(state, jnp.float32),
             arr(tail, jnp.bfloat16)]
    fns = S.make_step_fns(cfg, prefill_chunk=chunk,
                          sampling=SamplingParams(), kernels=True,
                          latent_kernel=True)
    rng = arr((2,), jnp.uint32)
    programs = {
        "prefill": (params, *cache, arr((1, chunk)), arr((1, per_req)),
                    arr(()), arr(()), rng, arr((), jnp.uint32), arr(())),
        "decode": (params, *cache, arr((batch,)), arr(()), arr((1,)),
                   arr((batch, per_req)), arr((batch,)), rng,
                   arr((), jnp.uint32)),
    }
    kernels = {"prefill": {"moe_gmm_prefill", "gdn_chunk"},
               "decode": {"moe_gmm_decode", "gdn_decode",
                          "mla_paged_decode"}}
    # (a bitcast moves nothing: the one latent layer's pool seen without
    # its leading 1)
    in_place = _POOL_CARRIERS | {"custom-call", "dynamic-update-slice",
                                 "bitcast"}
    n_params = len(jax.tree.leaves(params))
    for name, args in programs.items():
        lowered = fns[name].lower(*args)
        assert set(mosaic_kernels(lowered.as_text())) == kernels[name], name
        hlo = lowered.compile().as_text()
        alias = re.search(r"input_output_alias=\{(.*?)\}, entry", hlo)
        assert alias, f"{name}: no input-output aliasing at all"
        for out in range(3):
            assert f"{{{out}}}: ({n_params + out}, {{}}," in alias.group(1), \
                (name, out, alias.group(1))
        for shape in (pool, state):
            holders = _pool_holders(hlo, shape)
            assert holders, f"{name}: the cache {shape} is not in the program"
            stray = [h for h in holders if h[0] not in in_place]
            assert not stray, (name, stray)


def test_conv_moe_programs_keep_both_caches_one_buffer(topo, monkeypatch):
    """``decode`` and ``prefill`` of the short-convolution family, compiled
    for the v5e at the recipe's head geometry — 4 query heads to each of 8
    key-value heads of 64: half a lane tile, a 512-lane pool — and a narrow
    expert width: both pools and the convolution's tail aliased from input
    to output; the pool held by nothing but what enters, the loops'
    carries, the in-place row writes and the kernel that reads it (no copy,
    no layer of it cut out); ``paged_decode`` in the decode program under
    the name the trace finds it by, the grouped products in both."""
    from jax.sharding import SingleDeviceSharding

    from fleetx_tpu.models.conv_moe import model as M
    from fleetx_tpu.models.conv_moe.config import CONV, FULL, ConvMoEConfig
    from fleetx_tpu.ops import paged_attention as PA
    from fleetx_tpu.serving import conv_moe as S
    from fleetx_tpu.serving.decode import SamplingParams

    monkeypatch.setattr(ops, "interpret", lambda: False)
    cfg = ConvMoEConfig(
        vocab_size=VOCAB, hidden_size=2048, intermediate_size=512,
        moe_intermediate_size=128, num_hidden_layers=5,
        layer_types=(CONV, FULL, CONV, CONV, CONV), num_dense_layers=1,
        num_experts=8, num_experts_per_tok=2,
        rope_parameters={"rope_theta": 1000000, "rope_type": "default"})
    one = SingleDeviceSharding(topo.devices[0])

    def arr(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one)

    # a pool too large for the compiler to stage in on-chip memory, as the
    # real one is (abstract shapes: nothing is allocated)
    batch, page, per_req, chunk, pages = 64, 16, 224, 128, 8193
    assert not S.kernel_refusal(cfg, page_size=page, pages_per_req=per_req)
    params = jax.tree.map(lambda a: arr(a.shape, a.dtype),
                          M.served_template(cfg))
    pool, tail = S.cache_shapes(cfg, num_pages=pages, page_size=page,
                                max_batch=batch)
    assert pool == (1, pages, page, 512) and tail == (4, 2, batch, 2048)
    cache = [arr(pool, jnp.bfloat16), arr(pool, jnp.bfloat16),
             arr(tail, jnp.bfloat16)]
    fns = S.make_step_fns(cfg, prefill_chunk=chunk,
                          sampling=SamplingParams(), paged_kernel=True)
    rng = arr((2,), jnp.uint32)
    programs = {
        "prefill": (params, *cache, arr((1, chunk)), arr((1, per_req)),
                    arr(()), arr(()), rng, arr((), jnp.uint32), arr(())),
        "decode": (params, *cache, arr((batch,)), arr(()), arr((1,)),
                   arr((batch, per_req)), arr((batch,)), rng,
                   arr((), jnp.uint32)),
    }
    kernels = {"prefill": {"moe_gmm_prefill"},
               "decode": {"moe_gmm_decode", "paged_decode"}}
    in_place = _POOL_CARRIERS | {"custom-call", "bitcast"}
    n_params = len(jax.tree.leaves(params))
    for name, args in programs.items():
        lowered = fns[name].lower(*args)
        assert set(mosaic_kernels(lowered.as_text())) == kernels[name], name
        hlo = lowered.compile().as_text()
        alias = re.search(r"input_output_alias=\{(.*?)\}, entry", hlo)
        assert alias, f"{name}: no input-output aliasing at all"
        for out in range(3):
            assert f"{{{out}}}: ({n_params + out}, {{}}," in alias.group(1), \
                (name, out, alias.group(1))
        holders = _pool_holders(hlo, pool)
        assert holders, f"{name}: the pool {pool} is not in the program"
        stray = [h for h in holders if h[0] not in in_place]
        assert not stray, (name, stray)


def test_samba_y_programs_keep_every_cache_one_buffer(topo, monkeypatch):
    """``decode`` and ``prefill`` of the decoder-hybrid-decoder family,
    compiled for the v5e at the recipe's head and scan geometry — 40 query
    heads over 10 key-value PAIRS of 128 lanes, two score maps a pair
    through zero-half queries; 5,120 scan channels of 16 states — and a
    narrow MLP: the pool, the rings, the states and the tails aliased from
    input to output; pool, ring and state held by nothing but what enters,
    the loops' carries, the in-place writes and the kernels that read them
    (no copy, no layer cut out); ``ssm_chunk`` and the one prefill row's
    ``paged_decode`` in the prefill program, ``ssm_decode``,
    ``paged_decode`` and ``paged_decode_window`` in the decode program,
    under the names the trace finds them by."""
    from jax.sharding import SingleDeviceSharding

    from fleetx_tpu.models.samba_y import model as M
    from fleetx_tpu.models.samba_y.config import SambaYConfig
    from fleetx_tpu.serving import samba_y as S
    from fleetx_tpu.serving.decode import SamplingParams

    monkeypatch.setattr(ops, "interpret", lambda: False)
    cfg = SambaYConfig(vocab_size=VOCAB, intermediate_size=512,
                       num_hidden_layers=8)
    one = SingleDeviceSharding(topo.devices[0])

    def arr(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one)

    # a pool too large for the compiler to stage in on-chip memory, as the
    # real one is (abstract shapes: nothing is allocated)
    batch, page, per_req, chunk, pages = 64, 16, 512, 512, 8193
    assert not S.kernel_refusal(cfg, page_size=page, pages_per_req=per_req,
                                prefill_chunk=chunk)
    params = jax.tree.map(lambda a: arr(a.shape, a.dtype),
                          M.served_template(cfg))
    pool, ring, state, tail = S.cache_shapes(
        cfg, num_pages=pages, page_size=page, max_batch=batch,
        prefill_chunk=chunk)
    assert pool == (1, pages, page, 1280) and state == (3, batch, 16, 5120)
    assert ring == (2, 1 + batch * 64, page, 1280)
    assert tail == (3, 3, batch, 5120)
    cache = [arr(pool, jnp.bfloat16), arr(pool, jnp.bfloat16),
             arr(ring, jnp.bfloat16), arr(ring, jnp.bfloat16),
             arr(state, jnp.float32), arr(tail, jnp.bfloat16)]
    fns = S.make_step_fns(cfg, prefill_chunk=chunk, page_size=page,
                          sampling=SamplingParams(), kernels=True)
    rng = arr((2,), jnp.uint32)
    programs = {
        "prefill": (params, *cache, arr((1, chunk)), arr((1, per_req)),
                    arr(()), arr(()), rng, arr((), jnp.uint32), arr(())),
        "decode": (params, *cache, arr((batch,)), arr(()), arr((1,)),
                   arr((batch, per_req)), arr((batch,)), rng,
                   arr((), jnp.uint32)),
    }
    kernels = {"prefill": {"ssm_chunk", "paged_decode"},
               "decode": {"ssm_decode", "paged_decode",
                          "paged_decode_window"}}
    in_place = _POOL_CARRIERS | {"custom-call", "dynamic-update-slice",
                                 "bitcast"}
    n_params = len(jax.tree.leaves(params))
    for name, args in programs.items():
        lowered = fns[name].lower(*args)
        assert set(mosaic_kernels(lowered.as_text())) == kernels[name], name
        hlo = lowered.compile().as_text()
        alias = re.search(r"input_output_alias=\{(.*?)\}, entry", hlo)
        assert alias, f"{name}: no input-output aliasing at all"
        for out in range(S.CACHES):
            assert f"{{{out}}}: ({n_params + out}, {{}}," in alias.group(1), \
                (name, out, alias.group(1))
        for shape in (pool, ring, state):
            holders = _pool_holders(hlo, shape)
            assert holders, f"{name}: the cache {shape} is not in the program"
            stray = [h for h in holders if h[0] not in in_place]
            assert not stray, (name, stray)


def test_ssm_mqa_programs_keep_every_cache_one_buffer(topo, monkeypatch):
    """``decode`` and ``prefill`` of the scan / multi-query family, compiled
    for the v5e at the recipe's head and scan geometry — 20 query heads in
    ONE block over one key-value head of 128, a pool one lane tile wide in
    pages of 128 tokens, 256 slots; 5,120 scan channels of 16 states with
    the three inner norms — and a narrow MLP: the pool, the states and the
    tails aliased from input to output; pool and state held by nothing but
    what enters, the loops' carries, the in-place writes and the kernels that
    read them (no copy, no layer cut out); ``ssm_chunk`` in the prefill
    program, ``ssm_decode`` and ``paged_decode`` in the decode program,
    under the names the trace finds them by; the scan layers walked in
    loops (a run of one layer is unrolled)."""
    from jax.sharding import SingleDeviceSharding

    from fleetx_tpu.models.ssm_mqa import model as M
    from fleetx_tpu.models.ssm_mqa.config import SSMMQAConfig
    from fleetx_tpu.serving import ssm_mqa as S
    from fleetx_tpu.serving.decode import SamplingParams

    monkeypatch.setattr(ops, "interpret", lambda: False)
    # period 4, offset 1: scan, full, scan x 3, full, scan x 2
    cfg = SSMMQAConfig(vocab_size=VOCAB, intermediate_size=512,
                       num_hidden_layers=8, attn_layer_period=4,
                       attn_layer_offset=1)
    one = SingleDeviceSharding(topo.devices[0])

    def arr(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one)

    # a pool too large for the compiler to stage in on-chip memory, as the
    # real one is (abstract shapes: nothing is allocated)
    batch, page, per_req, chunk, pages = 256, 128, 144, 512, 8193
    assert not S.kernel_refusal(cfg, page_size=page, pages_per_req=per_req,
                                prefill_chunk=chunk, max_batch=batch)
    params = jax.tree.map(lambda a: arr(a.shape, a.dtype),
                          M.served_template(cfg))
    pool, state, tail = S.cache_shapes(cfg, num_pages=pages, page_size=page,
                                       max_batch=batch)
    assert pool == (2, pages, page, 128) and state == (6, batch, 16, 5120)
    assert tail == (6, 3, batch, 5120)
    cache = [arr(pool, jnp.bfloat16), arr(pool, jnp.bfloat16),
             arr(state, jnp.float32), arr(tail, jnp.bfloat16)]
    fns = S.make_step_fns(cfg, prefill_chunk=chunk, sampling=SamplingParams(),
                          kernels=True)
    rng = arr((2,), jnp.uint32)
    programs = {
        "prefill": (params, *cache, arr((1, chunk)), arr((1, per_req)),
                    arr(()), arr(()), rng, arr((), jnp.uint32), arr(())),
        "decode": (params, *cache, arr((batch,)), arr(()), arr((1,)),
                   arr((batch, per_req)), arr((batch,)), rng,
                   arr((), jnp.uint32)),
    }
    kernels = {"prefill": {"ssm_chunk"},
               "decode": {"ssm_decode", "paged_decode"}}
    in_place = _POOL_CARRIERS | {"custom-call", "dynamic-update-slice",
                                 "bitcast"}
    n_params = len(jax.tree.leaves(params))
    for name, args in programs.items():
        lowered = fns[name].lower(*args)
        assert set(mosaic_kernels(lowered.as_text())) == kernels[name], name
        hlo = lowered.compile().as_text()
        alias = re.search(r"input_output_alias=\{(.*?)\}, entry", hlo)
        assert alias, f"{name}: no input-output aliasing at all"
        for out in range(S.CACHES):
            assert f"{{{out}}}: ({n_params + out}, {{}}," in alias.group(1), \
                (name, out, alias.group(1))
        for shape in (pool, state):
            holders = _pool_holders(hlo, shape)
            assert holders, f"{name}: the cache {shape} is not in the program"
            stray = [h for h in holders if h[0] not in in_place]
            assert not stray, (name, stray)

