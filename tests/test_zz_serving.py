"""Serving runtime: paged KV cache, continuous batching, drain, fleet.

In-process tests pin the scheduler/allocator semantics and the decode
parity contract (paged continuous-batching decode must be token-identical
to one-shot ``generation.generate``); subprocess tests drive the REAL
fleet machinery — a replica draining on an injected SIGTERM
(``faults.py sigterm_at``) and the 2-replica supervised acceptance drill
(kill one replica mid-stream; the router must complete every admitted
request with token-correct output).

Named ``test_zz_*`` so it collects last (same stance as the other zz
suites): subprocess drills must add coverage after the seed dots, not
displace them inside the tier-1 timeout window.
"""

import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from fleetx_tpu.models.gpt import generation as G
from fleetx_tpu.models.gpt.model import (GPTConfig, GPTForPretraining,
                                         config_from_dict)
from fleetx_tpu.observability.schema import (SERVING_METRIC_NAMES,
                                             validate_serving_record)
from fleetx_tpu.serving import (NULL_PAGE, PageAllocator, ServingConfig,
                                ServingEngine)
from fleetx_tpu.serving.decode import SamplingParams

pytestmark = pytest.mark.serving

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SERVE = os.path.join(REPO, "tools", "serve.py")
SUPERVISE = os.path.join(REPO, "tools", "supervise.py")

MODEL_DICT = dict(vocab_size=97, hidden_size=64, num_layers=2,
                  num_attention_heads=4, max_position_embeddings=64,
                  hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0,
                  use_flash_attention=False, dtype="float32",
                  param_dtype="float32")
EOS = 96


def _loopback_available() -> bool:
    """Subprocess socket drills need a bindable loopback (sandbox gate)."""
    try:
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
    except OSError:
        return False
    return True


needs_net = pytest.mark.skipif(not _loopback_available(),
                               reason="loopback networking unavailable")


# ---------------------------------------------------------------------------
# page allocator units
# ---------------------------------------------------------------------------

class TestPageAllocator:
    """Host-side free-list semantics the admission policy stands on."""

    def test_alloc_free_roundtrip_never_hands_out_null_page(self):
        a = PageAllocator(num_pages=5, page_size=4)
        assert a.usable_pages == 4 and a.free_pages == 4
        pages = a.alloc(4)
        assert pages is not None and len(set(pages)) == 4
        assert NULL_PAGE not in pages
        assert a.free_pages == 0 and a.occupancy() == 1.0
        a.free(pages)
        assert a.free_pages == 4 and a.allocated_pages == 0
        assert a.occupancy() == 0.0

    def test_oom_alloc_is_all_or_nothing(self):
        a = PageAllocator(num_pages=4, page_size=4)
        assert a.alloc(4) is None  # only 3 usable — no partial grant
        assert a.free_pages == 3
        first = a.alloc(2)
        assert a.alloc(2) is None and a.free_pages == 1
        a.free(first)
        assert a.alloc(3) is not None

    def test_fits_ever_vs_can_allocate(self):
        a = PageAllocator(num_pages=4, page_size=4)
        held = a.alloc(2)
        # could fit once pages free → wait; larger than the pool → refuse
        assert a.fits_ever(3) and not a.can_allocate(3)
        assert not a.fits_ever(4)
        a.free(held)
        assert a.can_allocate(3)

    def test_pages_needed_and_fragmentation(self):
        a = PageAllocator(num_pages=9, page_size=4)
        assert a.pages_needed(1) == 1 and a.pages_needed(4) == 1
        assert a.pages_needed(5) == 2 and a.pages_needed(0) == 1
        a.alloc(2)  # 8 slots reserved
        assert a.internal_fragmentation(used_slots=6) == pytest.approx(0.25)
        assert a.internal_fragmentation(used_slots=8) == 0.0
        assert a.internal_fragmentation(used_slots=0) == 1.0

    def test_free_list_reuses_freed_pages(self):
        a = PageAllocator(num_pages=4, page_size=4)
        pages = a.alloc(3)
        a.free(pages)
        again = a.alloc(3)
        assert sorted(again) == sorted(pages)


# ---------------------------------------------------------------------------
# decode parity (the serving acceptance contract)
# ---------------------------------------------------------------------------

def _build_model(**over):
    from flax.core import meta

    cfg = config_from_dict(dict(MODEL_DICT, **over))
    model = GPTForPretraining(cfg)
    params = model.init({"params": jax.random.PRNGKey(0)},
                        jnp.zeros((1, 8), jnp.int32), None,
                        deterministic=True)["params"]
    return cfg, model, meta.unbox(params)


@pytest.fixture(scope="module")
def small_model():
    """The tiny f32 GPT shared by every parity test (same recipe as
    tests/test_generation.py)."""
    return _build_model()


@pytest.fixture(scope="module")
def deep_model():
    """Three layers, each with its own weights, so each layer of the pool
    holds different keys and values: an attention that reads layer 0 (or
    any one layer) every time decodes other tokens than the reference."""
    return _build_model(num_layers=3)


#: the parity tests run on both: ``request.getfixturevalue(which)``
MODELS = ["small_model", "deep_model"]


def one_shot(model, params, prompts, max_new):
    """Reference decode: one-shot batched greedy generation."""
    gen_cfg = G.GenerationConfig(max_new_tokens=max_new, do_sample=False,
                                 eos_token_id=EOS, pad_token_id=0)
    tokens, mask = G.left_pad(prompts, 0)
    return np.asarray(G.generate(model, params, gen_cfg,
                                 jnp.asarray(tokens), jnp.asarray(mask),
                                 jax.random.PRNGKey(1)))


def check_parity(req, want_row):
    """Serving tokens must equal the one-shot row (eos-trimmed)."""
    got = req.tokens
    want = [int(t) for t in want_row]
    assert got == want[:len(got)], (req.id, got, want)
    assert len(got) == len(want) or got[-1] == EOS, (req.id, got, want)


@pytest.fixture()
def engine(small_model):
    cfg, _, params = small_model
    eng = ServingEngine(
        cfg, params,
        ServingConfig(max_batch=4, page_size=4, num_pages=33,
                      max_seq_len=32, prefill_chunk=4),
        eos_token_id=EOS)
    # the metrics registry is process-global (one engine per process in
    # production); tests share it, so zero the serving stats per engine
    eng.reset_stats()
    return eng


def test_continuous_batching_matches_one_shot(small_model, engine):
    """Ragged prompts (one longer than the prefill chunk → chunked
    prefill) decoded through the paged runtime are token-identical to
    one-shot batch generation."""
    cfg, model, params = small_model
    prompts = [[5, 9, 23, 41], [7, 3],
               [11, 2, 8, 4, 19, 33, 7, 6, 1, 2, 3]]  # 11 > chunk of 4
    want = one_shot(model, params, prompts, 6)
    reqs = [engine.submit(p, 6, request_id=f"r{i}")
            for i, p in enumerate(prompts)]
    engine.run_until_drained()
    for req, row in zip(reqs, want):
        assert req.state == "finished" and req.error is None
        check_parity(req, row)
    assert engine.allocator.allocated_pages == 0  # everything freed


def test_join_mid_stream_and_never_retraces(small_model, engine):
    """A request joining while another decodes must not perturb the
    in-flight stream, and the join must not recompile either program."""
    cfg, model, params = small_model
    want = one_shot(model, params, [[5, 9, 23, 41]], 8)
    want_b = one_shot(model, params, [[7, 3, 11]], 8)
    a = engine.submit([5, 9, 23, 41], 8, request_id="a")
    for _ in range(4):  # prefill + a few decode steps
        engine.step()
    assert a.state == "running" and len(a.tokens) >= 1
    b = engine.submit([7, 3, 11], 8, request_id="b")  # joins mid-stream
    engine.run_until_drained()
    check_parity(a, want[0])
    check_parity(b, want_b[0])
    # static shapes: one compile per program for the engine's lifetime
    assert engine._fns["decode"]._cache_size() == 1
    assert engine._fns["prefill"]._cache_size() == 1


def test_admission_oom_refusal_queueing_and_drain(small_model):
    """Permanently-oversized requests refuse at submit (the worst-case
    bound holds even under lazy admission); requests that merely don't
    fit NOW wait for pages; drain refuses new work but finishes
    everything admitted."""
    cfg, _, params = small_model
    eng = ServingEngine(
        cfg, params,
        ServingConfig(max_batch=4, page_size=4, num_pages=4,  # 3 usable
                      max_seq_len=16, prefill_chunk=4),
        eos_token_id=EOS)
    eng.reset_stats()
    # 17 tokens > max_seq_len 16 → permanent refusal, never queued
    r_oom = eng.submit([1] * 9, 8, request_id="oom")
    assert r_oom.state == "refused" and "oom" in r_oom.error
    # 16 tokens fit max_seq_len but need 4 pages > 3 usable → refusal too
    # (refusal keys off the WORST case, not the lazy admission grant: a
    # request the pool could only hold by preempting forever is refused)
    r_oom2 = eng.submit([1] * 8, 8, request_id="oom2")
    assert r_oom2.state == "refused" and "oom" in r_oom2.error

    r1 = eng.submit([5, 9, 23, 41], 8, request_id="r1")
    r2 = eng.submit([7, 3], 8, request_id="r2")
    eng.step()
    assert r1.state in ("prefill", "running")
    # lazy grant: prompt page + 1 watermark page, NOT the 3-page worst
    # case reserve-up-front would take
    assert len(r1.pages) == 2
    assert r2.state == "waiting"  # only 1 page free — r2 (needs 2) waits
    assert eng.metrics.gauge("serving_queue_depth").value == 1

    eng.begin_drain()
    r3 = eng.submit([1, 2], 2, request_id="late")
    assert r3.state == "refused" and r3.error == "draining"
    eng.run_until_drained()
    assert r1.state == "finished" and r2.state == "finished"
    assert eng.metrics.counter("serving_requests_completed").value == 2
    assert eng.metrics.counter("serving_requests_refused").value == 3


@pytest.mark.parametrize("paged_kernel", [True, False],
                         ids=["kernel", "gather"])
@pytest.mark.parametrize("which", MODELS)
def test_quantized_decode_parity_bounded(request, which, paged_kernel):
    """The int8-activation decode path (Quantization.qat_act_bits) stays
    within a bounded drift of the fp path — same stance as the PR 3 remat
    drift tests — and still decodes mostly the same greedy tokens on the
    tiny model, through the layer-indexed kernel and through the gather."""
    cfg, _, params = request.getfixturevalue(which)
    qcfg = config_from_dict(dict(MODEL_DICT, num_layers=cfg.num_layers,
                                 qat_act_bits=8))
    prompts = [[5, 9, 23, 41], [7, 3, 11]]

    def run(quantize):
        eng = ServingEngine(
            qcfg, params,
            ServingConfig(max_batch=2, page_size=4, num_pages=17,
                          max_seq_len=32, prefill_chunk=8,
                          quantize_decode=quantize,
                          paged_kernel=paged_kernel),
            eos_token_id=EOS)
        assert eng.paged_kernel_active == paged_kernel
        reqs = [eng.submit(p, 6, request_id=f"q{i}")
                for i, p in enumerate(prompts)]
        eng.run_until_drained()
        # drift probe: the first-step logits of prompt 0, via the raw
        # prefill program (deterministic, same pages each run)
        pool_k, pool_v = eng.pool_k, eng.pool_v
        table = np.zeros((1, eng.pages_per_req), np.int32)
        table[0, :2] = [1, 2]
        tokens = np.zeros((1, 8), np.int32)
        tokens[0, :4] = prompts[0]
        _, _, _, logits = eng._fns["prefill"](
            eng.params, pool_k, pool_v, tokens, table, np.int32(0),
            np.int32(4), jax.random.PRNGKey(0), np.uint32(0))
        return [r.tokens for r in reqs], np.asarray(logits)[0]

    fp_tokens, fp_logits = run(False)
    q_tokens, q_logits = run(True)
    drift = np.abs(q_logits - fp_logits).max() / \
        max(np.abs(fp_logits).max(), 1e-9)
    assert drift < 0.05, f"int8-act decode drifted {drift:.4f} from fp"
    # token streams may diverge after a near-tie, but not wholesale
    agree = sum(a == b for a, b in zip(fp_tokens[0], q_tokens[0]))
    assert agree >= len(fp_tokens[0]) // 2, (fp_tokens, q_tokens)


@pytest.mark.parametrize("which", MODELS)
def test_pool_sharded_over_mesh_keeps_parity(request, which, devices8):
    """Pages shard over fsdp, heads over tensor: capacity scales with the
    mesh and greedy decode stays token-identical."""
    from fleetx_tpu.parallel.mesh import build_mesh

    cfg, model, params = request.getfixturevalue(which)
    mesh = build_mesh({"fsdp_degree": 2, "mp_degree": 2})
    eng = ServingEngine(
        cfg, params,
        ServingConfig(max_batch=2, page_size=4, num_pages=32,
                      max_seq_len=32, prefill_chunk=4),
        eos_token_id=EOS, mesh=mesh)
    def norm(spec):
        # PartitionSpec canonicalisation may drop trailing Nones
        return (tuple(spec) + (None,) * 4)[:4]

    assert eng.pool_k.shape == (cfg.num_layers, 32, 4, 64)
    assert norm(eng.pool_k.sharding.spec) == (None, "fsdp", None, "tensor")
    want = one_shot(model, params, [[5, 9, 23, 41], [7, 3]], 6)
    reqs = [eng.submit(p, 6, request_id=f"m{i}")
            for i, p in enumerate([[5, 9, 23, 41], [7, 3]])]
    eng.run_until_drained()
    for req, row in zip(reqs, want):
        check_parity(req, row)
    # the pool stays sharded through the donated-buffer step updates
    assert norm(eng.pool_k.sharding.spec) == (None, "fsdp", None, "tensor")


def test_registry_sharded_weights_compose_with_sharded_pool(devices8,
                                                            tmp_path):
    """ROADMAP items 1+4, last rung: replica WEIGHTS restore through the
    partition-rule registry onto the serving mesh
    (``load_params(mesh=...)``, the tools/serve.py ckpt_dir path) instead
    of a replicated host load — and compose with the fsdp/tensor-sharded
    page pool at token parity with the one-shot reference."""
    from flax.core import meta as flax_meta

    from fleetx_tpu.core import checkpoint as ckpt_lib
    from fleetx_tpu.parallel import rules as R
    from fleetx_tpu.parallel.mesh import build_mesh

    # tensor-divisible variant of the tiny model (vocab 97 cannot split
    # over mp=2; the parity reference EOS stays 96)
    cfg = config_from_dict(dict(MODEL_DICT, vocab_size=128))
    model = GPTForPretraining(cfg)
    params = flax_meta.unbox(model.init(
        {"params": jax.random.PRNGKey(0)}, jnp.zeros((1, 8), jnp.int32),
        None, deterministic=True)["params"])
    ckpt_lib.save_checkpoint(
        str(tmp_path), 0, {"params": params},
        meta={"spec_family": "gpt",
              "spec_registry": R.registry_fingerprint()})
    mesh = build_mesh({"fsdp_degree": 2, "mp_degree": 2})
    loaded = ckpt_lib.load_params(str(tmp_path), mesh=mesh)
    flat = dict(R.tree_leaf_names(loaded))
    # registry placement, not a replicated host load
    assert tuple(
        flat["gpt/embeddings/word_embeddings"].sharding.spec) == \
        ("tensor",)
    assert "tensor" in str(
        flat["gpt/layers/attn/qkv_kernel"].sharding.spec)
    eng = ServingEngine(
        cfg, loaded,
        ServingConfig(max_batch=2, page_size=4, num_pages=32,
                      max_seq_len=32, prefill_chunk=4),
        eos_token_id=EOS, mesh=mesh)
    prompts = [[5, 9, 23, 41], [7, 3]]
    want = one_shot(model, params, prompts, 6)
    reqs = [eng.submit(p, 6, request_id=f"w{i}")
            for i, p in enumerate(prompts)]
    eng.run_until_drained()
    for req, row in zip(reqs, want):
        check_parity(req, row)

    def norm(spec):
        return (tuple(spec) + (None,) * 4)[:4]

    # pool AND weights sharded simultaneously, through the whole run
    assert norm(eng.pool_k.sharding.spec) == (None, "fsdp", None, "tensor")


# ---------------------------------------------------------------------------
# the weights are cast ONCE, when the engine is built (PR 33)
# ---------------------------------------------------------------------------

#: the recipes' dtypes: bfloat16 products over float32 parameters
RECIPE_DTYPES = dict(dtype="bfloat16", param_dtype="float32")


def _is_norm(name):
    return name.split("/")[-2] in ("ln1", "ln2", "ln_f")


def _bits(a):
    return np.asarray(a).view(np.uint16 if a.dtype == jnp.bfloat16
                              else np.uint32)


def test_serving_params_casts_each_leaf_once_and_keeps_shardings(devices8):
    """bfloat16 / float32, the leaves on the partition rules' shardings:
    every kernel, bias and embedding table of the serving tree is the
    model tree's leaf ``.astype(bfloat16)`` bit for bit on the sharding it
    came with, the layer norms' leaves ARE the float32 originals, the
    caller's tree is left whole, and a tree that needs nothing comes back
    as it went in."""
    from fleetx_tpu.parallel import rules as R
    from fleetx_tpu.parallel.mesh import build_mesh
    from fleetx_tpu.serving.decode import serving_params

    cfg, _, params = _build_model(vocab_size=128, **RECIPE_DTYPES)
    mesh = build_mesh({"fsdp_degree": 2, "mp_degree": 2})
    params = jax.device_put(params, R.named_shardings(params, mesh, "gpt"))
    served = serving_params(params, cfg)
    cast = 0
    for (name, a), (_, b) in zip(R.tree_leaf_names(params),
                                 R.tree_leaf_names(served)):
        assert a.dtype == jnp.float32 and not a.is_deleted(), name
        if _is_norm(name):
            assert b is a, name
            continue
        cast += 1
        assert b.dtype == jnp.bfloat16, name
        assert np.array_equal(_bits(b), _bits(a.astype(jnp.bfloat16))), name
        assert b.sharding.is_equivalent_to(a.sharding, a.ndim), \
            (name, a.sharding, b.sharding)
    assert cast == 10
    assert "tensor" in str(
        served["gpt"]["layers"]["attn"]["qkv_kernel"].sharding.spec)
    again = serving_params(served, cfg)
    assert again is served


def test_serving_params_is_the_identity_at_the_models_dtype(small_model):
    """float32 / float32 (every CPU recipe): the function returns the
    leaves it was given, and the engine keeps them."""
    from fleetx_tpu.serving.decode import serving_params

    cfg, _, params = small_model
    served = serving_params(params, cfg)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(served)):
        assert b is a
    eng = ServingEngine(cfg, params, ServingConfig(
        max_batch=2, page_size=4, num_pages=9, max_seq_len=16,
        prefill_chunk=4), eos_token_id=EOS)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(eng.params)):
        assert b is a


def test_step_fns_refuse_a_tree_they_would_have_to_cast():
    """A float32 tree handed straight to the programs at a bfloat16
    ``dtype`` cannot silently pay the casts again: tracing refuses it."""
    from fleetx_tpu.serving.decode import make_step_fns, serving_params
    from fleetx_tpu.serving.paged_cache import init_pool

    cfg, _, params = _build_model(**RECIPE_DTYPES)
    fns = make_step_fns(cfg, max_batch=2, pages_per_req=4, prefill_chunk=4,
                        sampling=SamplingParams())
    args = (np.zeros((2,), np.int32), np.int32(-1), np.zeros((1,), np.int32),
            np.zeros((2, 4), np.int32), np.full((2,), -1, np.int32),
            jax.random.PRNGKey(0), np.uint32(0))
    with pytest.raises(TypeError, match="10 leaves are not in bfloat16"):
        fns["decode"](params, *init_pool(cfg, 9, 4), *args)
    fns["decode"](serving_params(params, cfg), *init_pool(cfg, 9, 4), *args)


@pytest.mark.parametrize("quantize", [False, True], ids=["fp", "int8"])
def test_engine_casts_once_what_a_hand_cast_tree_serves(quantize, caplog):
    """An engine built from the float32 tree and one built from the same
    tree cast by hand serve the same greedy tokens and the same first-step
    logits, bit for bit, with and without ``quantize_decode``; the first
    says it cast ten leaves, the second none; the jit caches hold one
    entry each."""
    from fleetx_tpu.parallel.rules import tree_leaf_names
    from fleetx_tpu.utils.log import logger as fx_logger

    cfg, _, params = _build_model(qat_act_bits=8, **RECIPE_DTYPES)
    names = [name for name, _ in tree_leaf_names(params)]
    by_hand = jax.tree.unflatten(jax.tree.structure(params), [
        a if _is_norm(name) else a.astype(jnp.bfloat16)
        for name, a in zip(names, jax.tree.leaves(params))])
    prompts = [[5, 9, 23, 41], [7, 3, 11, 2, 8, 4, 19, 33, 7]]

    def run(tree):
        caplog.clear()
        fx_logger.addHandler(caplog.handler)   # the logger does not propagate
        try:
            eng = ServingEngine(
                cfg, tree,
                ServingConfig(max_batch=2, page_size=4, num_pages=17,
                              max_seq_len=32, prefill_chunk=4,
                              quantize_decode=quantize),
                eos_token_id=EOS)
        finally:
            fx_logger.removeHandler(caplog.handler)
        said = [r.getMessage() for r in caplog.records
                if r.getMessage().startswith("serving engine:")]
        assert len(said) == 1, caplog.text
        reqs = [eng.submit(p, 6, request_id=f"c{i}")
                for i, p in enumerate(prompts)]
        eng.run_until_drained()
        assert eng._fns["decode"]._cache_size() == 1
        assert eng._fns["prefill"]._cache_size() == 1
        table = np.zeros((1, eng.pages_per_req), np.int32)
        table[0, 0] = 1
        tokens = np.asarray([prompts[0]], np.int32)
        _, _, _, logits = eng._fns["prefill"](
            eng.params, eng.pool_k, eng.pool_v, tokens, table, np.int32(0),
            np.int32(4), *eng._draw())
        assert eng._fns["prefill"]._cache_size() == 1
        return eng, said[0], [r.tokens for r in reqs], np.asarray(logits)

    eng, said, tokens, logits = run(params)
    assert "weights: 10 leaves cast float32 -> bfloat16, serving tree " \
        f"{sum(a.nbytes for a in jax.tree.leaves(by_hand))} bytes" in said
    for (name, a), b in zip(tree_leaf_names(eng.params),
                            jax.tree.leaves(by_hand)):
        assert a.dtype == b.dtype and np.array_equal(_bits(a), _bits(b)), name
    eng_h, said_h, tokens_h, logits_h = run(by_hand)
    assert "weights: 0 leaves cast, serving tree " in said_h
    for a, b in zip(jax.tree.leaves(eng_h.params), jax.tree.leaves(by_hand)):
        assert a is b
    assert all(len(t) == 6 for t in tokens) and tokens == tokens_h
    assert np.array_equal(logits, logits_h)


# ---------------------------------------------------------------------------
# in-kernel paged attention: path pins, predicate, fallback (PR 18)
# ---------------------------------------------------------------------------

def _decode_jaxpr(eng):
    """The traced decode program (pins which attention path compiled)."""
    return str(jax.make_jaxpr(eng._fns["decode"])(
        eng.params, eng.pool_k, eng.pool_v, eng._tokens, np.int32(-1),
        np.zeros((1,), np.int32), eng._block_tables, eng._lens,
        jax.random.PRNGKey(0), np.uint32(0)))


def test_null_page_constant_pinned_across_modules():
    """ops/paged_attention.py keeps a LOCAL copy of NULL_PAGE (no import
    cycle into serving); this pin is what makes that copy safe."""
    from fleetx_tpu.ops import paged_attention as PA

    assert PA.NULL_PAGE == NULL_PAGE


def test_paged_attention_support_predicate():
    from fleetx_tpu.ops import paged_attention as PA

    ok = dict(num_heads=4, head_dim=16, page_size=4, pages_per_req=8)
    assert PA.paged_attention_supported(**ok)
    assert not PA.paged_attention_supported(
        **dict(ok, head_dim=12))         # not a multiple of 8
    assert not PA.paged_attention_supported(
        **dict(ok, head_dim=512))        # over the lane budget
    assert not PA.paged_attention_supported(
        **dict(ok), dtype=jnp.float16)   # unsupported pool dtype
    assert PA.paged_attention_supported(**dict(ok), dtype=jnp.bfloat16)


@pytest.mark.parametrize("which", MODELS)
def test_kernel_vs_gather_parity_and_compiled_path_pinned(request, which):
    """The SAME prompts through a kernel engine and a forced-gather
    engine decode token-identically to the one-shot reference, and the
    jaxpr pins which attention path each engine compiled — a silent
    fallback (predicate regression) fails here, not in a perf chart."""
    cfg, model, params = request.getfixturevalue(which)
    prompts = [[5, 9, 23, 41], [7, 3],
               [11, 2, 8, 4, 19, 33, 7, 6, 1, 2, 3]]  # chunked prefill
    want = one_shot(model, params, prompts, 6)

    def run(paged_kernel):
        eng = ServingEngine(
            cfg, params,
            ServingConfig(max_batch=4, page_size=4, num_pages=33,
                          max_seq_len=32, prefill_chunk=4,
                          paged_kernel=paged_kernel),
            eos_token_id=EOS)
        reqs = [eng.submit(p, 6, request_id=f"k{int(paged_kernel)}{i}")
                for i, p in enumerate(prompts)]
        eng.run_until_drained()
        return eng, reqs

    eng_k, reqs_k = run(True)
    eng_g, reqs_g = run(False)
    assert eng_k.paged_kernel_active and not eng_g.paged_kernel_active
    for req, row in zip(reqs_k, want):
        check_parity(req, row)
    for req, row in zip(reqs_g, want):
        check_parity(req, row)
    # path pin: exactly the requested attention compiled into decode
    assert "pallas_call" in _decode_jaxpr(eng_k)
    assert "pallas_call" not in _decode_jaxpr(eng_g)
    # prefill stays gather on BOTH engines (S>1 chunks); no-retrace pin
    for eng in (eng_k, eng_g):
        assert eng._fns["decode"]._cache_size() == 1
        assert eng._fns["prefill"]._cache_size() == 1
    # both engines wrote the same rows into ONE pool, and every layer of
    # it holds its own: reading layer 0 for layer l cannot pass above
    pool_k, pool_g = np.asarray(eng_k.pool_k), np.asarray(eng_g.pool_k)
    assert pool_k.shape == (cfg.num_layers, 33, 4, 64)
    np.testing.assert_allclose(pool_k[:, 1:], pool_g[:, 1:], atol=1e-5)
    for l in range(1, cfg.num_layers):
        assert np.abs(pool_k[l, 1:] - pool_k[0, 1:]).max() > 0.1


def test_kernel_predicate_rejects_config_and_falls_back(small_model):
    """A head_dim the kernel cannot tile (15 — not a multiple of 8) must
    quietly compile the gather path even with paged_kernel requested, at
    full token parity."""
    from flax.core import meta

    bad_cfg = config_from_dict(dict(MODEL_DICT, hidden_size=60))  # hd 15
    model = GPTForPretraining(bad_cfg)
    params = meta.unbox(model.init({"params": jax.random.PRNGKey(0)},
                                   jnp.zeros((1, 8), jnp.int32), None,
                                   deterministic=True)["params"])
    eng = ServingEngine(
        bad_cfg, params,
        ServingConfig(max_batch=2, page_size=4, num_pages=17,
                      max_seq_len=32, prefill_chunk=4, paged_kernel=True),
        eos_token_id=EOS)
    assert not eng.paged_kernel_active
    want = one_shot(model, params, [[5, 9, 23]], 6)
    req = eng.submit([5, 9, 23], 6, request_id="fb")
    eng.run_until_drained()
    check_parity(req, want[0])
    assert "pallas_call" not in _decode_jaxpr(eng)


@pytest.mark.parametrize("which", MODELS)
def test_sharded_pool_runs_kernel_path(request, which, devices8):
    """The fsdp/tensor-sharded pool admits the kernel (page and head
    counts divide the mesh) and compiles it — the sharded parity test
    above then covers its token output."""
    from fleetx_tpu.parallel.mesh import build_mesh

    cfg, model, params = request.getfixturevalue(which)
    mesh = build_mesh({"fsdp_degree": 2, "mp_degree": 2})
    eng = ServingEngine(
        cfg, params,
        ServingConfig(max_batch=2, page_size=4, num_pages=32,
                      max_seq_len=32, prefill_chunk=4),
        eos_token_id=EOS, mesh=mesh)
    assert eng.paged_kernel_active
    want = one_shot(model, params, [[5, 9, 23, 41]], 6)
    req = eng.submit([5, 9, 23, 41], 6, request_id="shk")
    eng.run_until_drained()
    check_parity(req, want[0])
    assert "pallas_call" in _decode_jaxpr(eng)


def _paged_case(dtype, layers=3, pages=60, page_size=4, heads=4, hd=16,
                per_req=11):
    """A pool whose layers differ, five rows: contexts that end inside a
    page, on a page's edge and past a page group, one inactive row, and a
    block table with an unallocated tail (null pages)."""
    rng = np.random.default_rng(3)
    shape = (layers, pages, page_size, heads * hd)
    pool_k = jnp.asarray(rng.normal(size=shape), dtype)
    pool_v = jnp.asarray(rng.normal(size=shape), dtype)
    q = jnp.asarray(rng.normal(size=(5, heads, hd)), dtype)
    lens = np.asarray([0, 5, 15, -1, 42], np.int32)
    tables = np.zeros((5, per_req), np.int32)
    ids = rng.permutation(np.arange(1, pages))
    for r, n in enumerate(lens):
        used = 0 if n < 0 else n // page_size + 1
        tables[r, :used] = ids[r * per_req:r * per_req + used]
    return q, pool_k, pool_v, jnp.asarray(tables), jnp.asarray(lens)


def _fold_pages(monkeypatch, pages: int) -> None:
    """Hold the kernel to ``pages`` pages a fold whatever a page weighs (the
    rule follows the bytes a fold moves: pages as light as these tests'
    would otherwise all go in one fold, and no edge of a fold be walked)."""
    from fleetx_tpu.ops import paged_attention as PA

    monkeypatch.setattr(PA, "pick_pages_per_step", lambda **geometry: pages)


def _dense_attention(q, pool_k, pool_v, tables, lens, layer):
    """The gather path itself (``serving/decode.py``) on layer ``layer``,
    in f32."""
    from fleetx_tpu.serving.decode import _paged_attention

    B, heads, hd = q.shape
    f32 = jnp.float32
    kd = pool_k[layer, tables].reshape(B, -1, heads, hd).astype(f32)
    vd = pool_v[layer, tables].reshape(B, -1, heads, hd).astype(f32)
    return _paged_attention(q[:, None].astype(f32), kd, vd,
                            jnp.maximum(lens, 0)[:, None])[:, 0]


@pytest.mark.parametrize("pages_per_step", [1, 2, 8])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_paged_call_reads_the_layer_it_is_given(monkeypatch, dtype,
                                                pages_per_step):
    """``_paged_call`` on the whole pool with layer ``l`` gives, to the
    last bit, what it gives on that layer cut out and handed over as a
    one-layer pool (the per-layer call the kernel used to get) — for every
    layer, with one, two and eight pages folded a grid step (11 pages a
    request: the last group is padded) — and matches the gather's
    arithmetic; inactive rows come out as exact zeros."""
    from fleetx_tpu.ops import paged_attention as PA

    _fold_pages(monkeypatch, pages_per_step)
    q, pool_k, pool_v, tables, lens = _paged_case(dtype)
    local = PA._localize_tables(tables, 0, pool_k.shape[1])
    outs = []
    for l in range(pool_k.shape[0]):
        whole = PA._paged_call(q, pool_k, pool_v, local, lens, jnp.int32(l))
        alone = PA._paged_call(q, pool_k[l:l + 1], pool_v[l:l + 1], local,
                               lens, jnp.int32(0))
        for a, b in zip(whole, alone):
            assert float(jnp.abs(a - b).max()) == 0.0, l
        out = PA.paged_attention(q, pool_k, pool_v, tables, lens,
                                 jnp.int32(l))
        assert out.dtype == q.dtype
        want = _dense_attention(q, pool_k, pool_v, tables, lens, l)
        active = np.asarray(lens) >= 0
        tol = 2e-5 if dtype == jnp.float32 else 2e-2
        np.testing.assert_allclose(
            np.asarray(out, np.float32)[active], np.asarray(want)[active],
            atol=tol, rtol=tol)
        assert not np.asarray(out, np.float32)[~active].any()  # exact 0
        outs.append(np.asarray(out, np.float32))
    # the layers' answers differ: no layer stands in for another
    assert np.abs(outs[1] - outs[0]).max() > 0.1
    assert np.abs(outs[2] - outs[1]).max() > 0.1


@pytest.mark.parametrize("pages_per_step", [1, 8])
def test_paged_kernel_skips_pages_it_does_not_own(monkeypatch,
                                                  pages_per_step):
    """A ``-1`` in the middle of a block table (a page another shard
    owns) is left out of the softmax, alone in its group or beside valid
    pages; the (acc, m, l) triples of two disjoint halves of the pages
    combine to the whole — the cross-shard contract."""
    from fleetx_tpu.ops import paged_attention as PA

    _fold_pages(monkeypatch, pages_per_step)
    q, pool_k, pool_v, tables, lens = _paged_case(jnp.float32)
    local = PA._localize_tables(tables, 0, pool_k.shape[1])
    col = jnp.arange(local.shape[1])[None, :]
    layer = jnp.int32(2)
    parts = [PA._paged_call(q, pool_k, pool_v,
                            jnp.where(keep, local, -1), lens, layer)
             for keep in (col % 2 == 0, col % 2 == 1)]
    m = jnp.maximum(parts[0][1], parts[1][1])
    num = sum(a * jnp.exp(mi - m)[..., None] for a, mi, _ in parts)
    den = sum(li * jnp.exp(mi - m) for _, mi, li in parts)
    got = PA._normalize(num, den, jnp.float32)
    want = PA.paged_attention(q, pool_k, pool_v, tables, lens, layer)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-6, rtol=2e-6)


#: the walk's edges at page 4, 8 pages a fold (32 tokens), 19 columns:
#: name -> (query position, table columns that are holes); ``None`` as
#: the holes means no page at all
_WALK_ROWS = {
    "first_token": (0, ()),
    "fold_less_one": (31, ()),
    "fold": (32, ()),
    "fold_and_one": (33, ()),
    "full_table": (75, ()),
    "inactive": (-1, ()),
    "no_pages": (40, None),
    "holes_below": (70, (3, 9, 16)),
}
_WALK_CASES = {**{k: (k,) for k in _WALK_ROWS}, "mixed": tuple(_WALK_ROWS),
               "mixed_two_head_blocks": tuple(_WALK_ROWS)}


def _walk_case(names, dtype, page_size=4, per_req=19, heads=4, hd=16,
               pages=200):
    """One row a name of ``_WALK_ROWS``. Returns the kernel's inputs and,
    for the gather path (which knows no holes), each row's table with the
    holes squeezed out and its query position moved down by the tokens
    they held (-1: nothing left to attend to)."""
    rng = np.random.default_rng(7)
    shape = (2, pages, page_size, heads * hd)
    pool_k = jnp.asarray(rng.normal(size=shape), dtype)
    pool_v = jnp.asarray(rng.normal(size=shape), dtype)
    q = jnp.asarray(rng.normal(size=(len(names), heads, hd)), dtype)
    ids = rng.permutation(np.arange(1, pages))
    tables = np.full((len(names), per_req), -1, np.int32)
    dense = np.zeros_like(tables)
    lens, dense_lens = [], []
    for r, name in enumerate(names):
        n, holes = _WALK_ROWS[name]
        used = 0 if n < 0 or holes is None else n // page_size + 1
        tables[r, :used] = ids[r * per_req:r * per_req + used]
        tables[r, list(holes or ())] = -1
        kept = tables[r][tables[r] >= 0]
        dense[r, :len(kept)] = kept
        seen = sum(page_size if c < n // page_size else n % page_size + 1
                   for c in range(used) if tables[r, c] >= 0)
        lens.append(n)
        dense_lens.append(seen - 1)
    return (q, pool_k, pool_v, jnp.asarray(tables),
            jnp.asarray(lens, jnp.int32), jnp.asarray(dense),
            jnp.asarray(dense_lens, jnp.int32))


@pytest.mark.parametrize("case", list(_WALK_CASES))
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_paged_walk_is_as_long_as_the_row(monkeypatch, dtype, case):
    """The kernel's page walk, bounded by each row's own query position,
    gives the gather path's answer at every edge of a fold: the first
    token, one short of a fold, a fold, one past it, the whole table; an
    inactive row and a row with no page come out as exact zeros; holes
    below the query (pages another shard owns) are left out; and a batch
    that mixes them all is right row by row, with one head block a row
    and with two."""
    from fleetx_tpu.ops import paged_attention as PA

    _fold_pages(monkeypatch, 8)
    wide = dict(heads=32, hd=8) if "two_head_blocks" in case else {}
    q, pool_k, pool_v, tables, lens, dense, dense_lens = _walk_case(
        _WALK_CASES[case], dtype, **wide)
    assert q.shape[1] // PA.pick_head_block(*q.shape[1:], dtype) == \
        (2 if wide else 1)
    layer = jnp.int32(1)
    acc, _, l = PA._paged_call(q, pool_k, pool_v, tables, lens, layer)
    got = np.asarray(PA._normalize(acc, l, jnp.float32))
    want = np.asarray(_dense_attention(q, pool_k, pool_v, dense, dense_lens,
                                       layer))
    live = np.asarray(dense_lens) >= 0
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(got[live], want[live], atol=tol, rtol=tol)
    assert not got[~live].any()                        # exact zeros


@pytest.mark.parametrize("pages_per_step", [1, 8])
def test_paged_walk_reads_nothing_past_the_query(monkeypatch,
                                                 pages_per_step):
    """Every table column past a row's query position names a page full
    of NaN, in the query's own fold and in the folds after it: the answer
    is the gather path's and finite only if no such page is ever folded
    (0 x NaN is NaN: masking its scores would not do)."""
    from fleetx_tpu.ops import paged_attention as PA

    _fold_pages(monkeypatch, pages_per_step)
    q, pool_k, pool_v, tables, lens = _paged_case(jnp.float32)
    tables, n = np.array(tables), np.asarray(lens)
    poison = next(p for p in range(1, pool_k.shape[1])
                  if not (tables == p).any())    # a page no row holds
    for r in range(len(n)):
        tables[r, max(n[r], -1) // pool_k.shape[2] + 1:] = poison
    pool_k = pool_k.at[:, poison].set(jnp.nan)
    pool_v = pool_v.at[:, poison].set(jnp.nan)
    tables = jnp.asarray(tables)
    out = np.asarray(PA.paged_attention(q, pool_k, pool_v, tables, lens,
                                        jnp.int32(1)))
    assert np.isfinite(out).all()
    want = _dense_attention(q, jnp.nan_to_num(pool_k), jnp.nan_to_num(pool_v),
                            tables, lens, 1)
    live = n >= 0
    np.testing.assert_allclose(out[live], np.asarray(want)[live],
                               atol=2e-5, rtol=2e-5)
    assert not out[~live].any()


@pytest.mark.parametrize("position, folds", [
    (-1, 0), (0, 1), (127, 1), (128, 2), (129, 2), (1023, 8), (4000, 8)])
def test_page_groups_walked_at_the_edges(position, folds):
    """The trip-count helper, 128 tokens a fold and 8 folds a table row:
    none for an inactive row, the query's own fold included, never past
    the table — the same from the host's NumPy lengths and from a traced
    scalar."""
    from fleetx_tpu.ops import paged_attention as PA

    host = PA.page_groups_walked(np.asarray([position], np.int32), 128, 8)
    assert isinstance(host, np.ndarray) and host.tolist() == [folds]
    traced = jax.jit(lambda n: PA.page_groups_walked(n, 128, 8))(
        jnp.int32(position))
    assert int(traced) == folds


def test_page_walk_share_gauge_counts_what_the_kernel_folds(small_model,
                                                            monkeypatch):
    """``serving_page_walk_share`` after a tick is the helper's count over
    the lengths that tick's decode call was given, over the folds in the
    block table — on an engine whose requests grow past a fold."""
    from fleetx_tpu.ops import paged_attention as PA

    cfg, _, params = small_model
    _fold_pages(monkeypatch, 8)
    eng = ServingEngine(
        cfg, params,
        ServingConfig(max_batch=4, page_size=2, num_pages=129,
                      max_seq_len=64, prefill_chunk=8),
        eos_token_id=EOS)
    assert eng.paged_kernel_active
    span, folds = eng._programs.kernel.walk_shape
    assert (span, folds) == (16, 4)          # 8 pages of 2 tokens, 32 pages
    gauge = eng.metrics.gauge("serving_page_walk_share")
    eng.submit(list(range(1, 14)), 30, request_id="long")
    eng.submit([5, 9, 23], 6, request_id="short")
    given, real, seen = [], eng._call, set()

    def spy(name, *args):
        if name == "decode":
            given.append(np.array(args[7]))  # the call's query positions
        return real(name, *args)

    monkeypatch.setattr(eng, "_call", spy)
    while eng.has_work():
        calls = len(given)
        eng.step()
        if len(given) > calls:
            want = PA.page_groups_walked(given[-1], span, folds).sum()
            assert gauge.value == want / (4 * folds)
            seen.add(int(want))
    assert {1, 2, 3} <= seen                 # grew fold by fold
    assert "serving_page_walk_share" in SERVING_METRIC_NAMES


def test_fold_gauges_of_a_gpt_engine_are_set_at_its_build(small_model):
    """How the decode kernel fetches a fold is fixed when the engine is
    built: at the 345M serving geometry (16 heads of 64, pages of 16,
    bfloat16 — one layer and a small vocabulary here) 16 pages a fold, a
    copy a page, and no window cache; on the gathered view all four gauges
    read 0 and the snapshot names no fold."""
    cfg, _, params = _build_model(
        hidden_size=1024, num_attention_heads=16, num_layers=1,
        ffn_hidden_size=64, dtype="bfloat16", max_position_embeddings=1024)
    sc = dict(max_batch=2, page_size=16, num_pages=9, max_seq_len=64,
              prefill_chunk=16)
    eng = ServingEngine(cfg, params, ServingConfig(**sc), eos_token_id=EOS)
    assert eng.paged_kernel_active
    read = lambda: {  # noqa: E731
        name: eng.metrics.gauge(f"serving_kv_fold_{name}").value
        for name in ("pages_full", "copies_full", "pages_window",
                     "copies_window")}
    assert read() == {"pages_full": 4, "copies_full": 4, "pages_window": 0,
                      "copies_window": 0}      # a request has 4 pages
    eng = ServingEngine(cfg, params, ServingConfig(
        **dict(sc, num_pages=65, max_seq_len=1024)), eos_token_id=EOS)
    assert read() == {"pages_full": 16, "copies_full": 16, "pages_window": 0,
                      "copies_window": 0}
    snap = eng.serving_snapshot()
    assert snap["kv_folds"] == {"full": [16, 16]}
    assert not validate_serving_record(snap)
    gathered = ServingEngine(
        small_model[0], small_model[2],
        ServingConfig(max_batch=2, page_size=4, num_pages=9, max_seq_len=16,
                      prefill_chunk=4, paged_kernel=False),
        eos_token_id=EOS)
    assert not gathered.paged_kernel_active
    assert set(read().values()) == {0}
    assert gathered.serving_snapshot()["kv_folds"] == {}
    for name in read():
        assert f"serving_kv_fold_{name}" in SERVING_METRIC_NAMES


@pytest.mark.parametrize("dtype,paged,said", [
    ("bfloat16", True, "decode=paged_kernel (P·V bfloat16, one pass), full"),
    ("float32", True, "decode=paged_kernel (P·V float32, exact), full"),
    ("bfloat16", False, "decode=gather, alloc=")])
def test_the_build_line_says_what_the_kernels_product_is(caplog, dtype,
                                                         paged, said):
    """The form of the decode kernel's ``P · V`` is fixed by the pool's
    dtype when the programs are built (PR 41): one MXU pass in a bfloat16
    pool's dtype at any head count, the exact multi-pass product in a
    float32 pool's. Nothing to count a step, so the line in which the
    engine says which attention path it built says this too."""
    from fleetx_tpu.utils.log import logger as fx_logger

    cfg, _, params = _build_model(
        hidden_size=128, num_attention_heads=2, num_layers=1,
        ffn_hidden_size=64, dtype=dtype, max_position_embeddings=64)
    fx_logger.addHandler(caplog.handler)       # the logger does not propagate
    try:
        eng = ServingEngine(cfg, params, ServingConfig(
            max_batch=2, page_size=16, num_pages=9, max_seq_len=64,
            prefill_chunk=16, paged_kernel=paged), eos_token_id=EOS)
    finally:
        fx_logger.removeHandler(caplog.handler)
    assert eng.paged_kernel_active == paged
    assert eng.pool_k.dtype == jnp.dtype(dtype)
    line, = [r.getMessage() for r in caplog.records
             if r.getMessage().startswith("serving engine:")]
    assert said in line, line


# ---------------------------------------------------------------------------
# lazy page lifecycle: admission, growth, preempt-and-swap (PR 18)
# ---------------------------------------------------------------------------

def test_lazy_admission_admits_strictly_more_than_reserve(small_model):
    """The tentpole's occupancy claim: on the SAME pool, lazy admission
    runs strictly more concurrent requests than reserve-up-front."""
    cfg, _, params = small_model

    def admitted_after_first_step(lazy):
        eng = ServingEngine(
            cfg, params,
            ServingConfig(max_batch=4, page_size=4, num_pages=9,  # 8 usable
                          max_seq_len=32, prefill_chunk=4,
                          lazy_alloc=lazy),
            eos_token_id=EOS)
        for i in range(4):
            eng.submit([5 + i, 9, 23, 41], 8, request_id=f"a{lazy}{i}")
        eng.step()
        return sum(r is not None for r in eng._slots)

    reserve = admitted_after_first_step(False)  # 3 pages each → 2 fit
    lazy = admitted_after_first_step(True)      # 1 + watermark → all 4 fit
    assert reserve == 2 and lazy == 4
    assert lazy > reserve


def test_pool_exhaustion_preempts_youngest_and_completes_token_identical(
        small_model):
    """The preempt-and-swap drill: an over-admitted pool runs dry
    mid-decode; the YOUNGEST request is swapped out, re-enqueued at the
    queue head, and still completes token-identical (decode is
    idempotent) — nothing leaks and the oldest request is never the
    victim."""
    cfg, model, params = small_model
    eng = ServingEngine(
        cfg, params,
        ServingConfig(max_batch=4, page_size=4, num_pages=9,  # 8 usable
                      max_seq_len=32, prefill_chunk=4),
        eos_token_id=EOS)
    eng.reset_stats()
    prompts = [[5 + i, 9, 23, 41] for i in range(4)]
    want = one_shot(model, params, prompts, 8)
    reqs = [eng.submit(p, 8, request_id=f"pe{i}")
            for i, p in enumerate(prompts)]
    eng.run_until_drained()
    preempted = [r for r in reqs if r.preemptions > 0]
    assert preempted, "tight pool never triggered a preemption"
    assert eng.metrics.counter("serving_requests_preempted").value == \
        sum(r.preemptions for r in reqs)
    assert reqs[0].preemptions == 0  # oldest is never the victim
    for req, row in zip(reqs, want):
        assert req.state == "finished" and req.error is None
        check_parity(req, row)
    assert eng.allocator.allocated_pages == 0  # no page leaked
    # the lifecycle evidence landed on the timelines: the victim shows
    # the swap-out, and page-by-page growth appears on some request
    names = [e["name"]
             for e in eng.request_trace(preempted[0].id)["events"]]
    assert "preempted" in names
    assert names.count("admitted") >= 1  # re-admission after the swap
    all_events = [e["name"] for r in reqs
                  for e in eng.request_trace(r.id)["events"]]
    assert "page_grow" in all_events
    snap = eng.serving_snapshot()
    assert snap["requests_preempted"] >= 1
    assert validate_serving_record(snap) == []


def test_allocator_errors_are_real_exceptions():
    """Double-free / foreign-page free / zero-size alloc raise
    PageAllocatorError (an assert would vanish under ``python -O`` and
    corrupt the free list silently)."""
    from fleetx_tpu.serving.paged_cache import PageAllocatorError

    a = PageAllocator(num_pages=6, page_size=4)
    pages = a.alloc(2)
    a.free(pages)
    with pytest.raises(PageAllocatorError):
        a.free(pages)                       # double-free
    with pytest.raises(PageAllocatorError):
        a.free([NULL_PAGE])                 # the null page is never out
    with pytest.raises(PageAllocatorError):
        a.alloc(0)                          # caller bug, not exhaustion
    with pytest.raises(PageAllocatorError):
        a.alloc(-3)
    assert a.alloc(6) is None               # exhaustion stays None


def test_allocator_conserves_pages_under_grow_free_preempt():
    """Property drill over the lazy lifecycle's op mix: grants stay
    disjoint, the null page never escapes, and free+held always equals
    the pool — under random grow/free/preempt interleavings."""
    rng = np.random.RandomState(0)
    a = PageAllocator(num_pages=17, page_size=4)
    held = []
    for _ in range(500):
        roll = rng.rand()
        if roll < 0.55:
            got = a.alloc(int(rng.randint(1, 4)))
            if got is None and held:
                # pool dry → "preempt": free a random victim's grant
                a.free(held.pop(int(rng.randint(len(held)))))
            elif got is not None:
                held.append(got)
        elif held:
            a.free(held.pop(int(rng.randint(len(held)))))
        out = [p for grant in held for p in grant]
        assert len(out) == len(set(out)), "page granted twice"
        assert NULL_PAGE not in out
        assert a.allocated_pages == len(out)
        assert a.free_pages + len(out) == a.usable_pages, "pages leaked"
    for grant in held:
        a.free(grant)
    assert a.free_pages == a.usable_pages


# ---------------------------------------------------------------------------
# the tick's order: step N+1 is dispatched before step N is fetched (PR 36)
# ---------------------------------------------------------------------------

def _tick_engine(small_model, eos=EOS, **over):
    cfg, _, params = small_model
    kw = dict(max_batch=4, page_size=4, num_pages=33, max_seq_len=32,
              prefill_chunk=4)
    kw.update(over)
    eng = ServingEngine(cfg, params, ServingConfig(**kw), eos_token_id=eos)
    eng.reset_stats()
    return eng


def _in_flight(eng, req) -> bool:
    """Whether the step the device runs right now has a row of ``req``."""
    step = eng._inflight
    return step is not None and any(r is req for r, _ in step.rows)


TICK_PROMPTS = [[5, 9, 23, 41], [7, 3], [11, 2, 8, 4, 19, 33, 7, 6, 1, 2, 3]]


@pytest.mark.parametrize("where", ["mid_stream", "first_token"])
def test_an_eos_is_acted_on_one_step_late_and_the_overrun_is_dropped(
        small_model, where):
    """Some rows end on an eos while others run on: every row is the
    one-shot row up to and with its eos, the one row-step the device
    computed past each eos is dropped and counted, and no page leaks."""
    cfg, model, params = small_model
    new = 10
    rows = [[int(t) for t in row]
            for row in one_shot(model, params, TICK_PROMPTS, new)]
    if where == "first_token":
        eos = rows[0][0]
    else:   # a token some row reaches after its first and before its last
        eos = next(t for row in rows for t in row[1:-1]
                   if t not in (row[0], rows[0][0]))
    want = [row[:row.index(eos) + 1] if eos in row else row for row in rows]
    assert any(len(w) < new for w in want) and \
        any(len(w) == new for w in want), (eos, rows)
    eng = _tick_engine(small_model, eos=eos)
    reqs = [eng.submit(p, new, request_id=f"e{i}")
            for i, p in enumerate(TICK_PROMPTS)]
    eng.run_until_drained()
    assert [r.tokens for r in reqs] == want
    assert all(r.state == "finished" and r.error is None for r in reqs)
    # an eos before a row's last token had one more row-step in flight
    overrun = sum(len(w) < new for w in want)
    assert eng.metrics.counter("serving_overrun_rows").value == overrun
    assert eng.serving_snapshot()["overrun_rows"] == overrun
    assert eng.metrics.counter("serving_tokens_total").value == \
        sum(len(w) for w in want)
    assert eng.allocator.allocated_pages == 0
    assert eng.allocator.free_pages == eng.allocator.usable_pages
    assert not eng.has_work() and eng._inflight is None


@pytest.mark.parametrize("decision", ["preempt", "cancel", "shed"])
def test_a_decision_voids_the_row_already_in_flight(small_model, decision):
    """Preempted, cancelled or shed while its row runs on the device: the
    request gets no token and no callback from that step; the other row of
    the step does; run again, the request serves the one-shot tokens."""
    cfg, model, params = small_model
    want = one_shot(model, params, TICK_PROMPTS[:2], 10)
    eng = _tick_engine(small_model)
    calls = []
    a = eng.submit(TICK_PROMPTS[0], 10, request_id="a",
                   callback=lambda r: calls.append((r.state, len(r.tokens))),
                   deadline_s=600.0 if decision == "shed" else None)
    b = eng.submit(TICK_PROMPTS[1], 10, request_id="b")
    while len(a.tokens) < 3 or len(b.tokens) < 2:
        assert eng.step()
    assert _in_flight(eng, a) and _in_flight(eng, b)
    held, b_held = list(a.tokens), len(b.tokens)
    if decision == "preempt":
        eng._preempt(a)             # as _grow_or_preempt does, mid-schedule
        assert a.state == "waiting" and a.tokens == [] and not calls
    elif decision == "cancel":
        assert eng.cancel("a")
        assert a.state == "refused" and calls == [("refused", len(held))]
    else:
        a.deadline_s = 1e-6         # expired: the next schedule sheds it
    assert eng.step()               # fetches the step that ran a's row
    assert len(b.tokens) == b_held + 1, "the other row of that step counts"
    if decision == "preempt":
        # admitted again at the top of that tick: at most the first token of
        # its new pass, never one from the step its old pass was in
        assert a.tokens in ([], held[:1]) and a.preemptions == 1
        assert not calls
    else:
        assert a.tokens == held and a.state == "refused"
        assert calls == [("refused", len(held))]
        assert ("deadline_shed" in a.error) == (decision == "shed")
    eng.run_until_drained()
    check_parity(b, want[1])
    if decision != "preempt":
        assert a.tokens == held and len(calls) == 1
        a = eng.submit(TICK_PROMPTS[0], 10, request_id="a2")
        eng.run_until_drained()
    assert a.state == "finished"
    check_parity(a, want[0])
    assert eng.metrics.counter("serving_overrun_rows").value == 0
    assert eng.allocator.allocated_pages == 0 and not eng.has_work()


def test_a_request_grows_a_token_a_step_as_the_harness_reads_it(small_model):
    """What ``benchmarks/serve_cell.py:Loop.tick`` stands on: per ``step()``
    a request grows by at most one token (two only in the step in which its
    first lands), stamped ``[first_token_at, last_token_at]``, over a run
    with joins, finishes and a preemption; ``serving_tokens_total`` counts a
    token when it is emitted."""
    cfg, model, params = small_model
    eng = _tick_engine(small_model, num_pages=10,    # 9 usable: it preempts
                       alloc_watermark=0)
    prompts = [[5 + i, 9, 23, 41, 7, 3][:3 + i % 4] for i in range(7)]
    want = one_shot(model, params, prompts, 9)
    live, seen, stamps, done = {}, {}, {}, []
    todo = list(enumerate(prompts))
    for _ in range(500):
        while todo and len(live) < 5:
            i, prompt = todo.pop(0)
            live[i] = eng.submit(prompt, 9, request_id=f"h{i}")
            seen[i], stamps[i] = 0, []
        if not live:
            break
        eng.step()
        for i, h in list(live.items()):
            if len(h.tokens) < seen[i]:         # preempted: it starts over
                seen[i], stamps[i] = 0, []
            grown = len(h.tokens) - seen[i]
            assert grown <= (2 if seen[i] == 0 else 1), (h.id, grown)
            if grown:
                times = [h.last_token_at]
                if seen[i] == 0:
                    times = [h.first_token_at] + (
                        [h.last_token_at] if grown > 1 else [])
                assert len(times) == grown
                stamps[i] += times
                seen[i] += grown
            if h.state == "finished":
                done.append(live.pop(i))
        assert eng.metrics.counter("serving_tokens_total").value >= \
            sum(seen.values())
    assert len(done) == len(prompts) and not eng.has_work()
    assert sum(h.preemptions for h in done) > 0, "the drill needs one"
    for i, row in enumerate(want):
        h = next(h for h in done if h.id == f"h{i}")
        check_parity(h, row)
        assert stamps[i] == sorted(stamps[i]) and len(stamps[i]) == 9
        assert h.first_token_at == stamps[i][0]
        assert h.finished_at >= h.last_token_at == stamps[i][-1]


@pytest.mark.parametrize("how", ["run_until_drained", "begin_drain"])
def test_a_drain_delivers_the_token_in_flight(small_model, how):
    """The last in-flight step is flushed: a tick that only fetches and
    emits returns True, and ``has_work()`` is false only after it."""
    cfg, model, params = small_model
    want = one_shot(model, params, TICK_PROMPTS, 6)
    eng = _tick_engine(small_model)
    reqs = [eng.submit(p, 6, request_id=f"d{i}")
            for i, p in enumerate(TICK_PROMPTS)]
    if how == "run_until_drained":
        eng.run_until_drained()
    else:
        for _ in range(4):
            eng.step()
        assert eng._inflight is not None
        eng.begin_drain()
        assert eng.submit([1, 2], 2).error == "draining"
        fetch_only = 0
        while eng.step():       # the server loop's condition
            dispatched = "decode" in eng.last_tick or \
                "prefill" in eng.last_tick
            fetch_only += not dispatched
            assert dispatched or "decode.wait" in eng.last_tick
        assert fetch_only == 1, "the last tick only fetched and emitted"
    assert not eng.has_work() and eng._inflight is None
    assert not eng.step()
    for req, row in zip(reqs, want):
        assert req.state == "finished" and len(req.tokens) == 6
        check_parity(req, row)
    assert eng.allocator.allocated_pages == 0


def test_a_dispatch_takes_snapshots_of_the_hosts_arrays(small_model,
                                                        monkeypatch):
    """``_block_tables`` and ``_lens`` change while a program that read them
    may not have started (and the CPU backend may alias a NumPy buffer):
    every call is given arrays of its own, and scribbling over the host's
    right after a tick changes nothing that tick dispatched."""
    cfg, model, params = small_model
    want = one_shot(model, params, TICK_PROMPTS, 8)
    eng = _tick_engine(small_model)
    real, calls = eng._call, []

    def spy(name, *args):
        host = [a for a in args if isinstance(a, np.ndarray) and a.ndim]
        assert len(host) >= 3       # tokens or lengths, a table, the key
        for a in host:
            assert not np.shares_memory(a, eng._block_tables), name
            assert not np.shares_memory(a, eng._lens), name
        calls.append(name)
        return real(name, *args)

    monkeypatch.setattr(eng, "_call", spy)
    reqs = [eng.submit(p, 8, request_id=f"s{i}")
            for i, p in enumerate(TICK_PROMPTS)]
    while eng.has_work():
        eng.step()
        tables, lens = eng._block_tables.copy(), eng._lens.copy()
        eng._block_tables[:] = NULL_PAGE
        eng._lens[:] = 0
        if eng._inflight is not None:
            jax.block_until_ready(eng._inflight.toks)
        eng._block_tables[:] = tables
        eng._lens[:] = lens
    assert {"prefill", "decode"} == set(calls)
    for req, row in zip(reqs, want):
        check_parity(req, row)


def test_decode_overlapped_counts_all_but_the_first_of_a_busy_period(
        small_model, monkeypatch):
    """``serving_decode_overlapped`` is the decode steps dispatched while
    the step before was still unfetched: all of a busy period but its
    first."""
    eng = _tick_engine(small_model)
    real, decodes = eng._call, []

    def spy(name, *args):
        if name == "decode":
            decodes.append(eng._inflight is not None)
        return real(name, *args)

    monkeypatch.setattr(eng, "_call", spy)
    for period in range(2):
        for i, p in enumerate(TICK_PROMPTS):
            eng.submit(p, 5 + i, request_id=f"o{period}{i}")
        eng.run_until_drained()
        assert not eng.step()           # idle between the two
    m = eng.metrics
    steps = m.counter("serving_decode_steps").value
    assert steps == len(decodes) > 8
    assert m.counter("serving_decode_overlapped").value == steps - 2 \
        == sum(decodes)
    snap = eng.serving_snapshot()
    assert (snap["decode_steps"], snap["decode_overlapped"]) == \
        (steps, steps - 2)
    assert validate_serving_record(snap) == []
    for name in ("serving_decode_steps", "serving_decode_overlapped",
                 "serving_overrun_rows"):
        assert name in SERVING_METRIC_NAMES


def test_sampling_folds_a_host_count_into_one_key_inside_the_program(
        small_model):
    """No key is split on the device in a tick: a sampling engine hands its
    programs the one base key and the count of programs dispatched, and the
    same seed serves the same stream; greedy programs read neither."""
    cfg, _, params = small_model
    sc = ServingConfig(max_batch=2, page_size=4, num_pages=17,
                       max_seq_len=32, prefill_chunk=4)

    def run(seed):
        eng = ServingEngine(cfg, params, sc, SamplingParams(
            do_sample=True, temperature=1.0, top_k=20), eos_token_id=EOS,
            seed=seed)
        eng.reset_stats()
        key = eng._rng.copy()
        reqs = [eng.submit(p, 8) for p in TICK_PROMPTS[:2]]
        eng.run_until_drained()
        assert isinstance(eng._rng, np.ndarray) and \
            np.array_equal(eng._rng, key), "the base key never moves"
        assert eng._draws == \
            eng.metrics.counter("serving_decode_steps").value + 2
        return [r.tokens for r in reqs]

    first, again, other = run(3), run(3), run(4)
    assert first == again and first != other
    assert all(len(t) == 8 for t in first)
    greedy = _tick_engine(small_model)
    text = str(jax.make_jaxpr(greedy._fns["decode"])(
        greedy.params, greedy.pool_k, greedy.pool_v, greedy._tokens,
        np.int32(-1), np.zeros((1,), np.int32), greedy._block_tables,
        greedy._lens, *greedy._draw()))
    assert "random_bits" not in text and "threefry" not in text


# ---------------------------------------------------------------------------
# telemetry schema + perf gate wiring
# ---------------------------------------------------------------------------

def test_serving_snapshot_validates_and_metrics_registered(small_model,
                                                           engine):
    cfg, model, params = small_model
    req = engine.submit([5, 9, 23], 4, request_id="t")
    engine.run_until_drained()
    snap = engine.serving_snapshot()
    assert validate_serving_record(snap) == []
    assert snap["requests_completed"] == 1 and snap["tokens_total"] >= 1
    assert snap["ttft_p50_s"] is not None and snap["itl_p50_s"] is not None
    for name in ("serving_ttft", "serving_inter_token",
                 "serving_queue_depth"):
        assert name in SERVING_METRIC_NAMES
    # negative: a NaN quantile or missing required key must not validate
    bad = dict(snap, tokens_per_sec=float("nan"))
    assert validate_serving_record(bad)
    del bad["tokens_per_sec"]
    assert any("tokens_per_sec" in e for e in validate_serving_record(bad))


def test_shipped_serving_recipe_parses():
    """The committed serving yaml's full Serving section (ckpt_dir
    included) must round-trip through ServingConfig.from_dict — the
    replica entry point feeds it verbatim (review finding: an
    unknown-key assert killed every launch with the shipped recipe)."""
    from fleetx_tpu.utils import config as config_mod

    cfg = config_mod.parse_config(os.path.join(
        REPO, "fleetx_tpu", "configs", "nlp", "gpt",
        "serving_gpt_345M.yaml"))
    sc = ServingConfig.from_dict(dict(cfg.get("Serving") or {}))
    assert sc.ckpt_dir is None and sc.num_pages == 513
    assert sc.max_seq_len <= 1024


def test_inference_predict_fetches_output_tree_in_one_device_get(
        monkeypatch):
    """The batch-predict path must device_get the WHOLE output tree once,
    not leaf-by-leaf in a Python loop."""
    from fleetx_tpu.core.engine.inference_engine import InferenceEngine

    calls = []
    real = jax.device_get

    def counting(x):
        calls.append(x)
        return real(x)

    monkeypatch.setattr(jax, "device_get", counting)

    class Stub:
        mp = 1
        dp = 1
        params = None
        _plain_call = staticmethod(
            lambda params, *a: {"x": jnp.ones((2, 2)),
                                "y": jnp.zeros((3,)),
                                "z": jnp.ones((1, 4))})

    out = InferenceEngine._predict(Stub(), [np.zeros((2, 2), np.int32)])
    assert len(out) == 3 and all(isinstance(o, np.ndarray) for o in out)
    assert len(calls) == 1, f"{len(calls)} device_get calls for one tree"


# ---------------------------------------------------------------------------
# subprocess drills: drain on SIGTERM, supervised 2-replica fleet
# ---------------------------------------------------------------------------

def _serve_yaml(tmp_path, name="serving.yaml", **serving_over):
    serving = dict(max_batch=4, page_size=4, num_pages=33, max_seq_len=32,
                   prefill_chunk=8)
    serving.update(serving_over)
    cfg = {"Model": MODEL_DICT, "Serving": serving,
           "Generation": {"decode_strategy": "greedy_search",
                          "eos_token_id": EOS, "pad_token_id": 0},
           "Global": {"seed": 7}}
    import yaml

    path = tmp_path / name
    path.write_text(yaml.safe_dump(cfg))
    return str(path)


def _subprocess_env(**extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)  # single real CPU device is enough
    env.update(extra)
    return env


def _wait_ready(path, proc, timeout=120.0):
    """Poll for the replica's ready file; fail fast if it died."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if os.path.exists(path):
            try:
                with open(path) as f:
                    return json.load(f)
            except ValueError:
                pass  # torn write — retry
        if proc.poll() is not None:
            raise AssertionError(
                f"replica died before ready (rc={proc.returncode})")
        time.sleep(0.1)
    raise AssertionError("replica never became ready")


def _expected_tokens(prompts, max_new):
    """What every replica must produce: params are deterministic from
    Global.seed, so the in-process model predicts the fleet's output."""
    from flax.core import meta

    cfg = config_from_dict(MODEL_DICT)
    model = GPTForPretraining(cfg)
    params = meta.unbox(model.init({"params": jax.random.PRNGKey(7)},
                                   jnp.zeros((1, 8), jnp.int32), None,
                                   deterministic=True)["params"])
    rows = one_shot(model, params, prompts, max_new)
    out = []
    for row in rows:
        toks = [int(t) for t in row]
        if EOS in toks:
            toks = toks[:toks.index(EOS) + 1]
        out.append(toks)
    return out


def _ask(port, payload, timeout=90.0):
    from fleetx_tpu.serving.server import request

    return request(("127.0.0.1", port), payload, timeout=timeout)


@needs_net
def test_replica_drains_on_injected_sigterm(tmp_path):
    """``faults.py sigterm_at`` drill: the replica SIGTERMs itself after 6
    work steps — guaranteed mid-stream (one request alone needs ~9 steps)
    — then every ADMITTED request must complete token-correct before the
    process exits with the preemption code; anything arriving after the
    latch gets the explicit "draining" refusal (the router's re-dispatch
    signal), never a silent drop."""
    cfg_path = _serve_yaml(tmp_path)
    ready = tmp_path / "ready.json"
    metrics = tmp_path / "serving_metrics.jsonl"
    proc = subprocess.Popen(
        [sys.executable, SERVE, "-c", cfg_path, "--ready-file", str(ready),
         "--metrics-out", str(metrics), "--preemption-code", "75"],
        env=_subprocess_env(FLEETX_FAULTS="sigterm_at=6",
                            FLEETX_FLIGHT_DIR=str(tmp_path / "flight")),
        stdout=subprocess.DEVNULL, stderr=subprocess.STDOUT)
    try:
        info = _wait_ready(str(ready), proc)
        prompts = [[5, 9, 23, 41], [7, 3], [11, 2, 8]]
        want = _expected_tokens(prompts, 8)
        results = [None] * len(prompts)

        def ask(i):
            results[i] = _ask(info["port"],
                              {"id": f"d{i}", "prompt": prompts[i],
                               "max_new_tokens": 8})

        threads = [threading.Thread(target=ask, args=(i,))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        rc = proc.wait(timeout=120)
        assert rc == 75, f"expected preemption exit 75, got {rc}"
        completed = 0
        for i, resp in enumerate(results):
            assert resp is not None, f"request {i} got no response"
            if "tokens" in resp:
                completed += 1
                assert resp["tokens"] == want[i], (i, resp["tokens"],
                                                   want[i])
                assert resp["ttft_s"] is not None
            else:
                # a post-latch arrival: explicit refusal, not a drop
                assert resp.get("error") == "draining", (i, resp)
        assert completed >= 1, results  # the latch fired mid-stream
        # the drained snapshot is on disk and schema-valid
        lines = [l for l in open(metrics).read().splitlines() if l.strip()]
        snap = json.loads(lines[-1])
        assert validate_serving_record(snap) == []
        assert snap["requests_completed"] == completed
        # flight evidence of the drain landed in the ring dump
        flights = list((tmp_path / "flight").glob("flight_rank*.json"))
        assert flights, "no flight dump after drain"
        events = json.loads(flights[0].read_text())["events"]
        assert any(e.get("name") == "drain" for e in events)
        # ...and the drain spilled every live request TIMELINE alongside
        # the engine events — the postmortem can reconstruct exactly where
        # each in-flight request was when the preemption latch fired
        timelines = [e for e in events
                     if e.get("kind") == "serving_timeline"]
        assert timelines, "drain dumped no request timelines"
        for tl in timelines:
            assert tl["name"].startswith("d"), tl  # the drill's ids
            names = [ev["name"] for ev in tl["events"]]
            assert "drain" in names, (tl["name"], names)
            if "admitted" in names:  # queued-only requests have no span yet
                assert tl["attribution"]["queue_s"] is not None, tl
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=30)


def _free_port():
    with socket.socket() as s:
        s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@needs_net
def test_supervised_fleet_kill_one_replica_loses_nothing(tmp_path):
    """The acceptance drill (ISSUE 11): 2 replicas, each under its own
    ``tools/supervise.py``, a router in front. One replica is SIGKILLed
    mid-stream; the router must complete EVERY admitted request with
    token-identical output (re-dispatch is idempotent — decode is a pure
    function of the shared seeded params)."""
    cfg_path = _serve_yaml(tmp_path)
    ports = [_free_port(), _free_port()]
    readys = [tmp_path / f"ready{i}.json" for i in range(2)]
    sups = []
    for i in range(2):
        sups.append(subprocess.Popen(
            [sys.executable, SUPERVISE, "--max-restart", "2",
             "--backoff", "1.0", "--grace", "20", "--",
             sys.executable, SERVE, "-c", cfg_path,
             "--port", str(ports[i]), "--ready-file", str(readys[i]),
             "--preemption-code", "75"],
            env=_subprocess_env(
                FLEETX_FLIGHT_DIR=str(tmp_path / f"flight{i}")),
            stdout=subprocess.DEVNULL, stderr=subprocess.STDOUT))
    router = None
    try:
        infos = [_wait_ready(str(r), s) for r, s in zip(readys, sups)]
        router = subprocess.Popen(
            [sys.executable, SERVE, "--router",
             "--port", str(_free_port()),
             "--backends",
             f"127.0.0.1:{infos[0]['port']},127.0.0.1:{infos[1]['port']}"],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
        line = router.stdout.readline()
        assert "listening on" in line, line
        router_port = int(line.split(":")[-1].split()[0])

        rng = np.random.RandomState(3)
        prompts = [[int(t) for t in rng.randint(1, 90, size=rng.randint(
            2, 8))] for _ in range(10)]
        want = _expected_tokens(prompts, 8)
        results = [None] * len(prompts)
        started = threading.Semaphore(0)

        def ask(i):
            if i >= 3:
                started.acquire()  # the tail waits for the kill
            results[i] = _ask(router_port,
                              {"id": f"f{i}", "prompt": prompts[i],
                               "max_new_tokens": 8}, timeout=150.0)

        threads = [threading.Thread(target=ask, args=(i,))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        # let the head of the stream get in flight, then kill replica 0
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline and \
                not any(r is not None for r in results[:3]):
            time.sleep(0.05)
        os.kill(infos[0]["pid"], signal.SIGKILL)
        for _ in range(len(prompts)):
            started.release()
        for t in threads:
            t.join(timeout=180)
        for i, resp in enumerate(results):
            assert resp is not None, f"request {i} lost"
            assert resp.get("tokens") == want[i], (i, resp, want[i])

        # a completed request's lifecycle is retrievable THROUGH the
        # router: its dispatch journal merged (time-sorted) with whatever
        # replica still holds the timeline — the restarted replica lost
        # its half, which must degrade the trace, not error it
        tr = _ask(router_port, {"verb": "trace", "id": "f9"})
        assert tr.get("events"), tr
        names = [e["name"] for e in tr["events"]]
        assert "dispatch" in names and "completed" in names, names
        assert "router" in tr["sources"]
        ts = [e["t"] for e in tr["events"]]
        assert ts == sorted(ts)
        # an id nobody ever saw answers an explicit error, not a hang
        miss = _ask(router_port, {"verb": "trace", "id": "never"})
        assert miss.get("error") == "unknown request id", miss

        # graceful fleet shutdown: the surviving replica's supervisor
        # forwards SIGTERM → drain → preemption code (treated clean)
        sups[1].send_signal(signal.SIGTERM)
        rc1 = sups[1].wait(timeout=90)
        assert rc1 == 75, f"survivor's supervisor exited {rc1}"
    finally:
        if router is not None and router.poll() is None:
            router.kill()
        for s in sups:
            if s.poll() is None:
                s.send_signal(signal.SIGTERM)
        for s in sups:
            if s.poll() is None:
                try:
                    s.wait(timeout=60)
                except subprocess.TimeoutExpired:
                    s.kill()
                    s.wait(timeout=30)
