"""The short-convolution family (``models/conv_moe``,
``serving/conv_moe.py``, ``ops/paged_attention.py`` at half-tile grouped
heads) against its plain reference (``benchmarks/reference/lfm2_ref.py``),
at toy widths on the CPU.

Weights are seeded float32 (the benchmark's own ``weights.make``), so
program and reference differ by the order of float32 sums alone — and by
the form: the program convolves a chunk from the slot's tail and folds the
request's pages a key block at a time, the reference shifts and scores the
whole sequence. Logits (standard deviation ~0.5, largest ~2) are held to
2e-5: float32's grain through nine layers of sums of up to 512 terms (the
whole-sequence forward reads 5e-6).
"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import conv_moe_toy as toy  # noqa: E402
from benchmarks import check, weights  # noqa: E402
from benchmarks.manifest import load_module  # noqa: E402
from fleetx_tpu.models.conv_moe import model as M  # noqa: E402
from fleetx_tpu.models.conv_moe.config import (PUBLISHED_KEYS,  # noqa: E402
                                               config_from_dict)
from fleetx_tpu.observability import schema  # noqa: E402
from fleetx_tpu.ops import paged_attention as PA  # noqa: E402
from fleetx_tpu.serving import conv_moe as S, programs, registry  # noqa: E402
from fleetx_tpu.serving.decode import SamplingParams  # noqa: E402
from fleetx_tpu.serving.engine import (ServingConfig,  # noqa: E402
                                       ServingEngine)

ROOT = toy.ROOT
ref = load_module(os.path.join(ROOT, "benchmarks/reference/lfm2_ref.py"))
with open(os.path.join(ROOT, "benchmarks/configs/lfm2-24b-a2b.json")) as _f:
    SHIPPED = json.load(_f)
CHUNK, PAGE, ATOL = 8, 4, 2e-5


def _built(seed=7, **model):
    """``(model config, program tree, reference weights, sizes)``: the same
    seeded numbers on both sides, through ``param_paths``."""
    sizes = dict(toy.PUBLISHED)
    spec = ref.weight_spec(sizes)
    w = weights.make(spec, seed)
    cfg = config_from_dict(toy.model_section(**model))
    params = weights.to_program_tree(w, toy.param_paths(spec),
                                     M.served_template(cfg))
    return cfg, params, w, sizes


@pytest.fixture(scope="module")
def built():
    return _built()


@pytest.fixture(autouse=True)
def leave_no_expert_counts_behind():
    yield
    toy.zero_expert_counters()


_FNS: dict = {}
_forward = jax.jit(M.forward, static_argnums=1)


def _fns(cfg, paged_kernel):
    """One pair of programs a (config, path): a compile is most of a test."""
    key = (id(cfg), paged_kernel)
    if key not in _FNS:
        _FNS[key] = (cfg, S.make_step_fns(
            cfg, prefill_chunk=CHUNK, sampling=SamplingParams(),
            paged_kernel=paged_kernel))
    return _FNS[key][1]


def _serve(cfg, params, prompt, new, *, slot=1, max_batch=3,
           paged_kernel=False, max_seq=96, cache=None):
    """Prefill ``prompt`` in chunks, decode ``new`` tokens greedily, in slot
    ``slot`` of an otherwise empty batch: ``(tokens, logits a step,
    cache)``."""
    P = max_seq // PAGE
    fns = _fns(cfg, paged_kernel)
    cache = cache or S.init_cache(cfg, num_pages=1 + max_batch * P,
                                  page_size=PAGE, max_batch=max_batch)
    table = np.zeros((max_batch, P), np.int32)
    table[slot] = 1 + slot * P + np.arange(P)
    key = jax.random.PRNGKey(0)
    toks, logits, pos = list(prompt), [], 0
    while pos < len(prompt):
        part = prompt[pos:pos + CHUNK]
        row = np.zeros((1, CHUNK), np.int32)
        row[0, :len(part)] = part
        *cache, tok, lg = fns["prefill"](
            params, *cache, row, table[slot:slot + 1], np.int32(pos),
            np.int32(len(part)), key, np.uint32(0), np.int32(slot))
        pos += len(part)
    logits.append(np.asarray(lg[0]))
    toks.append(int(tok[0]))
    lens = np.full((max_batch,), -1, np.int32)
    last = np.zeros((max_batch,), np.int32)
    for _ in range(new):
        lens[slot], last[slot] = len(toks) - 1, toks[-1]
        *cache, tk, lg, _ = fns["decode"](
            params, *cache, last, np.int32(-1), np.zeros((1,), np.int32),
            table, lens, key, np.uint32(0))
        logits.append(np.asarray(lg[slot]))
        toks.append(int(tk[slot]))
    return toks, logits, cache


def _reference_rows(w, sizes, toks):
    row = np.zeros((1, -(-len(toks) // 64) * 64), np.int32)
    row[0, :len(toks)] = toks
    return np.asarray(ref.logits(w, sizes, jnp.asarray(row)))[0]


def _prompt(n, seed=None, vocab=96):
    return np.random.default_rng(n if seed is None else seed).integers(
        0, vocab, size=n).tolist()


# ------------------------------------------------------ against the reference
def test_the_whole_sequence_forward_is_the_reference_on_logits(built):
    cfg, params, w, sizes = built
    toks = _prompt(64)
    got = np.asarray(_forward(params, cfg, jnp.asarray(toks)))
    np.testing.assert_allclose(got, _reference_rows(w, sizes, toks),
                               atol=ATOL)


@pytest.mark.parametrize("prompt_len,paged_kernel", [
    (13, False),    # a ragged last chunk of 5
    (17, False),    # a last chunk of ONE token: shorter than the tail
    (18, True),     # ... of two; decode through the kernel (interpreted)
], ids=["ragged", "chunk-of-1", "chunk-of-2-kernel"])
def test_prefill_then_decode_through_both_caches_is_the_reference_on_logits(
        built, prompt_len, paged_kernel):
    """Chunked prefill then decode through pool and tail against ONE full
    pass of the reference: the logits at every served position."""
    cfg, params, w, sizes = built
    toks, logits, _ = _serve(cfg, params, _prompt(prompt_len), 6,
                             paged_kernel=paged_kernel)
    want = _reference_rows(w, sizes, toks)
    for i, got in enumerate(logits):
        np.testing.assert_allclose(got, want[prompt_len - 1 + i], atol=ATOL)


def test_a_reused_slot_starts_from_a_zero_tail(built):
    """A second request in a slot whose tail and pages a first one filled:
    its logits are those it gets in a fresh engine's slot — the first chunk
    reads zeros in place of what the slot holds."""
    cfg, params, _, _ = built
    first, second = _prompt(19, seed=1), _prompt(13, seed=2)
    _, _, used = _serve(cfg, params, first, 4)
    assert float(jnp.abs(used[2][:, :, 1]).max()) > 0       # a tail was left
    assert float(jnp.abs(used[2][:, :, 0]).max()) == 0      # ... in its slot
    toks_a, logits_a, _ = _serve(cfg, params, second, 4)
    toks_b, logits_b, _ = _serve(cfg, params, second, 4, cache=used)
    assert toks_a == toks_b
    for a, b in zip(logits_a, logits_b):
        np.testing.assert_array_equal(a, b)


def test_the_selection_bias_moves_the_choice_and_not_the_weights():
    cfg = config_from_dict(toy.model_section())
    ks = jax.random.split(jax.random.PRNGKey(3), 2)
    f = jax.random.normal(ks[0], (24, cfg.hidden_size))
    router = 0.05 * jax.random.normal(ks[1], (cfg.hidden_size, 8))
    plain = {"router": router, "expert_bias": jnp.zeros((8,))}
    pushed = {"router": router,
              "expert_bias": jnp.zeros((8,)).at[5].set(10.0)}
    ids0, w0 = M.route(f, plain, cfg)
    ids1, w1 = M.route(f, pushed, cfg)
    assert not bool((ids0 == 5).any(-1).all())      # not everyone's choice
    assert bool((ids1 == 5).any(-1).all())          # ... until it is pushed
    s = jax.nn.sigmoid(f @ router)
    picked = jnp.take_along_axis(s, ids1, axis=-1)
    # the weights are the chosen SCORES over their sum + 1e-6: no bias
    np.testing.assert_allclose(
        w1, picked / (picked.sum(-1, keepdims=True) + 1e-6), rtol=1e-6)
    assert float(w1.max()) < 1.0
    off = config_from_dict(toy.model_section(use_expert_bias=False))
    np.testing.assert_array_equal(M.route(f, pushed, off)[0], ids0)


# ---------------------------------------------------------------- the kernel
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-6),
                                       (jnp.bfloat16, 1e-2)],
                         ids=["float32", "bfloat16"])
def test_paged_decode_at_32_over_8_heads_of_64_is_the_gathered_view(dtype,
                                                                    tol):
    """The decode kernel (interpreted) at the recipe's head geometry — 4
    query heads to each of 8 key-value heads of 64, a 512-lane pool —
    against the gathered view of the same pages. float32: the same sums in
    another order (a fold of pages at a time against one softmax), 2e-6 on
    outputs of size ~1; bfloat16: the probabilities are rounded to 8 bits
    before ``P · V`` on both sides but at other maxima, 1e-2."""
    B, nh, kv, hd, ps, P, L = 5, 32, 8, 64, 4, 12, 2
    geometry = dict(num_heads=nh, head_dim=hd, page_size=ps, pages_per_req=P,
                    dtype=dtype, num_kv_heads=kv)
    assert not PA.paged_attention_refusal(**geometry)
    assert PA.fold_shape(**geometry) == (8, 8)
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, nh, hd)).astype(dtype)
    pool_k = jax.random.normal(ks[1], (L, 1 + B * P, ps, kv * hd)
                               ).astype(dtype)
    pool_v = jax.random.normal(ks[2], pool_k.shape).astype(dtype)
    tables = jnp.asarray(1 + np.arange(B * P).reshape(B, P), jnp.int32)
    lens = jnp.asarray([0, 17, -1, 47, 30], jnp.int32)
    got = PA.paged_attention(q, pool_k, pool_v, tables, lens, jnp.int32(1))
    kd = pool_k[1][tables].reshape(B, P * ps, kv, hd)
    vd = pool_v[1][tables].reshape(B, P * ps, kv, hd)
    kp = jnp.broadcast_to(jnp.arange(P * ps), (B, P * ps))
    with jax.default_matmul_precision("highest"):
        want = programs.gathered_attention(
            q[:, None], kd, vd, kp, jnp.maximum(lens, 0)[:, None], None,
            dtype)[:, 0]
    live = np.asarray(lens) >= 0
    np.testing.assert_allclose(
        np.asarray(got, np.float32)[live], np.asarray(want, np.float32)[live],
        atol=tol)
    assert not np.asarray(got, np.float32)[~live].any()    # an empty slot


def test_the_kernel_still_refuses_what_it_cannot_serve():
    ask = lambda **kw: PA.paged_attention_refusal(**{**dict(  # noqa: E731
        num_heads=32, head_dim=64, page_size=16, pages_per_req=224,
        dtype=jnp.bfloat16, num_kv_heads=8), **kw})
    assert ask() == ""
    assert "neither whole 128-lane tiles nor half of one" in ask(head_dim=32)
    assert "neither whole 128-lane tiles nor half of one" in ask(head_dim=96)
    assert "no multiple" in ask(num_kv_heads=5)
    assert "neither float32 nor bfloat16" in ask(dtype=jnp.float16)
    # ungrouped heads of 64 are what they were: GPT's geometry
    assert ask(num_heads=16, num_kv_heads=16) == ""
    assert PA.fold_shape(num_heads=32, head_dim=64, page_size=16,
                         pages_per_req=224, dtype=jnp.bfloat16,
                         num_kv_heads=8) == (32, 32)


# ----------------------------------------------------------------- the engine
def _engine(cfg, params, **serving):
    sc = ServingConfig(**{**dict(max_batch=3, page_size=PAGE, num_pages=60,
                                 max_seq_len=96, prefill_chunk=CHUNK,
                                 max_queue=0), **serving})
    return ServingEngine(cfg, params, sc, SamplingParams(), eos_token_id=-1)


def _widest_gap(w, sizes, prompt, served) -> float:
    toks = list(prompt) + list(served)
    lg = _reference_rows(w, sizes, toks)
    at = np.arange(len(prompt) - 1, len(toks) - 1)
    return float((lg[at].max(-1) - lg[at, np.asarray(served)]).max())


def test_the_engine_serves_the_family_and_never_retraces(built):
    """Requests join and leave ONE engine (the same class, scheduler and
    allocator as every family's) while others are mid-prefill — a decode
    step must leave a prefilling slot's tail alone —; every served token is
    the reference's best within float32's grain; each program compiled
    once; the build's line, the gauges and the snapshot name the tail."""
    import logging

    from fleetx_tpu.utils.log import logger

    cfg, params, w, sizes = built
    said = []
    handler = logging.Handler()
    handler.emit = lambda record: said.append(record.getMessage())
    logger.addHandler(handler)
    try:
        eng = _engine(cfg, params)
    finally:
        logger.removeHandler(handler)
    line = [m for m in said if m.startswith("serving engine:")]
    assert line and "2 attention layers paged (128 lanes a token), 7 " \
        "convolution layers a tail of 2 rows a slot" in line[-1]
    assert eng.family is registry.family("ConvMoEModule")
    assert len(eng.cache) == 3 and eng.paged_kernel_active
    prompts = [_prompt(n) for n in (5, 29, 9, 26, 17)]
    reqs = [eng.submit(p, 6) for p in prompts[:2]]
    for _ in range(3):
        eng.step()
    reqs += [eng.submit(p, 6) for p in prompts[2:]]
    eng.run_until_drained()
    for req, prompt in zip(reqs, prompts):
        assert req.state == "finished" and len(req.tokens) == 6
        assert _widest_gap(w, sizes, prompt, req.tokens) < 1e-4
    assert eng._fns["decode"]._cache_size() == 1
    assert eng._fns["prefill"]._cache_size() == 1
    assert eng.allocator.allocated_pages == 0
    m, snap = eng.metrics, eng.serving_snapshot()
    tail = int(eng.cache[2].nbytes)
    assert tail == 7 * 2 * 3 * 512 * 4
    assert m.gauge("serving_state_cache_bytes").value == tail \
        == snap["serving_state_cache_bytes"]
    assert m.gauge("serving_kv_cache_bytes").value == eng.cache_bytes \
        == tail + 2 * int(eng.cache[0].nbytes)
    assert m.gauge("serving_latent_cache_bytes").value == 0
    assert not schema.validate_serving_record(snap)
    assert snap["kv_folds"] == {"full": [16, 16]}
    assert m.gauge("serving_kv_fold_pages_full").value == 16
    assert m.gauge("serving_kv_fold_pages_window").value == 0
    assert m.counter("serving_moe_passes_total").value > 0
    assert 0 < m.gauge("serving_page_walk_share").value <= 1
    # a tail's bytes follow the slots, never max_seq_len or the pool
    longer = _engine(cfg, params, max_seq_len=192, num_pages=120)
    assert longer.metrics.gauge("serving_state_cache_bytes").value == tail


def test_a_preempted_request_resumes_to_the_same_tokens(built):
    """A pool too small for three growing requests preempts the youngest:
    its pages are freed, its tail is whatever it is; it is prefilled again
    from its first token (which rebuilds the tail from zero) and serves the
    tokens an unpressed engine serves — each the reference's best."""
    cfg, params, w, sizes = built
    prompts = [_prompt(n, seed=11 + n) for n in (9, 10, 11)]

    def run(num_pages):     # (the gathered view: interpreting the kernel
        eng = _engine(cfg, params, paged_kernel=False)  # is most of a step)
        eng.allocator = type(eng.allocator)(num_pages, PAGE)
        reqs = [eng.submit(p, 20) for p in prompts]
        eng.run_until_drained()
        return reqs

    calm, pressed = run(60), run(16)
    assert sum(r.preemptions for r in calm) == 0
    assert sum(r.preemptions for r in pressed) > 0
    for a, b, prompt in zip(calm, pressed, prompts):
        assert a.tokens == b.tokens and len(b.tokens) == 20
        assert _widest_gap(w, sizes, prompt, b.tokens) < 1e-4


def test_a_mesh_is_refused_with_a_sentence(built):
    from jax.sharding import Mesh

    cfg, params, _, _ = built
    mesh = Mesh(np.array(jax.devices()[:2]).reshape(1, 1, 2, 1, 1),
                ("data", "fsdp", "tensor", "seq", "pipe"))
    with pytest.raises(AssertionError, match="serves on one chip"):
        registry.family("ConvMoEModule").programs(
            cfg, ServingConfig(max_batch=2, page_size=PAGE, num_pages=20,
                               max_seq_len=32, prefill_chunk=CHUNK),
            SamplingParams(), mesh, 8)
    with pytest.raises(ValueError, match="the 6 served families"):
        registry.family("NoSuchModule")


# ------------------------------------------------------------ what is assumed
def _other_split(bcx, cfg):
    h = cfg.hidden_size
    b32 = bcx.astype(jnp.float32)       # read as C, B, x
    return (b32[:, h:2 * h] * b32[:, 2 * h:]).astype(bcx.dtype), b32[:, :h]


def _interleaved(x, cos, sin):
    x32 = x.astype(jnp.float32)
    a, b = x32[..., 0::2], x32[..., 1::2]
    c, s = cos[..., None, :], sin[..., None, :]
    return jnp.stack([a * c - b * s, b * c + a * s], -1).reshape(
        x.shape).astype(x.dtype)


#: each reading the comparison has to tell from the one taken: another one
#: in place of the one function of ``models/conv_moe/model.py`` that holds
#: it (the ``1e-6`` moves a weight by a millionth and is below any limit)
OTHER_READINGS = {
    "the split read as C, B, x": ("conv_gates", _other_split),
    "rotary on neighbouring pairs": ("apply_rotary", _interleaved),
    "no norm on queries and keys": (
        "rms_norm", lambda x, scale, eps, dtype: x.astype(dtype)
        if scale.shape[-1] == 64 else M.shared.rms_norm(x, scale, eps,
                                                        dtype)),
}


@pytest.mark.parametrize("reading", sorted(OTHER_READINGS))
def test_the_comparison_sees_each_other_reading(built, monkeypatch, reading):
    """With one reading taken otherwise in the program its logits leave the
    reference's by far more than the sound program's 2e-5."""
    cfg, params, w, sizes = built
    name, other = OTHER_READINGS[reading]
    monkeypatch.setattr(M, name, other)
    toks = _prompt(24)
    got = np.asarray(_forward(params, cfg, jnp.asarray(toks)))
    want = _reference_rows(w, sizes, toks)[:len(toks)]
    assert float(np.abs(got - want).max()) > 50 * ATOL, reading


def test_the_float8_control_fails_the_toy_limit(built):
    """What the cell's check does, at toy widths: the served tokens lie
    within float32's grain of the reference's best (limit 1e-3: fifty
    times the 2e-5 the logits are held to), and the tokens the reference
    puts first when its products run in float8 do not."""
    cfg, params, w, sizes = built
    prompt = _prompt(21)
    toks, _, _ = _serve(cfg, params, prompt, 10)
    samples = [(prompt, toks[len(prompt):])]
    source = weights.Source(ref.weight_spec(sizes), 7)
    limit = {"served_logit_widest_gap": 1e-3}
    sound = check.served_logit_gaps(ref, sizes, source, samples, 64)
    assert check.judge({"served_logit_widest_gap": sound["widest_gap"]},
                       limit)
    ctl = check.served_logit_gaps(ref, sizes, source, samples, 64,
                                  chooser="float8")
    assert not check.judge({"served_logit_widest_gap": ctl["widest_gap"]},
                           limit)


# ------------------------------------------------------ recipe and the tree
def _recipe_cfg(overrides=()):
    from fleetx_tpu.utils import config as config_mod

    return config_mod.get_config(
        os.path.join(ROOT, SHIPPED["serve"]["recipe"]), list(overrides),
        num_devices=1)


def test_the_built_tree_is_5178_m_parameters_served_in_bfloat16():
    """The recipe's tree, leaf by leaf: ISSUE 44's arithmetic (10.35 GB,
    the head tied) and the configuration file's ``bytes``."""
    model_cfg, template = registry.served_template(_recipe_cfg())
    leaves = jax.tree.leaves(template)
    count = sum(int(np.prod(l.shape)) for l in leaves)
    nbytes = sum(int(np.prod(l.shape)) * l.dtype.itemsize for l in leaves)
    assert count == M.count_params(model_cfg) == 5_177_950_976 \
        == SHIPPED["bytes"]["parameters"]
    assert nbytes == 10_358_078_464 == SHIPPED["bytes"]["served_bytes"]
    assert abs(nbytes / 10.35e9 - 1) < 0.02
    part = lambda kind, group: sum(  # noqa: E731
        int(np.prod(l.shape)) for l in jax.tree.leaves(template[kind][group]))
    assert part("conv_dense", "conv") == 16_783_360
    assert part("full_moe", "attn") == 2 * 10_485_888
    assert part("conv_dense", "mlp") == 72_351_744
    assert part("full_moe", "moe") == 2 * 604_110_912
    assert "head" not in template           # tied to the embedding
    assert model_cfg.kinds() == {"conv_dense": 1, "full_moe": 2,
                                 "conv_moe": 6}
    f32 = {"/".join(str(getattr(p, "key", p)) for p in path)
           for path, l in jax.tree_util.tree_flatten_with_path(template)[0]
           if l.dtype == jnp.float32}
    assert {"full_moe/moe/router", "full_moe/moe/expert_bias",
            "final_norm/scale", "full_moe/attn/q_norm",
            "conv_moe/operator_norm/scale"} <= f32
    assert "conv_moe/conv/in" not in f32 and "conv_moe/conv/taps" not in f32
    pool, tail = S.cache_shapes(model_cfg, num_pages=32769, page_size=16,
                                max_batch=256)
    assert pool == (2, 32769, 16, 512) and tail == (7, 2, 256, 2048)
    # a token's keys and values, a slot's tails: the issue's 4,096 and 57 KB
    assert 2 * 2 * 512 * 2 == 4096 and 7 * 2 * 2048 * 2 == 57_344
    assert not S.kernel_refusal(model_cfg, page_size=16, pages_per_req=224)


@pytest.mark.parametrize("missing", ["layer_types", "conv_L_cache",
                                     "use_expert_bias", "rope_parameters",
                                     "num_dense_layers"])
def test_a_recipe_that_omits_a_published_key_is_refused_by_name(missing):
    model = toy.model_section()
    del model[missing]
    with pytest.raises(ValueError, match=missing):
        config_from_dict(model)


def test_the_shipped_recipe_states_every_published_key_at_its_value():
    model = dict(_recipe_cfg()["Model"])
    for key in PUBLISHED_KEYS:
        assert key in model, key
        if key == "layer_types":    # the file keeps the published forty
            assert list(model[key]) == [SHIPPED[key][i]
                                        for i in SHIPPED["kept_layers"]]
        else:
            got = model[key]
            assert (dict(got) if isinstance(got, dict) else got) \
                == SHIPPED[key], key
    with pytest.raises(AssertionError, match="rope_type"):
        config_from_dict(toy.model_section(
            rope_parameters={"rope_theta": 1e6, "rope_type": "yarn"}))
    with pytest.raises(AssertionError, match="layer_types"):
        config_from_dict(toy.model_section(num_hidden_layers=8))


def test_tools_serve_builds_the_recipe_through_the_registry():
    """``tools/serve.py:_build_engine`` on the shipped recipe at toy
    widths: the same function that builds every family's engine."""
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    import serve as serve_tool

    over = [f"Model.{k}={v if isinstance(v, bool) else json.dumps(v)}"
            for k, v in toy.model_section().items()
            if k not in ("dtype", "param_dtype", "module")]
    over += ["Model.dtype=float32", "Serving.max_batch=2",
             "Serving.num_pages=33", "Serving.page_size=4",
             "Serving.max_seq_len=64", "Serving.prefill_chunk=8",
             "Serving.paged_kernel=False"]
    eng = serve_tool._build_engine(_recipe_cfg(over))
    assert isinstance(eng, ServingEngine)
    assert type(eng.family).__name__ == "ConvMoEFamily"
    req = eng.submit([1, 2, 3, 4, 5], 4)
    eng.run_until_drained()
    assert req.state == "finished" and len(req.tokens) == 4


def test_the_new_scopes_are_the_tables_and_add_no_host_span(built):
    """``conv.proj`` / ``conv.mix`` are in ``DEVICE_SCOPES`` and in both
    compiled programs beside the names every family uses; the tick's host
    spans are what they were."""
    from fleetx_tpu.observability import trace

    assert {"conv.proj", "conv.mix"} <= set(trace.DEVICE_SCOPES)
    assert not [s for s in trace.HOT_LOOP_SPANS if "conv" in s or "tail" in s]
    assert len(trace.HOT_LOOP_SPANS) == 15
    cfg, params, _, _ = built
    fns = _fns(cfg, False)
    cache = S.init_cache(cfg, num_pages=9, page_size=PAGE, max_batch=2)
    key = jax.random.PRNGKey(0)
    programs = {
        "decode": (params, *cache, np.zeros((2,), np.int32), np.int32(-1),
                   np.zeros((1,), np.int32), np.zeros((2, 8), np.int32),
                   np.zeros((2,), np.int32), key, np.uint32(0)),
        "prefill": (params, *cache, np.zeros((1, CHUNK), np.int32),
                    np.zeros((1, 8), np.int32), np.int32(0), np.int32(3),
                    key, np.uint32(0), np.int32(1))}
    for name, args in programs.items():
        text = fns[name].lower(*args).compile().as_text()
        scopes = {s for s, _ in trace.device_scope_table(text).values()}
        assert {"conv.proj", "conv.mix", "attn.proj", "attn.core",
                "attn.cache", "moe.experts", "moe.route", "mlp", "norm",
                "embed", "head"} <= scopes, (name, scopes)
