"""The windowed-attention sparse-expert family (``models/swa_moe``,
``serving/swa_moe.py``) against its plain reference
(``benchmarks/reference/laguna_ref.py``), at toy widths on the CPU.

Weights are seeded float32, so program and reference differ by the order of
float32 sums alone: logits are held to 2e-5 (the products accumulate a few
hundred terms of size ~1; float32's grain there is ~1e-7 a term).
"""

import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.manifest import load_module
from fleetx_tpu.models.swa_moe import model as M
from fleetx_tpu.models.swa_moe.config import config_from_dict
from fleetx_tpu.serving import registry, swa_moe as S
from fleetx_tpu.serving.decode import SamplingParams
from fleetx_tpu.serving.engine import ServingConfig, ServingEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ref = load_module(os.path.join(ROOT, "benchmarks/reference/laguna_ref.py"))
with open(os.path.join(ROOT, "benchmarks/configs/laguna-s-2.1.json")) as _f:
    SHIPPED = json.load(_f)

WINDOW, CHUNK, PAGE = 8, 8, 4
TOY = dict(
    vocab_size=64, hidden_size=32, intermediate_size=48, num_hidden_layers=9,
    num_attention_heads_per_layer=[4, 6, 6, 6] * 3,
    layer_types=["full_attention"] + ["sliding_attention"] * 3
    + ["full_attention"] + ["sliding_attention"] * 3 + ["full_attention"],
    mlp_only_layers=[0], num_key_value_heads=2, head_dim=16,
    sliding_window=WINDOW, num_experts=16, experts_held=4,
    first_expert_held=4, num_experts_per_tok=3, moe_intermediate_size=24,
    shared_expert_intermediate_size=24,
    # what the family's members differ in, as the first member states it
    moe_routed_scaling_factor=2.5, gating="per-head",
    router_input="post_attention", router_scoring="softmax_topk",
    hidden_act="silu", rope_parameters=SHIPPED["rope_parameters"],
    dtype="float32", param_dtype="float32", max_position_embeddings=4096)


def _sizes(toy: dict, cfg) -> dict:
    """What the reference reads, for a toy model config."""
    sizes = dict(toy)
    sizes.update(num_experts=cfg.experts_held, router_experts=cfg.num_experts,
                 rope_parameters=cfg.rope_parameters, rms_norm_eps=1e-6,
                 norm_topk_prob=True, moe_routed_scaling_factor=2.5)
    return sizes


def _seeded(cfg, seed=0):
    """Parameters with every leaf random (norm scales 1 + N(0, 0.1)) and
    matrices large enough that every path matters."""
    params = M.init_params(cfg, jax.random.PRNGKey(seed))

    def spread(path, x):
        key = jax.random.PRNGKey(abs(hash(str(path))) % (2 ** 31))
        if {getattr(k, "key", None) for k in path} & M.F32_GROUPS:
            return x + 0.1 * jax.random.normal(key, x.shape)
        return x * 5.0
    return registry.family("SWAMoEModule").serving_params(
        jax.tree_util.tree_map_with_path(spread, params), cfg)


def _reference_weights(params, sizes, module=None, shipped=None) -> dict:
    """The program's tree under a reference's names (``laguna_ref`` unless
    told), through its shipped configuration's ``param_paths``."""
    module, shipped = module or ref, shipped or SHIPPED
    out = {}
    for name, (shape, _) in module.weight_spec(sizes).items():
        node = params
        for key in shipped["param_paths"][name].split("/"):
            node = node[key]
        assert tuple(node.shape) == tuple(shape), (name, node.shape, shape)
        out[name] = jnp.asarray(node, jnp.float32)
    return out


@pytest.fixture(scope="module")
def toy():
    cfg = config_from_dict(TOY)
    params = _seeded(cfg)
    sizes = _sizes(TOY, cfg)
    return cfg, params, sizes, _reference_weights(params, sizes)


def _serve(*args, **kw):
    """``_serve_cache`` without the caches it leaves."""
    return _serve_cache(*args, **kw)[:4]


def _serve_cache(cfg, params, prompt, new, *, max_seq=64, slot=1,
                 max_batch=3, paged_kernel=False, page=PAGE, chunk=CHUNK,
                 cache=None, fns=None):
    """Prefill ``prompt`` in chunks, decode ``new`` tokens greedily, in slot
    ``slot`` of an otherwise empty batch: ``(tokens, logits a step, the last
    step's counters, the programs, the four caches as the request leaves
    them)``; ``cache`` and ``fns`` of an earlier call carry on from it."""
    P = max_seq // page
    fns = fns or S.make_step_fns(cfg, page_size=page, prefill_chunk=chunk,
                                 sampling=SamplingParams(),
                                 paged_kernel=paged_kernel)
    cache = cache or S.init_cache(cfg, num_pages=1 + max_batch * P,
                                  page_size=page, max_batch=max_batch,
                                  prefill_chunk=chunk)
    table = np.zeros((max_batch, P), np.int32)
    table[slot] = 1 + slot * P + np.arange(P)
    key = jax.random.PRNGKey(0)
    toks, logits, pos = list(prompt), [], 0
    while pos < len(prompt):
        part = prompt[pos:pos + chunk]
        row = np.zeros((1, chunk), np.int32)
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
        *cache, tk, lg, stats = fns["decode"](
            params, *cache, last, np.int32(-1), np.zeros((1,), np.int32),
            table, lens, key, np.uint32(0))
        logits.append(np.asarray(lg[slot]))
        toks.append(int(tk[slot]))
    return toks, logits, jax.device_get(stats), fns, cache


@pytest.mark.parametrize("prompt_len,new", [
    (3, 2),                 # all below the window
    (WINDOW, 3),            # the prompt is the window
    (WINDOW + 5, 12),       # decode leaves the window and a second chunk
    (3 * CHUNK + 1, 20),    # several chunks, a ragged last one, long decode
])
def test_prefill_then_decode_through_both_caches_is_the_reference(
        toy, prompt_len, new):
    cfg, params, sizes, w = toy
    prompt = np.random.default_rng(prompt_len).integers(
        0, cfg.vocab_size, size=prompt_len).tolist()
    toks, logits, stats, fns = _serve(cfg, params, prompt, new)
    width = -(-len(toks) // 16) * 16
    row = np.zeros((1, width), np.int32)
    row[0, :len(toks)] = toks
    want = np.asarray(ref.logits(w, sizes, jnp.asarray(row)))[0]
    for i, got in enumerate(logits):
        at = prompt_len - 1 + i
        np.testing.assert_allclose(got, want[at], atol=2e-5, rtol=0)
    # one active row: at most k held experts a layer hit, every pair counted
    layers = sum(n for kind, n in cfg.kinds().items() if kind.endswith("moe"))
    assert int(stats["rows"]) == 1
    assert 0 <= float(stats["hit"]) <= layers * cfg.num_experts_per_tok
    assert float(stats["hit"]) == int(stats["pairs_held"])
    assert fns["decode"]._cache_size() == 1
    assert fns["prefill"]._cache_size() == 1


def test_ring_is_an_all_paged_cache_to_the_bit(toy, monkeypatch):
    """Window layers in a ring of window + chunk tokens a slot, against the
    same layers keeping EVERY token (a slot's cache as long as a request:
    nothing wraps), at ``max_seq_len`` four times the window: the logits of
    prefill and of every decode step are the same bits. And the ring's
    bytes do not depend on ``max_seq_len``."""
    cfg, params, _, _ = toy
    max_seq = 4 * WINDOW
    prompt = np.random.default_rng(7).integers(0, 64, size=13).tolist()
    ring = _serve(cfg, params, prompt, max_seq - 14, max_seq=max_seq)
    # the all-paged twin: the same forward told that a slot's window cache
    # is as many pages as a whole request, over buffers that large
    all_pages, forward = max_seq // PAGE, S._forward
    monkeypatch.setattr(S, "_forward", lambda *a, rp, **kw: forward(
        *a, rp=all_pages, **kw))
    full, ring_shape = S.cache_shapes(cfg, num_pages=1 + 3 * all_pages,
                                      page_size=PAGE, max_batch=3,
                                      prefill_chunk=CHUNK)
    twin = (ring_shape[0], 1 + 3 * all_pages) + ring_shape[2:]
    paged = _serve(cfg, params, prompt, max_seq - 14, max_seq=max_seq,
                   cache=[jnp.zeros(full), jnp.zeros(full),
                          jnp.zeros(twin), jnp.zeros(twin)])
    monkeypatch.undo()
    assert ring[0] == paged[0]
    for a, b in zip(ring[1], paged[1]):
        np.testing.assert_array_equal(a.view(np.uint32), b.view(np.uint32))
    shapes = [S.cache_shapes(cfg, num_pages=n, page_size=PAGE, max_batch=3,
                             prefill_chunk=CHUNK)[1]
              for n in (1 + 3 * 8, 1 + 3 * 512)]
    assert shapes[0] == shapes[1] == (
        6, 1 + 3 * (WINDOW + CHUNK) // PAGE, PAGE,
        cfg.num_key_value_heads * cfg.head_dim)


def test_the_shares_add_up_to_the_uncut_layer(toy):
    """The routed parts the 4 shares of 4 experts give, plus the shared
    expert once, are the uncut reference's expert layer."""
    cfg, params, sizes, w = toy
    rng = np.random.default_rng(3)
    u = jnp.asarray(rng.normal(size=(11, cfg.hidden_size)), jnp.float32)
    full = {name: jnp.asarray(0.3 * rng.normal(size=shape), jnp.float32)
            for name, shape in {
                "router": (cfg.hidden_size, 16),
                "e_gate": (16, cfg.hidden_size, 24),
                "e_up": (16, cfg.hidden_size, 24),
                "e_down": (16, 24, cfg.hidden_size),
                "s_gate": (cfg.hidden_size, 24), "s_up": (cfg.hidden_size, 24),
                "s_down": (24, cfg.hidden_size)}.items()}
    uncut = dict(sizes, num_experts=16, first_expert_held=0)
    want = np.asarray(ref._experts(u, full, uncut, "float32"))
    shared = np.asarray(ref._gated_mlp(u, full["s_gate"], full["s_up"],
                                       full["s_down"], "float32"))
    total = np.zeros_like(want)
    for share in range(4):
        held = config_from_dict(dict(TOY, first_expert_held=4 * share))
        ids, weights = M.route(u, full["router"], held)
        lo = 4 * share
        moe = {"experts_gate": full["e_gate"][None, lo:lo + 4],
               "experts_up": full["e_up"][None, lo:lo + 4],
               "experts_down": full["e_down"][None, lo:lo + 4]}
        routed, rows, _ = M.held_experts(u, ids, weights, moe, jnp.int32(0),
                                         held, 16, "moe_gmm")
        assert int(rows.sum()) == int(((ids >= lo) & (ids < lo + 4)).sum())
        total += np.asarray(routed)
    np.testing.assert_allclose(total + shared, want, atol=2e-5, rtol=0)


def test_the_expert_layers_sizes_are_derived_and_a_skewed_router_drops_none(
        toy):
    """Tile rows, pass rows and the prefill's key block are no keys of the
    config: the tile is the served dtype's sublane tile, a pass what a
    uniform router sends the held experts in whole tiles (at the recipe 32
    x 16 rows a decode step, 32 x 32 a chunk: the values swept on the
    chip). A router that sends every token to ONE held expert fills more
    than a pass; the loop takes the further turns and drops no row."""
    import dataclasses

    from fleetx_tpu.utils import config as config_mod

    fields = {f.name for f in dataclasses.fields(type(toy[0]))}
    assert not fields & {"moe_tile_rows", "moe_decode_pass_rows",
                         "moe_prefill_pass_rows", "prefill_key_block"}
    recipe = registry.model_config(config_mod.get_config(
        os.path.join(ROOT, SHIPPED["serve"]["recipe"]), [], num_devices=1))
    assert (M.tile_rows(recipe), M.pass_rows(recipe, 64),
            M.pass_rows(recipe, 512)) == (16, 512, 1024)
    cfg = toy[0]
    assert M.tile_rows(cfg) == 8            # float32
    rng = np.random.default_rng(5)
    n, h, f = 100, cfg.hidden_size, cfg.moe_intermediate_size
    u = jnp.asarray(rng.normal(size=(n, h)), jnp.float32)
    moe = {name: jnp.asarray(0.3 * rng.normal(size=shape), jnp.float32)
           for name, shape in {"experts_gate": (1, 4, h, f),
                               "experts_up": (1, 4, h, f),
                               "experts_down": (1, 4, f, h)}.items()}
    lo = cfg.first_expert_held
    ids = jnp.asarray(np.tile([lo + 2, 0, 1], (n, 1)), jnp.int32)
    weights = jnp.asarray(rng.uniform(0.5, 1.0, size=(n, 3)), jnp.float32)
    rows = M.pass_rows(cfg, n)
    assert rows < n                         # 100 rows on one expert: 2 passes
    got, held, turns = M.held_experts(u, ids, weights, moe, jnp.int32(0),
                                      cfg, rows, "moe_gmm")
    assert held.tolist() == [0, 0, n, 0] and int(turns) == 2
    want = weights[:, :1] * ref._gated_mlp(
        u, moe["experts_gate"][0, 2], moe["experts_up"][0, 2],
        moe["experts_down"][0, 2], "float32")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5,
                               rtol=0)


def test_the_built_tree_is_3199_m_parameters_served_in_bfloat16():
    from fleetx_tpu.utils import config as config_mod

    cfg = config_mod.get_config(
        os.path.join(ROOT, SHIPPED["serve"]["recipe"]),
        list(SHIPPED["serve"]["overrides"]), num_devices=1)
    model_cfg, template = registry.served_template(cfg)
    leaves = jax.tree_util.tree_flatten_with_path(template)[0]
    assert sum(math.prod(l.shape) for _, l in leaves) == 3_199_460_352 \
        == SHIPPED["bytes"]["parameters"] == M.count_params(model_cfg)
    assert sum(math.prod(l.shape) * l.dtype.itemsize for _, l in leaves) \
        == SHIPPED["bytes"]["served_bytes"]
    for path, leaf in leaves:
        keys = {getattr(k, "key", None) for k in path}
        f32 = bool(keys & (M.F32_GROUPS | M.F32_LEAVES))
        assert leaf.dtype == (jnp.float32 if f32 else jnp.bfloat16), path
    # the reference's spec is the same tree under its own names
    spec = ref.weight_spec(SHIPPED)
    assert set(spec) == set(SHIPPED["param_paths"])
    assert sum(math.prod(s) for s, _ in spec.values()) == 3_199_460_352
    # the pattern is data: two periods after layer 0
    assert model_cfg.runs() == [
        ("full_dense", 0, 1, 0), ("window_moe", 0, 3, 0),
        ("full_moe", 0, 1, 1), ("window_moe", 3, 3, 3),
        ("full_moe", 1, 1, 2)]
    sc = cfg["Serving"]
    full, ring = S.cache_shapes(
        model_cfg, num_pages=sc["num_pages"], page_size=sc["page_size"],
        max_batch=sc["max_batch"], prefill_chunk=sc["prefill_chunk"])
    assert full == (3, 18001, 16, 1024) and ring == (6, 4097, 16, 1024)


# ------------------------------------------------------------ the engine
def _engine(cfg, params, **serving):
    sc = ServingConfig(max_batch=3, page_size=PAGE, num_pages=40,
                       max_seq_len=64, prefill_chunk=CHUNK, max_queue=0,
                       **serving)
    return ServingEngine(cfg, params, sc, SamplingParams(), eos_token_id=-1)


def _widest_gap(w, sizes, prompt, served) -> float:
    toks = list(prompt) + list(served)
    row = np.zeros((1, -(-len(toks) // 16) * 16), np.int32)
    row[0, :len(toks)] = toks
    lg = np.asarray(ref.logits(w, sizes, jnp.asarray(row)))[0]
    at = np.arange(len(prompt) - 1, len(toks) - 1)
    return float((lg[at].max(-1) - lg[at, np.asarray(served)]).max())


def test_the_engine_serves_the_family_and_never_retraces(toy):
    """Requests join and leave one engine (the same class, scheduler and
    allocator as GPT's); every served token is the reference's best within
    float32's grain; each program compiled once; the expert counters and
    the cache gauges are the program's own."""
    import logging

    from fleetx_tpu.utils.log import logger as program_logger

    cfg, params, sizes, w = toy
    said = []
    handler = logging.Handler()
    handler.emit = lambda record: said.append(record.getMessage())
    program_logger.addHandler(handler)
    try:
        eng = _engine(cfg, params)
    finally:
        program_logger.removeHandler(handler)
    assert eng.family is registry.family("SWAMoEModule")
    assert any(" 0 leaves cast, serving tree " in line for line in said)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, 64, size=n).tolist() for n in (5, 19, 9, 26)]
    reqs = [eng.submit(p, 12) for p in prompts[:2]]
    for _ in range(6):
        eng.step()
    reqs += [eng.submit(p, 12) for p in prompts[2:]]
    eng.run_until_drained()
    for req, prompt in zip(reqs, prompts):
        assert req.state == "finished" and len(req.tokens) == 12
        assert _widest_gap(w, sizes, prompt, req.tokens) < 1e-4
    assert eng._fns["decode"]._cache_size() == 1
    assert eng._fns["prefill"]._cache_size() == 1
    assert eng.allocator.allocated_pages == 0
    m = eng.metrics
    assert m.histogram("serving_moe_experts_hit").summary()["count"] > 0
    assert 0 < m.counter("serving_moe_pairs_held_total").value \
        <= m.counter("serving_moe_pairs_total").value
    assert m.gauge("serving_kv_cache_bytes").value == eng.cache_bytes \
        == sum(int(a.nbytes) for a in eng.cache)


def test_a_preempted_request_resumes_to_the_same_tokens(toy):
    """A pool too small for three growing requests preempts the youngest;
    it is prefilled again (which rebuilds its ring) and serves the tokens
    an unpressed engine serves."""
    cfg, params, _, _ = toy
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, 64, size=n).tolist() for n in (9, 10, 11)]

    def run(num_pages):
        eng = _engine(cfg, params)
        eng.allocator = type(eng.allocator)(num_pages, PAGE)
        reqs = [eng.submit(p, 24) for p in prompts]
        eng.run_until_drained()
        return reqs

    calm, pressed = run(40), run(18)
    assert sum(r.preemptions for r in calm) == 0
    assert sum(r.preemptions for r in pressed) > 0
    for a, b in zip(calm, pressed):
        assert a.tokens == b.tokens and len(b.tokens) == 24


@pytest.mark.parametrize("case", ["eos", "preempt_in_flight"])
def test_the_family_runs_the_one_tick_order(toy, case):
    """This family under the engine's one loop (a step is dispatched before
    the one before it is fetched; its programs take the slot's ring and
    return expert counters): a row that ends on an eos is acted on a step
    late, its overrun dropped and counted while the others run on; a row
    preempted while it is in flight gets nothing from that step and serves
    the same tokens again; the counters ride with every fetched step."""
    cfg, params, _, _ = toy
    rng = np.random.default_rng(21)
    prompts = [rng.integers(0, 64, size=n).tolist() for n in (7, 19, 11)]

    def serve(eos=-1, preempt=None):
        eng = ServingEngine(cfg, params, ServingConfig(
            max_batch=3, page_size=PAGE, num_pages=40, max_seq_len=64,
            prefill_chunk=CHUNK, max_queue=0), SamplingParams(),
            eos_token_id=eos)
        eng.reset_stats()
        reqs = [eng.submit(p, 14) for p in prompts]
        while eng.has_work():
            eng.step()
            r = reqs[preempt] if preempt is not None else None
            if r is not None and len(r.tokens) == 4 and not r.preemptions:
                assert any(q is r for q, _ in eng._inflight.rows)
                eng._preempt(r)
        assert eng.allocator.allocated_pages == 0 and eng._inflight is None
        m = eng.metrics
        assert m.histogram("serving_moe_experts_hit").total_count == \
            m.counter("serving_decode_steps").value
        return reqs, m.counter("serving_overrun_rows").value

    calm, overrun = serve()
    assert overrun == 0 and all(len(r.tokens) == 14 for r in calm)
    if case == "eos":
        eos = next(t for t in calm[1].tokens[2:-1]
                   if t not in calm[1].tokens[:2])
        want = [r.tokens[:r.tokens.index(eos) + 1] if eos in r.tokens
                else r.tokens for r in calm]
        got, overrun = serve(eos=eos)
        assert [r.tokens for r in got] == want
        assert overrun == sum(len(w) < 14 for w in want) >= 1
    else:
        got, overrun = serve(preempt=2)
        assert got[2].preemptions == 1 and overrun == 0
        assert [r.tokens for r in got] == [r.tokens for r in calm]


def test_window_cache_bytes_do_not_follow_max_seq_len(toy):
    cfg, params, _, _ = toy
    short = _engine(cfg, params)
    long = ServingEngine(cfg, params, ServingConfig(
        max_batch=3, page_size=PAGE, num_pages=40, max_seq_len=256,
        prefill_chunk=CHUNK, max_queue=0), SamplingParams(), eos_token_id=-1)
    assert [a.shape for a in short.cache] == [a.shape for a in long.cache]
    assert short.cache[2].shape[1] == 1 + 3 * (WINDOW + CHUNK) // PAGE


def test_the_kernel_path_serves_what_the_gather_serves():
    """At a geometry ``ops/paged_attention.py`` admits (128-wide heads, 2
    key-value heads under 4 or 6 query heads, a window) the decode program
    walks both caches in-kernel (interpret mode here) and serves the tokens
    and, within float32's grain, the logits of the gathered view."""
    toy = dict(TOY, head_dim=128, num_hidden_layers=5, sliding_window=16,
               rope_parameters=SHIPPED["rope_parameters"])
    cfg = config_from_dict(toy)
    assert not S.kernel_refusal(cfg, page_size=8, pages_per_req=8)
    params = _seeded(cfg, 1)
    prompt = np.random.default_rng(2).integers(0, 64, size=21).tolist()
    kw = dict(max_seq=64, page=8, chunk=8)
    gather = _serve(cfg, params, prompt, 30, **kw)
    kernel = _serve(cfg, params, prompt, 30, paged_kernel=True, **kw)
    assert gather[0] == kernel[0]
    for a, b in zip(gather[1], kernel[1]):
        np.testing.assert_allclose(a, b, atol=2e-5, rtol=0)


def test_tools_serve_builds_the_recipe_through_the_registry():
    """``tools/serve.py:_build_engine`` on the shipped recipe at toy
    widths: the same function that builds GPT's engine."""
    import sys

    from fleetx_tpu.utils import config as config_mod

    sys.path.insert(0, os.path.join(ROOT, "tools"))
    import serve as serve_tool

    over = [f"Model.{k}={json.dumps(v)}" for k, v in TOY.items()
            if k not in ("dtype", "param_dtype")]
    over += ["Model.dtype=float32", "Serving.max_batch=2",
             "Serving.num_pages=33", "Serving.page_size=4",
             "Serving.max_seq_len=64", "Serving.prefill_chunk=8"]
    cfg = config_mod.get_config(
        os.path.join(ROOT, SHIPPED["serve"]["recipe"]), over, num_devices=1)
    eng = serve_tool._build_engine(cfg)
    assert isinstance(eng, ServingEngine)
    assert type(eng.family).__name__ == "SWAMoEFamily"
    req = eng.submit([1, 2, 3, 4, 5], 4)
    eng.run_until_drained()
    assert req.state == "finished" and len(req.tokens) == 4


# ------------------------------------------------------------- the kernel
def _kernel_case(dtype, heads, kv, hd, *, ps=16, per_req=12, layers=2,
                 seed=35, lens=None):
    from fleetx_tpu.ops import paged_attention as PA  # noqa: F401

    rng = np.random.default_rng(seed)
    if lens is None:
        lens = [-1, 0, 37, per_req * ps - 1, 100, 129]
    B = len(lens)
    shape = (layers, 1 + B * per_req, ps, kv * hd)
    pk = jnp.asarray(rng.normal(size=shape), dtype)
    pv = jnp.asarray(rng.normal(size=shape), dtype)
    q = jnp.asarray(rng.normal(size=(B, heads, hd)), dtype)
    tables = (1 + rng.permutation(B * per_req).reshape(B, per_req)
              ).astype(np.int32)
    lens = np.array(lens, np.int32)
    for b in range(B):      # lazy allocation: null pages past the query
        tables[b, max(int(lens[b]), -1) // ps + 1:] = 0
    return q, pk, pv, jnp.asarray(tables), jnp.asarray(lens)


def _gathered(q, pk, pv, tables, lens, layer, window):
    """The gather path's attention over the same pages, float64; the keys
    of a null page below the query (a hole in the table) are not seen."""
    B, H, hd = q.shape
    kv = pk.shape[-1] // hd
    kd = np.asarray(pk[layer][tables], np.float64).reshape(B, -1, kv, hd)
    vd = np.asarray(pv[layer][tables], np.float64).reshape(B, -1, kv, hd)
    named = np.repeat(np.asarray(tables) != 0, pk.shape[2], axis=1)
    out = np.zeros((B, H, hd))
    for b in range(B):
        last = int(lens[b])
        if last < 0:
            continue
        lo = 0 if window is None else max(last - window + 1, 0)
        at = lo + np.flatnonzero(named[b, lo:last + 1])
        for j in range(H):
            k, v = kd[b, at, j // (H // kv)], vd[b, at, j // (H // kv)]
            s = k @ np.asarray(q[b, j], np.float64) / math.sqrt(hd)
            p = np.exp(s - s.max())
            out[b, j] = (p / p.sum()) @ v
    return out


@pytest.mark.parametrize("heads,kv,hd,window", [
    (16, 16, 64, None), (6 * 8, 8, 128, None), (9 * 8, 8, 128, 40),
    (9 * 8, 8, 128, 512)])
def test_paged_attention_with_fewer_kv_heads_and_a_window(heads, kv, hd,
                                                          window):
    """The extended kernel (interpret mode) against the gather for the
    serve cells' head geometries: as many key-value heads as query heads,
    6 and 9 query heads to each of 8, a window shorter than the context
    and one longer than any."""
    from fleetx_tpu.ops import paged_attention as PA

    assert PA.paged_attention_supported(
        num_heads=heads, head_dim=hd, page_size=16, pages_per_req=12,
        num_kv_heads=kv)
    args = _kernel_case(jnp.float32, heads, kv, hd)
    for layer in range(2):
        got = np.asarray(PA.paged_attention(*args, jnp.int32(layer), window))
        np.testing.assert_allclose(got, _gathered(*args, layer, window),
                                   atol=3e-6, rtol=0)
        assert not got[0].any()         # an inactive row: exact zeros


#: query positions of the long-table case's rows: either side of a 16-page
#: fold's edge (256 tokens at pages of 16) and of the next, an inactive row
#: between live ones, a row inside its first fold and one at its table's end
_FOLD_EDGE_LENS = (255, 256, -1, 257, 511, 512, 37, 639)


@pytest.mark.parametrize("stale", ["finite", "nan"])
@pytest.mark.parametrize("heads,kv,hd", [(16, 16, 64), (6 * 8, 8, 128)],
                         ids=["group1", "group6"])
def test_a_fold_of_16_pages_on_a_long_table_is_the_gather(heads, kv, hd,
                                                          stale):
    """A bfloat16 pool 1,024 lanes wide and 40 pages a request: the table
    walk folds 16 pages at a time (`_kernel_case`'s 12 pages a request never
    reach such a fold). Against the gathered reference for query positions
    either side of a fold's edge, an inactive row between live ones and a
    null entry INSIDE a counted group (a hole below the query: its keys are
    masked). ``nan``: every page no table names but the null page holds NaN
    — a page that does not count is copied from local page 0 in its place,
    so nothing of them reaches the output (the null page is READ and
    multiplied by a zero probability: it has to be finite)."""
    from fleetx_tpu.ops import paged_attention as PA

    ps, per_req = 16, 40
    assert PA.fold_shape(num_heads=heads, num_kv_heads=kv, head_dim=hd,
                         page_size=ps, pages_per_req=per_req,
                         dtype=jnp.bfloat16) == (16, 16)
    q, pk, pv, tables, lens = _kernel_case(
        jnp.bfloat16, heads, kv, hd, per_req=per_req, layers=1,
        lens=_FOLD_EDGE_LENS)
    tables = np.array(tables)
    tables[4, 5] = tables[7, 20] = 0    # holes below the query
    if stale == "nan":
        unnamed = np.setdiff1d(np.arange(1, pk.shape[1]), tables[tables > 0])
        pk, pv = (pool.at[:, unnamed].set(jnp.nan) for pool in (pk, pv))
    got = np.asarray(PA.paged_attention(
        q, pk, pv, jnp.asarray(tables), lens, jnp.int32(0)
    ).astype(jnp.float32))
    assert np.isfinite(got).all()
    assert not got[2].any()             # the inactive row: exact zeros
    np.testing.assert_allclose(
        got, _gathered(q, pk, pv, tables, lens, 0, None), atol=2e-2, rtol=0)


#: cell geometry -> (query heads, key-value heads, head_dim, table columns,
#: pages a fold): 512 KB a pool a fold, PR 43's sweep on the one-pass product
_CELL_FOLDS = {
    "gpt345m": (16, 16, 64, 64, 16),
    "laguna-full-layers": (48, 8, 128, 608, 16),
    "smallthinker-full-layers": (28, 4, 128, 816, 32),
}


@pytest.mark.parametrize("cell", sorted(_CELL_FOLDS))
def test_pages_a_fold_at_the_cells_geometries(cell):
    from fleetx_tpu.ops import paged_attention as PA

    heads, kv, hd, cols, fold = _CELL_FOLDS[cell]
    geometry = dict(num_heads=heads, num_kv_heads=kv, head_dim=hd,
                    page_size=16, pages_per_req=cols, dtype=jnp.bfloat16)
    assert PA.pick_pages_per_step(**geometry) == fold
    assert PA.page_walk_shape(**geometry) == (fold * 16, -(-cols // fold))
    # two slots a pool of it (2 MB) and the scratch fit the fold's budget
    assert 2 << 20 < PA._step_vmem_bytes(
        fold, 16, PA.pick_head_block(kv, hd, jnp.bfloat16), hd,
        jnp.bfloat16, heads // kv) <= PA._PAGED_VMEM_BUDGET_BYTES


def test_with_as_many_kv_heads_and_no_window_a_float32_pool_keeps_its_bits():
    """16 heads of 64 over 16 key-value heads, no window, a float32 pool:
    the bits of PR 34's kernel (``git show
    c2ae43d:fleetx_tpu/ops/paged_attention.py`` on this case, in this
    container's interpret mode, gave this digest; the new kernel was
    compared with it array for array when it was written). A pool that
    states float32 gets the exact product, whatever came since."""
    import hashlib

    from fleetx_tpu.ops import paged_attention as PA

    out = PA.paged_attention(*_kernel_case(jnp.float32, 16, 16, 64),
                             jnp.int32(1))
    got = hashlib.sha1(np.asarray(out).tobytes())
    assert got.hexdigest() == "5c9720caa1fb241f65b752f9b1b7e1d9430e8548"


def test_with_as_many_kv_heads_a_bfloat16_pool_multiplies_as_the_gather():
    """The same case on a bfloat16 pool (PR 34's bits until PR 41: they
    were the float32 product of a value tile converted every fold). The
    kernel now rounds its probabilities to bfloat16 and multiplies the tile
    as it landed, as the served program's gather path does
    (``serving/decode.py:_paged_attention`` on the same pool): the two
    agree within one bfloat16 ulp of the output's scale, and the kernel
    holds the float64 reference within the 2e-2 of the other bfloat16
    tests (it keeps its scores in float32, so it lies nearer than the
    gather does)."""
    from fleetx_tpu.ops import paged_attention as PA
    from fleetx_tpu.serving.decode import _paged_attention

    q, pk, pv, tables, lens = _kernel_case(jnp.bfloat16, 16, 16, 64)
    out = PA.paged_attention(q, pk, pv, tables, lens, jnp.int32(1))
    assert out.dtype == jnp.bfloat16
    out = np.asarray(out.astype(jnp.float32))
    B, H, hd = q.shape
    kd, vd = (pool[1][tables].reshape(B, -1, H, hd) for pool in (pk, pv))
    gathered = np.asarray(_paged_attention(
        q[:, None], kd, vd, lens[:, None])[:, 0].astype(jnp.float32))
    ulp = 2.0 ** (math.floor(math.log2(np.abs(out).max())) - 7)
    assert not out[0].any()             # the inactive row: exact zeros
    np.testing.assert_allclose(out[1:], gathered[1:], atol=ulp, rtol=0)
    np.testing.assert_allclose(
        out, _gathered(q, pk, pv, tables, lens, 1, None), atol=2e-2, rtol=0)


def _kernel_body_eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold (the
    Pallas body, its loops and branches)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _kernel_body_eqns(sub)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
@pytest.mark.parametrize("heads,kv,hd", [(16, 16, 64), (6 * 8, 8, 128)],
                         ids=["group1", "group6"])
def test_the_folds_products_follow_the_pools_dtype(monkeypatch, heads, kv,
                                                   hd, dtype):
    """What the kernel multiplies, read from its jaxpr as Mosaic would get
    it (interpret mode off; nothing is lowered): ``Q · Kᵀ`` and ``P · V``,
    one each. A bfloat16 pool — at one head count as with grouped queries —
    has no ``HIGHEST`` product, multiplies bfloat16 into bfloat16 with a
    float32 result, and makes no float32 copy of a ``[pages · page_size,
    width]`` tile; a float32 pool has the ``HIGHEST`` product, twice."""
    from fleetx_tpu import ops
    from fleetx_tpu.ops import paged_attention as PA

    monkeypatch.setattr(ops, "interpret", lambda: False)
    ps, per_req = 16, 12
    pool = jax.ShapeDtypeStruct((2, 40, ps, kv * hd), dtype)
    jaxpr = jax.make_jaxpr(PA._paged_call)(
        jax.ShapeDtypeStruct((4, heads, hd), dtype), pool, pool,
        jax.ShapeDtypeStruct((4, per_req), jnp.int32),
        jax.ShapeDtypeStruct((4,), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32))
    pages = PA.pick_pages_per_step(
        num_heads=heads, head_dim=hd, page_size=ps, pages_per_req=per_req,
        dtype=dtype, num_kv_heads=kv)
    tile = (pages * ps, PA.pick_head_block(kv, hd, dtype) * hd)
    eqns = list(_kernel_body_eqns(jaxpr.jaxpr))
    dots = [e for e in eqns if e.primitive.name == "dot_general"]
    assert len(dots) == 2
    for dot in dots:
        assert [v.aval.dtype for v in dot.invars] == [dtype, dtype]
        assert dot.outvars[0].aval.dtype == jnp.float32
        assert tile in [v.aval.shape for v in dot.invars]
        exact = dot.params["precision"] is not None and all(
            p == jax.lax.Precision.HIGHEST for p in dot.params["precision"])
        assert exact == (dtype == jnp.float32)
    if dtype == jnp.bfloat16:
        assert not [e for e in eqns for v in e.outvars
                    if getattr(v.aval, "shape", None) == tile
                    and v.aval.dtype == jnp.float32]


def test_the_window_walk_starts_where_the_window_does():
    from fleetx_tpu.ops import paged_attention as PA

    lens = np.array([-1, 0, 127, 128, 511, 512, 639, 640, 9000], np.int32)
    first = PA.first_group_walked(lens, 128, 512)
    np.testing.assert_array_equal(first[1:], [0, 0, 0, 0, 0, 1, 1, 66])
    np.testing.assert_array_equal(
        PA.page_groups_walked(lens, 128, 76, 512),
        [0, 1, 1, 2, 4, 5, 4, 5, 5])
    np.testing.assert_array_equal(
        PA.page_groups_walked(lens, 128, 76), [0, 1, 1, 2, 4, 5, 5, 6, 71])


def _ring_reference(q, pk, pv, first, lens, layer, window, rp):
    """Attention over the last ``window`` positions of each row's ring
    (position *p* in ring page ``(p // page) mod rp``), float64."""
    B, H, hd = q.shape
    ps, kv = pk.shape[2], pk.shape[3] // hd
    out = np.zeros((B, H, hd))
    pk, pv = (np.asarray(a[layer], np.float32) for a in (pk, pv))
    for b in range(B):
        last = int(lens[b])
        if last < 0:
            continue
        pos = np.arange(max(last - window + 1, 0), last + 1)
        at = int(first[b]) + (pos // ps) % rp, pos % ps
        k = pk[at].astype(np.float64).reshape(len(pos), kv, hd)
        v = pv[at].astype(np.float64).reshape(len(pos), kv, hd)
        for j in range(H):
            s = k[:, j // (H // kv)] @ np.asarray(q[b, j], np.float64) \
                / math.sqrt(hd)
            p = np.exp(s - s.max())
            out[b, j] = (p / p.sum()) @ v[:, j // (H // kv)]
    return out


#: name -> (query heads, key-value heads, ring pages, window, pages a fold
#: asked for, pages a fold taken, dtype): the two members' rings — 7 query
#: heads over each of 4 key-value heads of 128, a ring of 288 pages under a
#: 4,096-token window; 6 and 9 over 8, 64 pages under 512 — at 8, 16 and 32
#: pages a fold (32 pages of 1,024 lanes do not fit the fold's VMEM budget:
#: 16), and a ring that 32 and 16 pages do not divide (72 = 8 · 9)
RING_CASES = {
    "group7-8": (28, 4, 288, 4096, 8, 8, jnp.bfloat16),
    "group7-16": (28, 4, 288, 4096, 16, 16, jnp.bfloat16),
    "group7-32": (28, 4, 288, 4096, 32, 32, jnp.bfloat16),
    "group7-16-f32": (28, 4, 288, 4096, 16, 16, jnp.float32),
    "group6-8": (48, 8, 64, 512, 8, 8, jnp.bfloat16),
    "group6-16": (48, 8, 64, 512, 16, 16, jnp.bfloat16),
    "group9-8": (72, 8, 64, 512, 8, 8, jnp.bfloat16),
    "group9-16": (72, 8, 64, 512, 16, 16, jnp.bfloat16),
    "group9-32-does-not-fit": (72, 8, 64, 512, 32, 16, jnp.bfloat16),
    "group9-8-f32": (72, 8, 64, 512, 8, 8, jnp.float32),
    "group7-ring-of-72-takes-8": (28, 4, 72, 1024, 32, 8, jnp.bfloat16),
}


@pytest.mark.parametrize("case", sorted(RING_CASES))
def test_a_ring_fetched_in_one_copy_is_the_table_path_to_the_bit(
        monkeypatch, case):
    """The window layers' decode call tells the kernel what its pages are
    (``ring_pages=``, each row's first ring page in the table's place): a
    fold is then one copy a buffer and no page is flagged. At the same
    pages a fold the answer is the table path's (logical page *j* -> ring
    page ``j mod ring_pages``) to the BIT — the same keys, the same masks,
    the same order of sums — and the gathered reference's within the
    dtype's grain, for rows under the window, exactly at it, one past it,
    at the ring's wrap, at multiples of the ring, and inactive (exact
    zeros). The ring holds finite values everywhere: what a masked key
    holds is read."""
    from fleetx_tpu.ops import paged_attention as PA

    heads, kv, rp, window, asked, taken, dtype = RING_CASES[case]
    hd, ps = 128, 16
    page_bytes = ps * kv * hd * jnp.dtype(dtype).itemsize
    monkeypatch.setattr(PA, "_RING_FOLD_BYTES", asked * page_bytes)
    monkeypatch.setattr(PA, "_FOLD_BYTES", taken * page_bytes)
    geometry = dict(num_heads=heads, head_dim=hd, page_size=ps, dtype=dtype,
                    num_kv_heads=kv)
    assert PA.fold_shape(pages_per_req=rp, ring_pages=rp, **geometry) \
        == (taken, 1)
    ring = rp * ps
    lens = np.array([-1, 0, window // 2 + 3, window - 1, window, ring - 1,
                     ring, ring + 5, 2 * ring - 1, 2 * ring, 3 * ring + 77],
                    np.int32)
    B, per_req = len(lens), -(-(int(lens.max()) + 1) // ps)
    assert PA.fold_shape(pages_per_req=per_req, **geometry) == (taken, taken)
    rng = np.random.default_rng(39)
    shape = (2, 1 + B * rp, ps, kv * hd)
    pk = jnp.asarray(rng.standard_normal(shape, np.float32), dtype)
    pv = jnp.asarray(rng.standard_normal(shape, np.float32), dtype)
    q = jnp.asarray(rng.normal(size=(B, heads, hd)), dtype)
    first = (1 + rp * rng.permutation(B)).astype(np.int32)
    table = first[:, None] + np.arange(per_req, dtype=np.int32)[None, :] % rp
    layer = jnp.int32(1)
    got = PA.paged_attention(q, pk, pv, jnp.asarray(first), jnp.asarray(lens),
                             layer, window, ring_pages=rp)
    by_table = PA.paged_attention(q, pk, pv, jnp.asarray(table),
                                  jnp.asarray(lens), layer, window)
    assert got.dtype == q.dtype
    got, by_table = (np.asarray(a, np.float32) for a in (got, by_table))
    np.testing.assert_array_equal(got, by_table)
    tol = 3e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        got, _ring_reference(q, pk, pv, first, lens, 1, window, rp),
        atol=tol, rtol=tol)
    assert not got[0].any()             # an inactive row: exact zeros


def test_pages_a_fold_follow_the_bytes_a_fold_moves():
    """The one rule, at the geometries that are served: 16 pages a fold on
    the 1,024-lane bfloat16 pools (both GPT cells: 16 heads of 64; Laguna's
    full layers: 8 key-value heads of 128), 32 on the second member's
    512-lane full pool — 512 KB a pool, as a ring's fold; a ring is one
    copy a buffer whatever its pages, and takes 32 pages of 512 lanes or 16
    of 1,024; a float32 pool keeps 8 (its page is twice the bytes)."""
    from fleetx_tpu.ops import paged_attention as PA

    page = dict(head_dim=128, page_size=16, dtype=jnp.bfloat16)
    assert PA.fold_shape(num_heads=16, head_dim=64, page_size=16,
                         pages_per_req=64, dtype=jnp.bfloat16) == (16, 16)
    assert PA.fold_shape(num_heads=16, head_dim=64, page_size=16,
                         pages_per_req=64, dtype=jnp.float32) == (8, 8)
    for heads in (48, 72):
        assert PA.fold_shape(num_heads=heads, num_kv_heads=8,
                             pages_per_req=608, **page) == (16, 16)
    assert PA.fold_shape(num_heads=72, num_kv_heads=8, pages_per_req=64,
                         ring_pages=64, **page) == (16, 1)
    assert PA.fold_shape(num_heads=28, num_kv_heads=4, pages_per_req=816,
                         **page) == (32, 32)
    assert PA.fold_shape(num_heads=28, num_kv_heads=4, pages_per_req=288,
                         ring_pages=288, **page) == (32, 1)
    # no more than a request has; a ring no fold divides is walked a page
    assert PA.fold_shape(num_heads=28, num_kv_heads=4, pages_per_req=5,
                         **page) == (4, 4)
    assert PA.fold_shape(num_heads=28, num_kv_heads=4, pages_per_req=33,
                         ring_pages=33, **page) == (1, 1)


def test_kv_pool_spec_refuses_a_tensor_axis_wider_than_the_kv_heads():
    from fleetx_tpu.parallel.rules import kv_pool_spec

    assert kv_pool_spec(num_kv_heads=8, tensor_degree=4) == kv_pool_spec()
    with pytest.raises(ValueError, match="8 key-value heads.*tensor axis "
                                         "of 16"):
        kv_pool_spec(num_kv_heads=8, tensor_degree=16)


# ---------------------------------------------- the family's second member
# SmallThinker (ISSUE 38): the router reads the layer's normed INPUT, the k
# largest logits are chosen and the softmax is over those alone, ReGLU
# experts all held, no shared expert, no dense layer, no gate; window layers
# rotate, full layers carry no position signal; 7 query heads to a
# key-value head. Against ``benchmarks/reference/smallthinker_ref.py``,
# which reads the PUBLISHED keys.
ref2 = load_module(os.path.join(ROOT,
                                "benchmarks/reference/smallthinker_ref.py"))
with open(os.path.join(ROOT,
                       "benchmarks/configs/smallthinker-21b-a3b.json")) as _f:
    SHIPPED2 = json.load(_f)

PUBLISHED2 = dict(
    vocab_size=64, hidden_size=32, num_hidden_layers=8, head_dim=16,
    num_attention_heads=14, num_key_value_heads=2,
    sliding_window_layout=[0, 1, 1, 1] * 2, rope_layout=[0, 1, 1, 1] * 2,
    sliding_window_size=WINDOW, rope_theta=1500000, rms_norm_eps=1e-6,
    moe_num_primary_experts=16, moe_num_active_primary_experts=3,
    moe_ffn_hidden_size=24, moe_primary_router_apply_softmax=True,
    norm_topk_prob=True)


def _toy2(published: dict = PUBLISHED2, **over) -> dict:
    """The family's keys as the shipped recipe derives them from the
    published ones (``SHIPPED2['derived']`` says how)."""
    p = published
    rotary = {"rope_type": "default", "rope_theta": p["rope_theta"],
              "partial_rotary_factor": 1}
    by_type = {}
    for win, rot in zip(p["sliding_window_layout"], p["rope_layout"]):
        by_type["sliding_attention" if win else "full_attention"] = \
            rotary if rot else "none"
    toy = dict(
        vocab_size=p["vocab_size"], hidden_size=p["hidden_size"],
        intermediate_size=0, num_hidden_layers=p["num_hidden_layers"],
        num_attention_heads_per_layer=[p["num_attention_heads"]]
        * p["num_hidden_layers"],
        layer_types=["sliding_attention" if w else "full_attention"
                     for w in p["sliding_window_layout"]],
        mlp_only_layers=[], num_key_value_heads=p["num_key_value_heads"],
        head_dim=p["head_dim"], sliding_window=p["sliding_window_size"],
        rope_parameters=by_type, num_experts=p["moe_num_primary_experts"],
        experts_held=p["moe_num_primary_experts"], first_expert_held=0,
        num_experts_per_tok=p["moe_num_active_primary_experts"],
        moe_intermediate_size=p["moe_ffn_hidden_size"],
        shared_expert_intermediate_size=0, moe_routed_scaling_factor=1.0,
        norm_topk_prob=True, gating="none", router_input="pre_attention",
        router_scoring="topk_softmax", hidden_act="relu", dtype="float32",
        param_dtype="float32", max_position_embeddings=4096)
    toy.update(over)
    return toy


@pytest.fixture(scope="module")
def second():
    cfg = config_from_dict(_toy2())
    params = _seeded(cfg, 2)
    return cfg, params, dict(PUBLISHED2), \
        _reference_weights(params, PUBLISHED2, ref2, SHIPPED2)


def _reference_rows(module, w, sizes, toks):
    row = np.zeros((1, -(-len(toks) // 16) * 16), np.int32)
    row[0, :len(toks)] = toks
    return np.asarray(module.logits(w, sizes, jnp.asarray(row)))[0]


def test_the_second_members_tree_has_no_leaf_for_what_it_lacks(second):
    cfg, params, _, _ = second
    assert set(params) == {"embed", "head", "final_norm", "full_moe",
                           "window_moe"}
    assert cfg.kinds() == {"full_moe": 2, "window_moe": 6}
    for kind in ("full_moe", "window_moe"):
        assert set(params[kind]["attn"]) == {"q", "k", "v", "out"}
        assert set(params[kind]["moe"]) == {
            "router", "experts_gate", "experts_up", "experts_down"}
    assert cfg.rope_parameters["full_attention"] is None
    assert M.rotary_tables(cfg, "full_attention", jnp.arange(4)) is None
    assert M.count_params(cfg) == sum(
        math.prod(x.shape) for x in jax.tree.leaves(params))


@pytest.mark.parametrize("prompt_len,new,chunk", [
    (3, 3, 4),                  # all below the window
    (6, 8, 4),                  # crosses the window mid-decode
    (WINDOW + 5, 30, 4),        # wraps a ring of 12 tokens: no multiple of
                                # the window; prefill folds the ring
    (3 * CHUNK + 1, 20, CHUNK),  # a ring of two key blocks: the fold visits
                                 # both, three on the ragged last chunk
])
def test_second_member_prefill_then_decode_is_the_reference_on_logits(
        second, prompt_len, new, chunk):
    """Prefill in chunks, then decode, through the paged pool (full
    layers, not rotated) and the ring (window layers, rotated): every
    step's LOGITS are the reference's full forward at that position."""
    cfg, params, sizes, w = second
    ring = S.ring_pages(cfg, PAGE, chunk) * PAGE
    assert (ring % WINDOW != 0) == (chunk == 4)
    prompt = np.random.default_rng(prompt_len).integers(
        0, cfg.vocab_size, size=prompt_len).tolist()
    toks, logits, stats, fns = _serve(cfg, params, prompt, new, chunk=chunk)
    want = _reference_rows(ref2, w, sizes, toks)
    for i, got in enumerate(logits):
        np.testing.assert_allclose(got, want[prompt_len - 1 + i], atol=2e-5,
                                   rtol=0)
    # one active row, every expert held: k experts a layer hit, one pass a
    # layer, and the fullest expert's one row over a mean of k / 16
    assert int(stats["rows"]) == 1
    assert float(stats["hit"]) == int(stats["pairs_held"]) == 8 * 3
    assert int(stats["passes"]) == 8
    np.testing.assert_allclose(float(stats["load_max_over_mean"]), 16 / 3,
                               rtol=1e-6)
    assert fns["decode"]._cache_size() == fns["prefill"]._cache_size() == 1


@pytest.mark.parametrize("member", ["toy", "second"])
def test_a_reused_slots_stale_ring_keys_are_masked_by_position(request,
                                                               member):
    """A ring of two key blocks (window = chunk): a long request wraps its
    slot's ring three times and leaves; a shorter one takes the slot, ring
    and pages as they were left. Its prefill folds blocks that still hold
    the first one's keys, at logical positions past its own last token, and
    its decode reads them in the gathered view: every step's logits are the
    reference's, for both members."""
    cfg, params, sizes, w = request.getfixturevalue(member)
    module = ref if member == "toy" else ref2
    rp = S.ring_pages(cfg, PAGE, CHUNK)
    assert rp * PAGE == 2 * CHUNK
    rng = np.random.default_rng(46)
    long = rng.integers(0, cfg.vocab_size, size=3 * CHUNK + 1).tolist()
    short = rng.integers(0, cfg.vocab_size, size=CHUNK + 3).tolist()
    *_, fns, cache = _serve_cache(cfg, params, long, 20)
    for ring in cache[2:]:      # slot 1's ring is full of the first one's
        assert bool(jnp.all(jnp.any(ring[:, 1 + rp:1 + 2 * rp] != 0, -1)))
    toks, logits, _, _ = _serve(cfg, params, short, 9, cache=cache, fns=fns)
    assert fns["decode"]._cache_size() == fns["prefill"]._cache_size() == 1
    want = _reference_rows(module, w, sizes, toks)
    for i, got in enumerate(logits):
        np.testing.assert_allclose(got, want[len(short) - 1 + i], atol=2e-5,
                                   rtol=0)


def _avals(fn, *args):
    """Every value the traced ``fn`` computes, dead code included, in the
    jaxprs its equations hold too: ``[(shape, dtype's name), ...]``."""
    return [(tuple(v.aval.shape), str(v.aval.dtype))
            for eqn in _kernel_body_eqns(jax.make_jaxpr(fn)(*args).jaxpr)
            for v in eqn.outvars if hasattr(v.aval, "shape")]


def _program_args(cfg, *, page, chunk, max_batch=3, max_seq=64):
    """Arguments to trace both programs with, the parameters abstract:
    ``{"prefill", "decode"}``."""
    P, params = max_seq // page, M.served_template(cfg)
    cache = S.init_cache(cfg, num_pages=1 + max_batch * P, page_size=page,
                         max_batch=max_batch, prefill_chunk=chunk)
    i32, key = np.int32(0), jax.random.PRNGKey(0)
    return {
        "prefill": (params, *cache, np.zeros((1, chunk), np.int32),
                    np.zeros((1, P), np.int32), i32, i32, key, np.uint32(0),
                    i32),
        "decode": (params, *cache, np.zeros((max_batch,), np.int32), i32,
                   np.zeros((1,), np.int32),
                   np.zeros((max_batch, P), np.int32),
                   np.zeros((max_batch,), np.int32), key, np.uint32(0))}


def test_no_prefill_scores_a_ring_whole_and_kernel_decode_builds_no_view():
    """The traced programs, so the whole-ring score cannot come back
    unseen. Prefill at a ring of exactly two key blocks (the geometry that
    was scored whole; sizes that no other array's last two dimensions
    share): no float32 value of ``[..., chunk, ring tokens]``, what a score
    of the ring at once would be; the fold's ``[..., chunk, key block]``
    is there. Decode on the paged kernel: no int32 positions of a gathered
    ring ``[slots, ring tokens]`` and no ring gathered ``[slots, ring
    tokens, kv, hd]``; the gathered fallback has both."""
    chunk = window = 20
    cfg = config_from_dict(dict(TOY, sliding_window=window))
    ring = S.ring_pages(cfg, PAGE, chunk) * PAGE
    assert ring == 2 * chunk
    fns = S.make_step_fns(cfg, page_size=PAGE, prefill_chunk=chunk,
                          sampling=SamplingParams())
    args = _program_args(cfg, page=PAGE, chunk=chunk)
    f32 = [shape for shape, dtype in _avals(fns["prefill"], *args["prefill"])
           if dtype == "float32"]
    assert not [s for s in f32 if s[-2:] == (chunk, ring)]
    assert [s for s in f32 if s[-2:] == (chunk, chunk)]

    # the geometry of test_the_kernel_path_serves_what_the_gather_serves
    cfg = config_from_dict(dict(TOY, head_dim=128, num_hidden_layers=5,
                                sliding_window=16))
    page = chunk = 8
    ring = S.ring_pages(cfg, page, chunk) * page
    args = _program_args(cfg, page=page, chunk=chunk)
    view = {((3, ring), "int32"),
            ((3, ring, cfg.num_key_value_heads, cfg.head_dim), "float32")}
    for paged_kernel in (True, False):
        fns = S.make_step_fns(cfg, page_size=page, prefill_chunk=chunk,
                              sampling=SamplingParams(),
                              paged_kernel=paged_kernel)
        seen = set(_avals(fns["decode"], *args["decode"]))
        assert (view & seen) == (set() if paged_kernel else view)


def _router_reads_v(x, lw, sizes_key, windowed, rotated, precision):
    """``smallthinker_ref._layer`` with the router's input SWAPPED: it
    reads the normed state after attention."""
    sizes = ref2._SIZES[sizes_key]
    eps = float(sizes["rms_norm_eps"])
    u = ref2._rms_norm(x, lw["norm_in"], eps)
    h = x + ref2._attention(u, lw, sizes, windowed, rotated, precision)
    v = ref2._rms_norm(h, lw["norm_post"], eps)
    ids, weights = ref2._route(v, lw["router"], sizes)
    return h + ref2._experts(v, ids, weights, lw, precision)


def _silu_glu(v, gate, up, down, precision):
    a = jax.nn.silu(ref2._product("sh,hf->sf", v, gate, precision)) \
        * ref2._product("sh,hf->sf", v, up, precision)
    return ref2._product("sf,fh->sh", a, down, precision)


@pytest.mark.parametrize("departure", [
    "router_reads_the_state_after_attention", "silu_experts",
    "full_layers_rotated", "window_layers_not_rotated"])
def test_a_departure_from_the_second_members_equations_is_seen(
        second, monkeypatch, departure):
    """The comparison above is tight enough to tell the member's equations
    from their neighbours: the program matches the reference as written and
    NOT a reference whose router reads ``v`` instead of ``u``, whose experts
    are SiLU-gated, whose full layers rotate or whose window layers do not
    (each would pass unseen if program and reference shared the mistake:
    the reference shares no code with the program)."""
    cfg, params, sizes, w = second
    prompt = np.random.default_rng(4).integers(0, 64, size=13).tolist()
    toks, logits, _, _ = _serve(cfg, params, prompt, 6, chunk=4)
    got = np.stack(logits)
    at = np.arange(len(prompt) - 1, len(toks) - 1)
    right = _reference_rows(ref2, w, sizes, toks)[at]
    assert np.abs(got - right).max() < 2e-5
    wrong_sizes = dict(sizes)
    if departure == "router_reads_the_state_after_attention":
        monkeypatch.setattr(ref2, "_layer", _router_reads_v)
    elif departure == "silu_experts":
        monkeypatch.setattr(ref2, "_reglu", _silu_glu)
    elif departure == "full_layers_rotated":
        wrong_sizes["rope_layout"] = [1] * 8
    else:
        wrong_sizes["rope_layout"] = [0] * 8
    ref2._jitted_layer.cache_clear()
    try:
        wrong = _reference_rows(ref2, w, wrong_sizes, toks)[at]
    finally:
        monkeypatch.undo()
        ref2._jitted_layer.cache_clear()
    assert np.abs(got - wrong).max() > 1e-2, departure


def test_topk_then_softmax_in_float32(second):
    """``topk_softmax``: the k largest LOGITS, a softmax over those alone,
    no scaling. (With ``norm_topk_prob`` the first member's scoring — a
    softmax over all, the chosen over their sum — is the same function up
    to rounding; the member states its own and a test holds it.)"""
    cfg = second[0]
    rng = np.random.default_rng(9)
    u = jnp.asarray(rng.normal(size=(7, 32)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(32, 16)), jnp.float32)
    ids, weights = M.route(u, router, cfg)
    z = np.asarray(u, np.float64) @ np.asarray(router, np.float64)
    want_ids = np.argsort(-z, axis=-1)[:, :3]
    np.testing.assert_array_equal(np.asarray(ids), want_ids)
    top = np.take_along_axis(z, want_ids, axis=-1)
    want = np.exp(top - top.max(-1, keepdims=True))
    want /= want.sum(-1, keepdims=True)
    np.testing.assert_allclose(np.asarray(weights), want, atol=1e-6)
    np.testing.assert_allclose(np.asarray(weights).sum(-1), 1.0, atol=1e-6)
    other = config_from_dict(_toy2(router_scoring="softmax_topk"))
    ids2, weights2 = M.route(u, router, other)
    np.testing.assert_array_equal(np.asarray(ids2), want_ids)
    np.testing.assert_allclose(np.asarray(weights2), want, atol=1e-6)


def test_four_shares_of_16_add_up_to_the_uncut_reference_layer():
    """All 64 experts held as four shares of 16 (the cut this member's
    cell does NOT make; the layer is the one a share of a wider deployment
    would run): the shares' routed parts add up to the uncut reference's
    expert layer, the choice made once from ``u`` for all of them."""
    published = dict(PUBLISHED2, moe_num_primary_experts=64,
                     moe_num_active_primary_experts=6)
    rng = np.random.default_rng(13)
    h, f = 32, 24
    u = jnp.asarray(rng.normal(size=(11, h)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(11, h)), jnp.float32)
    full = {name: jnp.asarray(0.3 * rng.normal(size=shape), jnp.float32)
            for name, shape in {"router": (h, 64), "e_gate": (64, h, f),
                                "e_up": (64, h, f),
                                "e_down": (64, f, h)}.items()}
    ids, weights = ref2._route(u, full["router"], published)
    want = np.asarray(ref2._experts(v, ids, weights, full, "float32"))
    total = np.zeros_like(want)
    for share in range(4):
        lo = 16 * share
        held = config_from_dict(_toy2(published, experts_held=16,
                                      first_expert_held=lo))
        got_ids, got_w = M.route(u, full["router"], held)
        np.testing.assert_array_equal(np.asarray(got_ids), np.asarray(ids))
        moe = {"experts_gate": full["e_gate"][None, lo:lo + 16],
               "experts_up": full["e_up"][None, lo:lo + 16],
               "experts_down": full["e_down"][None, lo:lo + 16]}
        routed, rows, _ = M.held_experts(
            v, got_ids, got_w, moe, jnp.int32(0), held,
            M.pass_rows(held, 11), "moe_gmm")
        assert int(rows.sum()) == int(((ids >= lo) & (ids < lo + 16)).sum())
        total += np.asarray(routed)
    np.testing.assert_allclose(total, want, atol=2e-5, rtol=0)


def test_the_second_members_built_tree_is_3967_m_parameters():
    from fleetx_tpu.utils import config as config_mod

    cfg = config_mod.get_config(
        os.path.join(ROOT, SHIPPED2["serve"]["recipe"]),
        list(SHIPPED2["serve"]["overrides"]), num_devices=1)
    model_cfg, template = registry.served_template(cfg)
    leaves = jax.tree_util.tree_flatten_with_path(template)[0]
    assert sum(math.prod(l.shape) for _, l in leaves) == 3_966_937_600 \
        == SHIPPED2["bytes"]["parameters"] == M.count_params(model_cfg)
    assert sum(math.prod(l.shape) * l.dtype.itemsize for _, l in leaves) \
        == SHIPPED2["bytes"]["served_bytes"]
    # a layer: attention, router, 64 experts, two norms
    assert 3_966_937_600 == 8 * (20_971_520 + 163_840 + 64 * 5_898_240
                                 + 5_120) + 2 * 151_936 * 2_560 + 2_560
    for path, leaf in leaves:
        keys = {getattr(k, "key", None) for k in path}
        f32 = bool(keys & (M.F32_GROUPS | M.F32_LEAVES))
        assert leaf.dtype == (jnp.float32 if f32 else jnp.bfloat16), path
    # the reference's spec, from the PUBLISHED keys, is the same tree
    spec = ref2.weight_spec(SHIPPED2)
    assert set(spec) == set(SHIPPED2["param_paths"])
    assert sum(math.prod(s) for s, _ in spec.values()) == 3_966_937_600
    by_name = {"/".join(str(k.key) for k in path): leaf
               for path, leaf in leaves}
    for name, (shape, _) in spec.items():
        assert by_name[SHIPPED2["param_paths"][name]].shape == tuple(shape)
    assert model_cfg.runs() == [
        ("full_moe", 0, 1, 0), ("window_moe", 0, 3, 0),
        ("full_moe", 1, 1, 1), ("window_moe", 3, 3, 3)]
    sc = cfg["Serving"]
    assert S.ring_pages(model_cfg, sc["page_size"],
                        sc["prefill_chunk"]) == 288
    full, ring = S.cache_shapes(
        model_cfg, num_pages=sc["num_pages"], page_size=sc["page_size"],
        max_batch=sc["max_batch"], prefill_chunk=sc["prefill_chunk"])
    assert full == (2, 28001, 16, 512) and ring == (6, 13825, 16, 512)
    # one chip holds the whole expert set: nothing masked, 288 pairs a
    # decode step over 64 experts in one pass of 64 tiles
    assert (model_cfg.experts_held, model_cfg.first_expert_held) == (64, 0)
    assert (M.tile_rows(model_cfg), M.pass_rows(model_cfg, 48),
            M.pass_rows(model_cfg, 512)) == (16, 1024, 4096)
    # group 7: the kernel takes all 4 key-value heads, 28 query rows, in
    # one block. A page of the 512-lane pool is 16 KB, so a fold of the
    # full layers takes 32 pages (512 keys: 512 KB a pool, PR 43) and one
    # of a ring, fetched in one copy a buffer, as many: a row's
    # 4,096-token window is walked in 9 folds at most (33 of 8 pages
    # through a block table)
    from fleetx_tpu.ops import paged_attention as PA

    per_req = -(-sc["max_seq_len"] // sc["page_size"])
    assert S.kernel_refusal(model_cfg, page_size=16,
                            pages_per_req=per_req) == ""
    assert PA.pick_head_block(4, 128, jnp.bfloat16) == 4
    geometry = dict(num_heads=28, head_dim=128, page_size=16,
                    dtype=jnp.bfloat16, num_kv_heads=4)
    span, folds = PA.page_walk_shape(pages_per_req=per_req, **geometry)
    assert (span, folds) == (512, 26)
    ring_span = 16 * PA.pick_pages_per_step(
        pages_per_req=288, ring_pages=288, **geometry)
    assert ring_span == 512
    lens = np.arange(4096, 13056, 37, dtype=np.int32)
    assert PA.page_groups_walked(lens, ring_span, None, 4096).max() == 9
    assert PA.page_groups_walked(lens, 128, -(-per_req // 8),
                                 4096).max() == 33


@pytest.mark.parametrize("routing", ["uniform", "skewed", "one_tile_each"])
def test_a_chunks_sorted_rows_fit_one_pass_at_the_second_members_sizes(
        routing):
    """What ``pass_rows``' half tile buys, by count: the recipe's chunk
    sends 512 x 6 pairs to 64 experts in 16-row tiles, whose padded runs
    are at most 3,072 + 64 x 15 = 4,032 rows WHATEVER the routing, so the
    pass of 64 x 64 rows takes one turn of ``held_experts``' loop; the
    uniform share alone (64 x 48, no room for the padding) takes a second
    turn unless every expert's count is a whole number of tiles."""
    from fleetx_tpu.models.mla_moe import moe as held_share
    from fleetx_tpu.utils import config as config_mod

    cfg = registry.model_config(config_mod.get_config(
        os.path.join(ROOT, SHIPPED2["serve"]["recipe"]),
        list(SHIPPED2["serve"]["overrides"]), num_devices=1))
    n, k, held, tile = 512, cfg.num_experts_per_tok, cfg.experts_held, 16
    rng = np.random.default_rng(38)
    if routing == "uniform":
        ids = np.stack([rng.permutation(held)[:k] for _ in range(n)])
    elif routing == "skewed":       # a few experts draw most of the rows
        p = np.exp(-np.arange(held) / 6.0)
        ids = np.stack([rng.choice(held, k, replace=False, p=p / p.sum())
                        for _ in range(n)])
    else:                           # 48 rows each: three whole tiles
        ids = (np.arange(n * k) % held).reshape(n, k)
    shipped = M.pass_rows(cfg, n)
    bare = held * -(-(n * k // held) // tile) * tile
    assert (shipped, bare) == (4096, 3072)
    assert n * k + held * (tile - 1) <= shipped
    turns = {rows: int(held_share.plan_rows(
        jnp.asarray(ids, jnp.int32), 0, held, tile, rows)["n_passes"])
        for rows in (shipped, bare)}
    assert turns[shipped] == 1
    assert turns[bare] == (1 if routing == "one_tile_each" else 2)


@pytest.mark.parametrize("missing", [
    "moe_routed_scaling_factor", "shared_expert_intermediate_size",
    "mlp_only_layers", "sliding_window", "num_key_value_heads", "gating",
    "router_input", "router_scoring", "hidden_act", "rope_parameters"])
def test_a_recipe_that_omits_a_member_key_is_refused_by_name(missing):
    """On the way from a recipe no key on which the members differ takes a
    default: one model's number is never lent to another."""
    from fleetx_tpu.models.swa_moe.config import MEMBER_KEYS

    assert missing in MEMBER_KEYS and len(MEMBER_KEYS) == 10
    for toy in (TOY, _toy2()):
        assert config_from_dict(toy)
        short = {k: v for k, v in toy.items() if k != missing}
        with pytest.raises(ValueError, match=missing):
            config_from_dict(short)
    with pytest.raises(ValueError, match="sliding_attention"):
        config_from_dict(dict(_toy2(), rope_parameters={
            "full_attention": "none"}))


def test_the_shipped_recipes_state_every_member_key():
    import yaml

    from fleetx_tpu.models.swa_moe.config import MEMBER_KEYS

    for recipe in (SHIPPED["serve"]["recipe"], SHIPPED2["serve"]["recipe"]):
        with open(os.path.join(ROOT, recipe)) as f:
            model = yaml.safe_load(f)["Model"]
        assert not [k for k in MEMBER_KEYS if model.get(k) is None], recipe


def test_a_gather_fallback_is_said_once_when_the_engine_is_built(second):
    """At toy widths ``ops/paged_attention.py`` does not admit the
    geometry: the engine's build names each layer kind and the bound that
    refused it, once; at a geometry the kernel admits it says nothing."""
    import logging

    from fleetx_tpu.utils.log import logger as program_logger

    cfg, params, _, _ = second
    said = []
    handler = logging.Handler()
    handler.emit = lambda record: said.append(record.getMessage())
    program_logger.addHandler(handler)
    try:
        eng = _engine(cfg, params)
        wide = config_from_dict(_toy2(dict(PUBLISHED2, head_dim=128)))
        ServingEngine(wide, _seeded(wide, 3), ServingConfig(
            max_batch=2, page_size=8, num_pages=20, max_seq_len=64,
            prefill_chunk=8, max_queue=0), SamplingParams(), eos_token_id=-1)
    finally:
        program_logger.removeHandler(handler)
    lines = [s for s in said if "falls back to the gathered view" in s]
    assert len(lines) == 1 and not eng.paged_kernel_active
    assert "full_moe layers: head_dim 16 is neither whole 128-lane tiles " \
        "nor half of one" in lines[0] and "window_moe layers: " in lines[0]
    for _ in range(3):
        eng.submit([1, 2, 3], 2)
    eng.run_until_drained()
    assert len([s for s in said if "falls back" in s]) == 1


def test_the_fold_gauges_say_how_each_cache_is_fetched(second):
    """Set once, when the engine is built, a cache kind each: the pages a
    fold of the decode kernel takes and the copies a buffer that fetch
    them — a copy a page through the request's block table, ONE for a
    ring's run of pages; on the build's log line and in
    ``serving_snapshot()``; 0 and no entry on the gathered view."""
    import logging

    from fleetx_tpu.observability import schema
    from fleetx_tpu.utils.log import logger as program_logger

    said = []
    handler = logging.Handler()
    handler.emit = lambda record: said.append(record.getMessage())
    program_logger.addHandler(handler)
    try:
        wide = config_from_dict(_toy2(dict(PUBLISHED2, head_dim=128)))
        eng = ServingEngine(wide, _seeded(wide, 3), ServingConfig(
            max_batch=2, page_size=8, num_pages=20, max_seq_len=64,
            prefill_chunk=8, max_queue=0), SamplingParams(), eos_token_id=-1)
    finally:
        program_logger.removeHandler(handler)
    assert eng.paged_kernel_active
    # 8 pages a request; a ring of (window 8 + chunk 8) / 8 = 2 pages
    want = {"full": [8, 8], "window": [2, 1]}
    assert eng.serving_snapshot()["kv_folds"] == want
    assert not schema.validate_serving_record(eng.serving_snapshot())
    for kind, (pages, copies) in want.items():
        assert eng.metrics.gauge(f"serving_kv_fold_pages_{kind}").value \
            == pages
        assert eng.metrics.gauge(f"serving_kv_fold_copies_{kind}").value \
            == copies
    line = next(s for s in said if s.startswith("serving engine:"))
    assert "full cache 8 pages a fold in 8 copies a buffer" in line and \
        "window cache 2 pages a fold in 1 copy a buffer" in line
    req = eng.submit(list(range(1, 30)), 20)
    eng.run_until_drained()
    assert req.state == "finished" and len(req.tokens) == 20
    cfg, params, _, _ = second          # toy widths: the gathered view
    gathered = _engine(cfg, params)
    assert gathered.serving_snapshot()["kv_folds"] == {}
    for name in ("pages_full", "copies_full", "pages_window",
                 "copies_window"):
        assert gathered.metrics.gauge(f"serving_kv_fold_{name}").value == 0
        assert f"serving_kv_fold_{name}" in schema.SERVING_METRIC_NAMES
    with open(os.path.join(ROOT, "docs/observability.md")) as f:
        assert "serving_kv_fold_copies_window" in f.read()


def test_the_new_counters_ride_with_the_tokens_and_add_no_span(second):
    """``serving_moe_load_max_over_mean`` and ``serving_moe_passes_total``
    come out of the decode program with the tokens (no span, no extra
    fetch: the tick's span table is the one it was) and are in
    ``serving_snapshot()``."""
    from fleetx_tpu.observability import schema
    from fleetx_tpu.observability.trace import HOT_LOOP_SPANS

    assert sorted(HOT_LOOP_SPANS) == [
        "data_fetch", "fit.fetch_metrics", "fit.log", "sdc_sentinel",
        "serve.admit", "serve.decode", "serve.decode.wait", "serve.emit",
        "serve.gauges", "serve.prefill", "serve.prefill.wait",
        "serve.schedule", "serve.tick", "shard_batch", "train_step"]
    cfg, params, _, _ = second
    eng = _engine(cfg, params)
    eng.reset_stats()
    snap = eng.serving_snapshot()
    assert snap["serving_moe_load_max_over_mean"] is None
    assert snap["serving_moe_passes_total"] == 0
    reqs = [eng.submit(p, 9) for p in ([1, 2, 3, 4, 5], [7] * 11)]
    eng.run_until_drained()
    assert all(r.state == "finished" for r in reqs)
    m, snap = eng.metrics, eng.serving_snapshot()
    steps = m.counter("serving_decode_steps").value
    assert m.histogram("serving_moe_load_max_over_mean").total_count == steps
    assert snap["serving_moe_passes_total"] == \
        m.counter("serving_moe_passes_total").value == 8 * steps
    # one or two rows of 3 experts each over 16 experts: 16/3 or 16/6 .. 16/3
    assert 16 / 6 - 1e-6 <= snap["serving_moe_load_max_over_mean"] \
        <= 16 / 3 + 1e-6
    schema.validate_serving_record(snap)
    for name in ("serving_moe_load_max_over_mean",
                 "serving_moe_passes_total"):
        assert name in schema.SERVING_METRIC_NAMES
    with open(os.path.join(ROOT, "docs/observability.md")) as f:
        text = f.read()
    assert "serving_moe_load_max_over_mean" in text and \
        "serving_moe_passes_total" in text
