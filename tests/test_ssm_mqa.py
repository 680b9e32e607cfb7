"""The scan / multi-query family (``models/ssm_mqa``, ``serving/ssm_mqa.py``,
the shared ``models/scan_mixer.py`` and ``serving/programs.py:scan_mixer``)
against its plain reference (``benchmarks/reference/jamba2_ref.py``), at toy
widths on the CPU.

Weights are seeded float32 (the benchmark's own ``weights.make``), so
program and reference differ by the order of float32 sums alone — and by
the form: the program scans a chunk from the slot's state, convolves it from
the slot's tail and folds the request's pages a key block at a time; the
reference scans, shifts and scores the whole sequence. Logits (standard
deviation ~0.17, largest ~1.2) are held to 2e-5 — inside the 1e-4 ISSUE 51
asks for: float32's grain through eight layers (the whole-sequence forward
reads 6e-7).
"""

import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import ssm_mqa_toy as toy  # noqa: E402
from benchmarks import check, weights  # noqa: E402
from benchmarks.manifest import load_module  # noqa: E402
from fleetx_tpu.models import scan_mixer  # noqa: E402
from fleetx_tpu.models.samba_y import model as samba_model  # noqa: E402
from fleetx_tpu.models.ssm_mqa import model as M  # noqa: E402
from fleetx_tpu.models.ssm_mqa.config import (PUBLISHED_KEYS,  # noqa: E402
                                              config_from_dict)
from fleetx_tpu.observability import schema  # noqa: E402
from fleetx_tpu.ops import paged_attention as PA  # noqa: E402
from fleetx_tpu.serving import programs, registry  # noqa: E402
from fleetx_tpu.serving import ssm_mqa as S  # noqa: E402
from fleetx_tpu.serving.decode import SamplingParams  # noqa: E402
from fleetx_tpu.serving.engine import (ServingConfig,  # noqa: E402
                                       ServingEngine)

ROOT = toy.ROOT
ref = load_module(os.path.join(ROOT, "benchmarks/reference/jamba2_ref.py"))
family = load_module(os.path.join(ROOT, "benchmarks/families/SSMMQAModule.py"))
with open(os.path.join(ROOT, "benchmarks/configs/ai21-jamba2-3b.json")) as _f:
    SHIPPED = json.load(_f)
CHUNK, PAGE, ATOL = 8, 4, 2e-5
WIDER = {"hidden_size": 128, "intermediate_size": 256}


def _built(seed=7, **widths):
    """``(model config, program tree, reference weights, sizes)``: the same
    seeded numbers on both sides, through ``param_paths``."""
    sizes = toy.sizes(**widths)
    spec = ref.weight_spec(sizes)
    w = weights.make(spec, seed)
    cfg = config_from_dict(toy.model_section(**widths))
    params = family.seeded(weights.to_program_tree(
        w, toy.param_paths(spec), M.served_template(cfg)))
    return cfg, params, w, sizes


@pytest.fixture(scope="module")
def built():
    return _built()


_FNS: dict = {}
_forward = jax.jit(M.forward, static_argnums=1)


def _fns(cfg, kernels):
    """One pair of programs a (config, path): a compile is most of a test."""
    key = (id(cfg), kernels)
    if key not in _FNS:
        _FNS[key] = (cfg, S.make_step_fns(
            cfg, prefill_chunk=CHUNK, sampling=SamplingParams(),
            kernels=kernels))
    return _FNS[key][1]


def _serve(cfg, params, prompt, new, *, slot=1, max_batch=3, kernels=False,
           max_seq=96, cache=None):
    """Prefill ``prompt`` in chunks, decode ``new`` tokens greedily, in slot
    ``slot`` of an otherwise empty batch: ``(tokens, logits a step,
    cache)``."""
    P = max_seq // PAGE
    fns = _fns(cfg, kernels)
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


@pytest.mark.parametrize("prompt_len", [
    5,      # shorter than a chunk
    13,     # a ragged last chunk of 5
    17,     # a last chunk of ONE token: shorter than the tail
    24,     # whole chunks
])
def test_prefill_then_decode_through_every_cache_is_the_reference_on_logits(
        built, prompt_len):
    """Chunked prefill (pool, states, tails), then decode through all of
    them, a token at a time, on the gathered paths: the logits of the
    prompt's last position and of every decoded one are the reference's
    full forward pass."""
    cfg, params, w, sizes = built
    prompt = _prompt(prompt_len)
    toks, logits, _ = _serve(cfg, params, prompt, 12)
    want = _reference_rows(w, sizes, toks)
    for i, got in enumerate(logits):
        np.testing.assert_allclose(got, want[prompt_len - 1 + i], atol=ATOL,
                                   err_msg=f"position {prompt_len - 1 + i}")


def test_the_kernels_serve_the_reference_on_logits():
    """The same through the Pallas kernels (interpreted), at widths they
    admit: ``ssm_chunk`` and ``ssm_decode`` for the scan, ``paged_decode``
    with every query head in one block over ONE key-value head of 128
    (``tests/test_tpu_lowering.py`` finds them by name in the programs
    compiled for the chip)."""
    cfg, params, w, sizes = _built(**toy.KERNEL_WIDTHS)
    assert cfg.head_dim == 128 and cfg.kv_lanes == 128
    assert not S.kernel_refusal(cfg, page_size=PAGE, pages_per_req=24,
                                prefill_chunk=CHUNK, max_batch=3)
    prompt = _prompt(13)
    toks, logits, _ = _serve(cfg, params, prompt, 3, kernels=True)
    want = _reference_rows(w, sizes, toks)
    for i, got in enumerate(logits):
        np.testing.assert_allclose(got, want[12 + i], atol=ATOL)


def test_a_reused_slot_starts_from_zero(built):
    """A slot's state and tail are whatever the last request left: a
    request's first chunk reads zeros in their place."""
    cfg, params, w, sizes = built
    _, _, cache = _serve(cfg, params, _prompt(21, seed=3), 5)
    assert float(jnp.abs(cache[2][:, 1]).max()) > 0     # the slot's states
    assert float(jnp.abs(cache[3][:, :, 1]).max()) > 0  # ... and tails
    prompt = _prompt(11, seed=4)
    toks, logits, _ = _serve(cfg, params, prompt, 4, cache=cache)
    want = _reference_rows(w, sizes, toks)
    for i, got in enumerate(logits):
        np.testing.assert_allclose(got, want[10 + i], atol=ATOL)


# ------------------------------------------------------ multi-query attention
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-6),
                                       (jnp.bfloat16, 2e-2)])
def test_twenty_heads_over_one_through_the_paged_kernel_are_the_gathered_view(
        dtype, tol):
    """The recipe's head geometry — 20 query heads in ONE block over one
    key-value head of 128, a pool one lane tile wide — through the kernel
    (interpreted) against ``programs.gathered_attention`` on the same
    keys, at the two page sizes a fold of which is several pages and one."""
    B, H, hd = 3, 20, 128
    assert PA.pick_head_block(1, hd, dtype) == 1
    for ps, P in ((8, 6), (32, 2)):
        k = jax.random.split(jax.random.PRNGKey(ps), 3)
        q = jax.random.normal(k[0], (B, H, hd)).astype(dtype)
        pool_k = jax.random.normal(k[1], (2, 1 + B * P, ps, hd)).astype(dtype)
        pool_v = jax.random.normal(k[2], (2, 1 + B * P, ps, hd)).astype(dtype)
        assert not PA.paged_attention_refusal(
            num_heads=H, head_dim=hd, page_size=ps, pages_per_req=P,
            dtype=dtype, num_kv_heads=1)
        tables = 1 + np.arange(B * P, dtype=np.int32).reshape(B, P)
        lens = np.array([ps * P - 3, -1, 7], np.int32)
        got = PA.paged_attention(q, pool_k, pool_v, tables, lens,
                                 jnp.int32(1))
        kd = pool_k[1, tables].reshape(B, -1, 1, hd)
        vd = pool_v[1, tables].reshape(B, -1, 1, hd)
        kp = np.broadcast_to(np.arange(P * ps, dtype=np.int32), (B, P * ps))
        want = programs.gathered_attention(
            q[:, None], kd, vd, kp, np.maximum(lens, 0)[:, None], None,
            dtype)[:, 0]
        live = lens >= 0
        np.testing.assert_allclose(
            np.asarray(got, np.float32)[live],
            np.asarray(want, np.float32)[live], atol=tol)


# --------------------------------------------- the scan mixer is one definition
def test_the_scan_mixer_is_one_definition_for_both_families(built):
    """``models/scan_mixer.py`` holds the mixer ONCE: the fifth family's
    model hands out the same function objects, ``softplus`` of the step
    stands in one module of ``fleetx_tpu/models/``, both serving modules
    call ``programs.scan_mixer`` — and with the inner norms' leaves absent a
    layer is the sixth family's with the norm's division undone (unit
    weights on parts scaled to unit RMS)."""
    for name in ("ssm_in", "conv_act", "ssm_params", "ssm_decay", "ssm_out"):
        assert getattr(samba_model, name) is getattr(scan_mixer, name), name
    models = os.path.join(ROOT, "fleetx_tpu", "models")
    holders = []
    for folder, _, files in os.walk(models):
        for fn in files:
            if fn.endswith(".py"):
                with open(os.path.join(folder, fn)) as f:
                    # (the linear-attention family's decay has a softplus
                    # of its own: another mechanism)
                    if "jax.nn.softplus(delta" in f.read():
                        holders.append(os.path.relpath(
                            os.path.join(folder, fn), models))
    assert holders == ["scan_mixer.py"], holders
    for package in ("samba_y", "ssm_mqa"):
        with open(os.path.join(models, package, "model.py")) as f:
            assert "jax.nn.softplus(" not in f.read(), package
    for module in ("samba_y", "ssm_mqa"):
        with open(os.path.join(ROOT, "fleetx_tpu", "serving",
                               module + ".py")) as f:
            text = f.read()
        assert "programs.scan_mixer(" in text, module
        assert not re.search(r"SS\.scan_(step|chunk)\(", text), module
    cfg, params, _, _ = built
    lp = jax.tree.map(lambda v: v[0], params["scan"]["ssm"])
    xc = jax.random.normal(jax.random.PRNGKey(1), (6, cfg.d_inner))
    r, n = cfg.dt_rank, cfg.d_state
    normed = scan_mixer.ssm_params(xc, dict(
        lp, dt_norm=jnp.ones(r), b_norm=jnp.ones(n), c_norm=jnp.ones(n)),
        cfg, 0.0)
    plain_lp = {k: v for k, v in lp.items() if not k.endswith("_norm")}
    delta, b, c = scan_mixer.ssm_params(xc, plain_lp, cfg)
    rms = lambda v: jnp.sqrt(jnp.square(v).mean(-1, keepdims=True))  # noqa: E731,E501
    np.testing.assert_allclose(normed[1], b / rms(b), rtol=1e-5)
    np.testing.assert_allclose(normed[2], c / rms(c), rtol=1e-5)
    dbc = xc @ lp["x"]
    step = dbc[:, :r] / rms(dbc[:, :r])
    np.testing.assert_allclose(
        normed[0], jax.nn.softplus(step @ lp["dt"] + lp["dt_bias"]),
        rtol=1e-5, atol=1e-6)
    assert float(jnp.abs(normed[0] - delta).max()) > 1e-3


# ----------------------------------------------------------------- the engine
def _engine(cfg, params, **serving):
    sc = ServingConfig(**{**dict(max_batch=3, page_size=PAGE, num_pages=60,
                                 max_seq_len=96, prefill_chunk=CHUNK,
                                 max_queue=0, paged_kernel=False), **serving})
    return ServingEngine(cfg, params, sc, SamplingParams(), eos_token_id=-1)


def _widest_gap(w, sizes, prompt, served) -> float:
    toks = list(prompt) + list(served)
    lg = _reference_rows(w, sizes, toks)
    at = np.arange(len(prompt) - 1, len(toks) - 1)
    return float((lg[at].max(-1) - lg[at, np.asarray(served)]).max())


def test_the_engine_serves_the_family(built):
    """Requests join and leave ONE engine (the same class, scheduler and
    allocator as every family's) while others are mid-prefill — a decode
    step must leave a prefilling slot's state and tail alone —; every
    served token is the reference's best within float32's grain; each
    program compiled once; the build's line, the gauges and the snapshot
    name the three caches."""
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
    assert line and "2 attention layers paged (16 lanes a token, pages of " \
        "4), 6 scan layers a state of 8 x 128 and a tail of 3 rows a slot" \
        in line[-1]
    assert eng.family is registry.family("SSMMQAModule")
    assert len(eng.cache) == S.CACHES and not eng.paged_kernel_active
    eng.reset_stats()
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
    state = int(eng.cache[2].nbytes + eng.cache[3].nbytes)
    assert state == 6 * 3 * 8 * 128 * 4 + 6 * 3 * 3 * 128 * 4
    assert m.gauge("serving_state_cache_bytes").value == state \
        == snap["serving_state_cache_bytes"]
    assert m.gauge("serving_kv_cache_bytes").value == eng.cache_bytes
    assert m.gauge("serving_latent_cache_bytes").value == 0
    assert m.gauge("serving_kv_fold_pages_full").value == 0     # gathered
    assert not schema.validate_serving_record(snap)
    assert eng.family.kv_tokens(cfg, np.array([5, -1, 30])) == (35, 0)
    # a slot's state and tail follow the slots, never max_seq_len
    longer = _engine(cfg, params, max_seq_len=192, num_pages=120)
    assert longer.metrics.gauge("serving_state_cache_bytes").value == state


def test_a_preempted_request_rebuilds_state_and_tail_whole(built):
    """A pool too small for three growing requests preempts the youngest:
    its pages are freed, its state and tail are whatever they are; it is
    prefilled again from its first token (which rebuilds both from zero)
    and serves the tokens an unpressed engine serves — each the
    reference's best."""
    cfg, params, w, sizes = built
    prompts = [_prompt(n, seed=11 + n) for n in (9, 10, 11)]

    def run(num_pages):
        eng = _engine(cfg, params)
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
    from fleetx_tpu.observability.metrics import get_registry

    get_registry().counter("serving_requests_preempted").reset()


def test_the_kernels_geometry_names_its_fold_to_the_engine():
    """At widths the kernels admit the engine's build sets the fold gauges
    from the pool's geometry: a pool one lane tile wide takes as many pages
    a fold as move 512 KB, or as a request has."""
    cfg = config_from_dict(toy.model_section(**toy.KERNEL_WIDTHS))
    fam = registry.family("SSMMQAModule")
    sc = ServingConfig(max_batch=2, page_size=16, num_pages=40,
                       max_seq_len=256, prefill_chunk=CHUNK, max_queue=0)
    got = fam.programs(cfg, sc, SamplingParams(), None, 16)
    assert got.paged_kernel_active and got.kv_folds == {"full": (16, 16)}
    assert got.kernel.walk_shape == (256, 1)


def test_a_mesh_and_quantization_are_refused_with_a_sentence(built):
    from jax.sharding import Mesh

    cfg, _, _, _ = built
    mesh = Mesh(np.array(jax.devices()[:2]).reshape(1, 1, 2, 1, 1),
                ("data", "fsdp", "tensor", "seq", "pipe"))
    sc = dict(max_batch=2, page_size=PAGE, num_pages=20, max_seq_len=32,
              prefill_chunk=CHUNK)
    fam = registry.family("SSMMQAModule")
    with pytest.raises(AssertionError, match="serves on one chip: its "
                       "programs place none of its three caches"):
        fam.programs(cfg, ServingConfig(**sc), SamplingParams(), mesh, 8)
    with pytest.raises(AssertionError, match="quantized decode is not "
                       "written for SSMMQAFamily"):
        fam.programs(cfg, ServingConfig(quantize_decode=True, **sc),
                     SamplingParams(), None, 8)
    with pytest.raises(AssertionError, match="quantized decode"):
        fam.model_config(toy.model_section(), {"weight_bits": 8})


# ------------------------------------------------------------ what is assumed
S_ssm_in, S_ssm_params = scan_mixer.ssm_in, scan_mixer.ssm_params


def _bc_swapped(xc, lp, cfg, eps=0.0):
    delta, b, c = S_ssm_params(xc, lp, cfg, eps)
    return delta, c, b


def _no_inner_norms(xc, lp, cfg, eps=0.0):
    return S_ssm_params(xc, {k: v for k, v in lp.items()
                             if not k.endswith("_norm")}, cfg, eps)


#: each reading the comparison has to tell from the one taken
OTHER_READINGS = {
    "the in-projection read as z, x": (
        scan_mixer, "ssm_in", lambda u, lp: S_ssm_in(u, lp)[::-1]),
    "the x-projection read as delta, C, B": (
        scan_mixer, "ssm_params", _bc_swapped),
    "no norm on the step, B and C": (
        scan_mixer, "ssm_params", _no_inner_norms),
    "the MLP read as up, gate": (
        M, "gated_mlp", lambda f, gate, up, down: M.shared.gated_mlp(
            f, up, gate, down)),
}


@pytest.mark.parametrize("reading", sorted(OTHER_READINGS))
def test_the_comparison_sees_each_other_reading(built, monkeypatch, reading):
    """With one reading taken otherwise in the program its logits leave the
    reference's by far more than the sound program's 2e-5."""
    cfg, params, w, sizes = built
    where, name, other = OTHER_READINGS[reading]
    monkeypatch.setattr(where, name, other)
    toks = _prompt(24)
    # (a fresh function a case: ``jit`` keeps its trace by the function)
    got = np.asarray(jax.jit(lambda p, t: M.forward(p, cfg, t))(
        params, jnp.asarray(toks)))
    want = _reference_rows(w, sizes, toks)[:len(toks)]
    assert float(np.abs(got - want).max()) > 50 * ATOL, reading


def test_the_layer_map_is_the_two_keys(built):
    """Layers ``l mod period == offset`` attend (ASSUMED: the ``jamba``
    convention); another offset is another model, and the comparison sees
    it."""
    cfg, params, w, sizes = built
    assert [cfg.kind_of(l) for l in range(8)] == [
        "scan", "full", "scan", "scan", "scan", "full", "scan", "scan"]
    assert cfg.runs() == [("scan", 0, 1, 0), ("full", 0, 1, 0),
                          ("scan", 1, 3, 1), ("full", 1, 1, 1),
                          ("scan", 4, 2, 4)]
    real = config_from_dict({**toy.model_section(), **{
        k: SHIPPED[k] for k in ("num_hidden_layers", "attn_layer_period",
                                "attn_layer_offset")}})
    assert [l for l in range(28) if real.kind_of(l) == "full"] == [7, 21]
    assert [(k, n) for k, _, n, _ in real.runs()] == [
        ("scan", 7), ("full", 1), ("scan", 13), ("full", 1), ("scan", 6)]
    toks = _prompt(24)
    want = _reference_rows(w, toy.sizes(attn_layer_offset=2), toks)[:24]
    got = np.asarray(_forward(params, cfg, jnp.asarray(toks)))
    assert float(np.abs(got - want).max()) > 50 * ATOL
    with pytest.raises(AssertionError, match="sparse feed-forward"):
        config_from_dict(toy.model_section(num_experts=4))
    with pytest.raises(AssertionError, match="window"):
        config_from_dict(toy.model_section(sliding_window=512))


def test_the_one_scaled_leaf_is_scaled_alike_on_both_sides(built):
    """The family file and the reference each hold the seeded draw of the
    weight of the norm on the scan's ``C`` at a power of two (exact in
    float32, the leaf's served dtype) by a table of their own: the two
    tables name the same leaf with the same exponent through the
    configuration's ``param_paths``, the program's leaf is the harness's
    times it, and without it the comparison fails."""
    cfg, params, w, sizes = built
    paths = SHIPPED["param_paths"]
    assert {paths[n]: e for n, e in ref.WEIGHT_SCALE_LOG2.items()} \
        == family.WEIGHT_SCALE_LOG2 == {"scan/ssm/c_norm": -4}
    assert "at a sixteenth" in SHIPPED["assumed"]["weights"]
    np.testing.assert_array_equal(
        np.asarray(params["scan"]["ssm"]["c_norm"]),
        np.asarray(w["sc_c_norm_w"]) / 16)
    toks = _prompt(24)
    unscaled = dict(params, scan=dict(params["scan"], ssm=dict(
        params["scan"]["ssm"], c_norm=w["sc_c_norm_w"])))
    got = np.asarray(_forward(unscaled, cfg, jnp.asarray(toks)))
    want = _reference_rows(w, sizes, toks)[:len(toks)]
    assert float(np.abs(got - want).max()) > 50 * ATOL


def test_the_float8_control_fails_the_toy_limit():
    """What the cell's check does, at toy widths: the served tokens lie
    within float32's grain of the reference's best (limit 1e-3: fifty
    times the 2e-5 the logits are held to), and the tokens the reference
    puts first when its products run in float8 do not. At twice the other
    tests' width: at 64 the token's own embedding rules its logits through
    the tied head and no rounding moves a choice."""
    cfg, params, w, sizes = _built(**WIDER)
    prompt = _prompt(56, seed=21)       # the control judges these too
    toks, _, _ = _serve(cfg, params, prompt, 3)
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


def test_the_tree_is_3_03_b_parameters_and_6_06_gb_served():
    """The recipe's tree (``eval_shape``: nothing is made) — ISSUE 51's
    arithmetic, leaf group by leaf group — and the configuration file's
    two numbers; nothing is cut, so recipe and cell hold the same tree."""
    model_cfg, template = registry.served_template(_recipe_cfg())
    leaves = jax.tree.leaves(template)
    count = sum(int(np.prod(l.shape)) for l in leaves)
    nbytes = sum(int(np.prod(l.shape)) * l.dtype.itemsize for l in leaves)
    assert count == M.count_params(model_cfg) == 3_029_337_472 \
        == SHIPPED["bytes"]["parameters"]
    assert nbytes == 6_064_035_328 == SHIPPED["bytes"]["served_bytes"]
    assert model_cfg.kinds() == {"scan": 26, "full": 2}
    part = lambda kind, group: sum(  # noqa: E731
        int(np.prod(l.shape)) for l in jax.tree.leaves(template[kind][group]))
    assert part("scan", "ssm") == 26 * 41_241_792
    assert part("scan", "mlp") == 26 * 62_914_560
    assert part("full", "attn") == 2 * 13_762_560
    assert int(np.prod(template["embed"]["tokens"].shape)) == 167_772_160
    assert "head" not in template           # tied to the embedding
    assert not [o for o in SHIPPED["serve"]["overrides"]
                if o.startswith("Model.")] and SHIPPED["reduced"] == []
    f32 = {"/".join(str(getattr(p, "key", p)) for p in path)
           for path, l in jax.tree_util.tree_flatten_with_path(template)[0]
           if l.dtype == jnp.float32}
    assert f32 == {"final_norm/scale", "scan/norm1/scale", "scan/norm2/scale",
                   "full/norm1/scale", "full/norm2/scale", "scan/ssm/A_log",
                   "scan/ssm/D", "scan/ssm/dt_bias", "scan/ssm/conv_bias",
                   "scan/ssm/dt_norm", "scan/ssm/b_norm", "scan/ssm/c_norm"}
    over = dict(o.split("=") for o in SHIPPED["serve"]["overrides"])
    slots, page = int(over["Serving.max_batch"]), \
        int(over["Serving.page_size"])
    pool, state, tail = S.cache_shapes(
        model_cfg, num_pages=int(over["Serving.num_pages"]), page_size=page,
        max_batch=slots)
    assert pool == (2, 20481, 128, 128) and state == (26, 256, 16, 5120)
    assert tail == (26, 3, 256, 5120)
    # a token's keys and values in both layers: the issue's 1,024 B; a
    # layer's state a slot: 328 KB; 2.6 M token slots
    assert 2 * 2 * 128 * 2 == 1024 and 16 * 5120 * 4 == 327_680
    assert (pool[1] - 1) * page >= 2_600_000
    per_req = int(over["Serving.max_seq_len"]) // page
    assert not S.kernel_refusal(model_cfg, page_size=page,
                                pages_per_req=per_req, prefill_chunk=512,
                                max_batch=slots)
    # pages of 16 tokens: 256 rows x 1,152 entries do not fit the kernel's
    # scalar memory, and the family says so instead of failing to compile
    assert "scalar memory" in S.kernel_refusal(
        model_cfg, page_size=16, pages_per_req=18432 // 16,
        prefill_chunk=512, max_batch=slots)
    assert not S.kernel_refusal(model_cfg, page_size=64, pages_per_req=288,
                                prefill_chunk=512, max_batch=slots)
    assert PA.fold_shape(**S.kernel_geometry(
        model_cfg, page_size=page, pages_per_req=per_req)) == (16, 16)


@pytest.mark.parametrize("missing", ["attn_layer_period", "mamba_dt_rank",
                                     "num_key_value_heads", "num_experts",
                                     "rms_norm_eps"])
def test_a_recipe_that_omits_a_published_key_is_refused_by_name(missing):
    model = toy.model_section()
    del model[missing]
    with pytest.raises(ValueError, match=missing):
        config_from_dict(model)


def test_the_shipped_recipe_states_every_published_key_at_its_value():
    """The recipe's ``Model:`` section against the catalog row's numbers as
    the benchmark's configuration file holds them."""
    model = dict(_recipe_cfg()["Model"])
    for key in PUBLISHED_KEYS:
        assert model[key] == SHIPPED[key], key
    assert model.get("sliding_window") is None is SHIPPED["sliding_window"]


def test_tools_serve_builds_the_recipe_through_the_registry():
    """``tools/serve.py:_build_engine`` on the shipped recipe at toy
    widths: the same function that builds every family's engine."""
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    import serve as serve_tool

    over = [f"Model.{k}={v if isinstance(v, bool) else json.dumps(v)}"
            for k, v in toy.model_section().items()
            if k not in ("dtype", "param_dtype", "module", "hidden_act")]
    over += ["Model.dtype=float32", "Serving.max_batch=2",
             "Serving.num_pages=33", "Serving.page_size=4",
             "Serving.max_seq_len=64", "Serving.prefill_chunk=8",
             "Serving.paged_kernel=False"]
    eng = serve_tool._build_engine(_recipe_cfg(over))
    assert isinstance(eng, ServingEngine)
    assert type(eng.family).__name__ == "SSMMQAFamily"
    req = eng.submit([1, 2, 3, 4, 5], 4)
    eng.run_until_drained()
    assert req.state == "finished" and len(req.tokens) == 4


def test_the_new_scope_is_the_tables_and_adds_no_host_span(built):
    """``ssm.norm`` is in ``DEVICE_SCOPES`` and in both compiled programs
    beside the scopes the fifth family's scan layers and every family's
    attention layers use, no new name for either; the tick's host spans are
    what they were."""
    from fleetx_tpu.observability import trace

    assert "ssm.norm" in trace.DEVICE_SCOPES
    assert len(trace.HOT_LOOP_SPANS) == 15
    cfg, params, _, _ = built
    fns = _fns(cfg, False)
    cache = S.init_cache(cfg, num_pages=9, page_size=PAGE, max_batch=2)
    key = jax.random.PRNGKey(0)
    calls = {
        "decode": (params, *cache, np.zeros((2,), np.int32), np.int32(-1),
                   np.zeros((1,), np.int32), np.zeros((2, 8), np.int32),
                   np.zeros((2,), np.int32), key, np.uint32(0)),
        "prefill": (params, *cache, np.zeros((1, CHUNK), np.int32),
                    np.zeros((1, 8), np.int32), np.int32(0), np.int32(3),
                    key, np.uint32(0), np.int32(1))}
    for name, args in calls.items():
        text = fns[name].lower(*args).compile().as_text()
        scopes = {s for s, _ in trace.device_scope_table(text).values()}
        assert {"ssm.norm", "ssm.proj", "ssm.conv", "ssm.core", "attn.proj",
                "attn.core", "attn.cache", "mlp", "norm", "embed",
                "head"} <= scopes, (name, scopes)
        assert not scopes & {"attn.cross", "gmu", "conv.mix"}, (name, scopes)
