"""The decoder-hybrid-decoder family (``models/samba_y``,
``serving/samba_y.py``, ``ops/selective_scan.py``, ``ops/paged_attention.py``
with a ``scale``) against its plain reference
(``benchmarks/reference/phi4flash_ref.py``), at toy widths on the CPU.

Weights are seeded float32 (the benchmark's own ``weights.make``), so
program and reference differ by the order of float32 sums alone — and by
the form: the program scans a chunk from the slot's state, convolves it from
the slot's tail, folds a ring or the request's pages a key block at a time
through zero-half queries and runs the upper half on one row; the reference
scans, shifts and scores the whole sequence. Logits (standard deviation
~0.17, largest ~0.6) are held to 2e-5: float32's grain through eight layers
(the whole-sequence forward reads 3e-7), with room for the sub-norm, which
divides a difference of two softmax maps by its own size.
"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import samba_y_toy as toy  # noqa: E402
from benchmarks import check, weights  # noqa: E402
from benchmarks.manifest import load_module  # noqa: E402
from fleetx_tpu.models import scan_mixer  # noqa: E402
from fleetx_tpu.models.samba_y import model as M  # noqa: E402
from fleetx_tpu.models.samba_y.config import (PUBLISHED_KEYS,  # noqa: E402
                                              config_from_dict)
from fleetx_tpu.observability import schema  # noqa: E402
from fleetx_tpu.ops import paged_attention as PA  # noqa: E402
from fleetx_tpu.ops import selective_scan as SS  # noqa: E402
from fleetx_tpu.serving import programs, registry  # noqa: E402
from fleetx_tpu.serving import samba_y as S  # noqa: E402
from fleetx_tpu.serving.decode import SamplingParams  # noqa: E402
from fleetx_tpu.serving.engine import (ServingConfig,  # noqa: E402
                                       ServingEngine)

ROOT = toy.ROOT
ref = load_module(os.path.join(ROOT, "benchmarks/reference/phi4flash_ref.py"))
family = load_module(os.path.join(ROOT, "benchmarks/families/SambaYModule.py"))
with open(os.path.join(
        ROOT, "benchmarks/configs/phi-4-mini-flash-reasoning.json")) as _f:
    SHIPPED = json.load(_f)
CHUNK, PAGE, ATOL = 8, 4, 2e-5


def _built(seed=7, **widths):
    """``(model config, program tree, reference weights, sizes)``: the same
    seeded numbers on both sides, through ``param_paths``."""
    sizes = toy.sizes(**widths)
    spec = ref.weight_spec(sizes)
    w = weights.make(spec, seed)
    cfg = config_from_dict(toy.model_section(**widths))
    # as the benchmark serves them: the family file's one scaled leaf
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
            cfg, prefill_chunk=CHUNK, page_size=PAGE,
            sampling=SamplingParams(), kernels=kernels))
    return _FNS[key][1]


def _serve(cfg, params, prompt, new, *, slot=1, max_batch=3, kernels=False,
           max_seq=96, cache=None):
    """Prefill ``prompt`` in chunks, decode ``new`` tokens greedily, in slot
    ``slot`` of an otherwise empty batch: ``(tokens, logits a step,
    cache)``."""
    P = max_seq // PAGE
    fns = _fns(cfg, kernels)
    cache = cache or S.init_cache(
        cfg, num_pages=1 + max_batch * P, page_size=PAGE,
        max_batch=max_batch, prefill_chunk=CHUNK)
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
    5,      # shorter than a chunk (and than the window)
    13,     # a ragged last chunk of 5; longer than the 8-token window
    17,     # a last chunk of ONE token: shorter than the tail
    24,     # whole chunks, three windows long
])
def test_prefill_then_decode_through_every_cache_is_the_reference_on_logits(
        built, prompt_len):
    """Chunked prefill (pool, rings, states, tails; the upper half on one
    row), then decode through all of them, a token at a time: the logits of
    the prompt's last position and of every decoded one are the reference's
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
    over the pool (the full layer, the cross layer, the one prefill row)
    and ``paged_decode_window`` over the rings, key-value pairs of 64
    lanes (``tests/test_tpu_lowering.py`` finds them by name in the
    programs compiled for the chip)."""
    cfg, params, w, sizes = _built(**toy.KERNEL_WIDTHS)
    assert not S.kernel_refusal(cfg, page_size=PAGE, pages_per_req=24,
                                prefill_chunk=CHUNK)
    prompt = _prompt(13)
    toks, logits, _ = _serve(cfg, params, prompt, 4, kernels=True)
    want = _reference_rows(w, sizes, toks)
    for i, got in enumerate(logits):
        np.testing.assert_allclose(got, want[12 + i], atol=ATOL)


def test_a_reused_slot_starts_from_zero(built):
    """A slot's ring, state and tail are whatever the last request left: a
    request's first chunk reads zeros in place of the state and the tail,
    and sees none of the ring's stale keys."""
    cfg, params, w, sizes = built
    _, _, cache = _serve(cfg, params, _prompt(21, seed=3), 5)
    assert float(jnp.abs(cache[4][:, 1]).max()) > 0     # the slot's states
    prompt = _prompt(11, seed=4)
    toks, logits, _ = _serve(cfg, params, prompt, 4, cache=cache)
    want = _reference_rows(w, sizes, toks)
    for i, got in enumerate(logits):
        np.testing.assert_allclose(got, want[10 + i], atol=ATOL)


# ------------------------------------------------------------------ the scan
def _scan_data(T, ch, n, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 7)
    return (jax.random.normal(k[0], (T, ch)),
            jax.nn.softplus(jax.random.normal(k[1], (T, ch))),
            -jnp.exp(0.3 * jax.random.normal(k[2], (n, ch))),
            jax.random.normal(k[3], (T, n)), jax.random.normal(k[4], (T, n)),
            jax.random.normal(k[5], (ch,)), jax.random.normal(k[6], (n, ch)))


def test_the_chunk_kernel_is_the_scan_and_a_ragged_tail_changes_nothing():
    x, delta, a, b, c, d, s = _scan_data(48, 256, 8)
    delta = delta.at[40:].set(0.0)          # rows past a ragged chunk's end
    want_y, want_s = SS.scan_rule(x, delta, a, b, c, d, s)
    got_y, got_s = jax.jit(SS.scan_chunk)(x, delta, a, b, c, d, s)
    np.testing.assert_allclose(got_y, want_y, atol=1e-5)
    np.testing.assert_allclose(got_s, want_s, atol=1e-6)
    upto = SS.scan_rule(x[:40], delta[:40], a, b[:40], c[:40], d, s)[1]
    np.testing.assert_allclose(got_s, upto, atol=1e-6)
    # a geometry the kernel refuses takes the plain form, in words
    assert "128-lane" in SS.scan_refusal(channels=96, states=8, chunk=8)
    assert "sublane" in SS.scan_refusal(channels=128, states=4, chunk=8)
    assert "sublane" in SS.scan_refusal(channels=128, states=8, chunk=12)
    assert SS.scan_refusal(channels=5120, states=16, chunk=512) == ""
    y, _ = SS.scan_chunk(x[:, :96], delta[:, :96], a[:, :96], b, c, d[:96],
                         s[:, :96])
    np.testing.assert_allclose(y, want_y[:, :96], atol=1e-5)


@pytest.mark.parametrize("kernel", [False, True])
def test_a_chunk_then_steps_is_the_scan_over_the_joined_sequence(kernel):
    """``scan_chunk`` from a state, then ``scan_step`` a token at a time in
    place, is ``scan_rule`` over all the tokens; a row that is not live
    keeps its state and no other layer is touched."""
    x, delta, a, b, c, d, s = _scan_data(24, 128, 8, seed=1)
    want_y, want_s = SS.scan_rule(x, delta, a, b, c, d, s)
    y, h = jax.jit(lambda *args: SS.scan_chunk(*args, kernel=kernel))(
        x[:16], delta[:16], a, b[:16], c[:16], d, s)
    buf = jax.random.normal(jax.random.PRNGKey(5), (2, 3, 8, 128))
    before = buf
    buf = buf.at[1, 2].set(h)
    live = jnp.array([False, False, True])
    step = jax.jit(lambda buf, *args: SS.scan_step(
        buf, jnp.int32(1), *args, kernel=kernel))
    ys = [y]
    for t in range(16, 24):
        row = lambda v: jnp.zeros((3,) + v.shape[1:]).at[2].set(v[t])  # noqa: E731,E501
        yt, buf = step(buf, row(x), row(delta), a, row(b), row(c), d, live)
        assert float(jnp.abs(yt[:2]).max()) == 0.0
        ys.append(yt[2:3])
    np.testing.assert_allclose(jnp.concatenate(ys), want_y, atol=1e-5)
    np.testing.assert_allclose(buf[1, 2], want_s, atol=1e-6)
    np.testing.assert_array_equal(buf[0], before[0])
    np.testing.assert_array_equal(buf[1, :2], before[1, :2])


# ------------------------------------------------- differential attention
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-6),
                                       (jnp.bfloat16, 2e-2)])
def test_two_maps_through_the_paged_kernel_are_the_gathered_view(dtype, tol):
    """8 query heads over 4 key-value heads of 64 as TWO pairs of 128
    lanes, four zero-half queries to each, scaled by 1/8 (``scale=``): the
    kernel (interpreted) over a pool and over a ring against
    ``programs.gathered_attention`` on the same keys."""
    B, H, hd, ps, P = 3, 8, 64, 4, 6
    k = jax.random.split(jax.random.PRNGKey(2), 3)
    q = M.diff_queries(jax.random.normal(k[0], (B, H, hd))).astype(dtype)
    pool_k = jax.random.normal(k[1], (2, 1 + B * P, ps, 4 * hd)).astype(dtype)
    pool_v = jax.random.normal(k[2], (2, 1 + B * P, ps, 4 * hd)).astype(dtype)
    assert q.shape == (B, H, 2 * hd)
    # a query scores one half of its pair's lanes: the other half is zero
    assert float(jnp.abs(q[:, 0::2, hd:]).max()) == 0.0
    assert float(jnp.abs(q[:, 1::2, :hd]).max()) == 0.0
    tables = 1 + np.arange(B * P, dtype=np.int32).reshape(B, P)
    lens = np.array([21, -1, 7], np.int32)
    got = PA.paged_attention(q.astype(jnp.float32), pool_k, pool_v, tables,
                             lens, jnp.int32(1), scale=0.125)
    assert got.dtype == jnp.float32         # the two maps, not yet rounded
    kd = pool_k[1, tables].reshape(B, -1, 2, 2 * hd)
    vd = pool_v[1, tables].reshape(B, -1, 2, 2 * hd)
    kp = np.broadcast_to(np.arange(P * ps, dtype=np.int32), (B, P * ps))
    want = programs.gathered_attention(
        q[:, None], kd, vd, kp, np.maximum(lens, 0)[:, None], None, dtype,
        scale=0.125, out_dtype=jnp.float32)[:, 0]
    live = lens >= 0
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               atol=tol)
    # the first map of pair 0 is head 0's softmax over K[0] times [V0; V1]
    s = jnp.einsum("d,td->t", q[0, 0, :hd].astype(jnp.float32),
                   kd[0, :22, 0, :hd].astype(jnp.float32)) / 8
    one = jax.nn.softmax(s) @ vd[0, :22, 0].astype(jnp.float32)
    np.testing.assert_allclose(got[0, 0], one, atol=tol)
    # ... and over a ring of 6 pages with a 16-token window
    first = jnp.asarray(1 + np.arange(B) * P, jnp.int32)
    got = PA.paged_attention(q.astype(jnp.float32), pool_k, pool_v, first,
                             lens, jnp.int32(0), window=16, ring_pages=P,
                             scale=0.125)
    pages, pos = programs.ring_view(first, jnp.asarray(lens), P, ps)
    want = programs.gathered_attention(
        q[:, None], pool_k[0, pages].reshape(B, -1, 2, 2 * hd),
        pool_v[0, pages].reshape(B, -1, 2, 2 * hd), pos,
        np.maximum(lens, 0)[:, None], 16, dtype, scale=0.125,
        out_dtype=jnp.float32)[:, 0]
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               atol=tol)


def test_the_difference_the_sub_norm_and_lambda_are_all_there():
    """``diff_combine``: ``RMSNorm(o₁ − λ o₂) (1 − λ_init)`` with ``λ =
    exp(λ_q1·λ_k1) − exp(λ_q2·λ_k2) + λ_init`` — by hand on one pair."""
    cfg = config_from_dict(toy.model_section())
    assert cfg.lambda_init("window") == tuple(
        0.8 - 0.6 * np.exp(-0.3 * l) for l in (1, 3))
    assert cfg.lambda_init("full") == (0.8 - 0.6 * np.exp(-0.3 * 5),)
    assert cfg.lambda_init("cross") == (0.8 - 0.6 * np.exp(-0.3 * 7),)
    k = jax.random.split(jax.random.PRNGKey(3), 6)
    o = jax.random.normal(k[0], (2, 4, 16))
    lp = {"lambda_q1": 0.3 * jax.random.normal(k[1], (8,)),
          "lambda_k1": 0.3 * jax.random.normal(k[2], (8,)),
          "lambda_q2": 0.3 * jax.random.normal(k[3], (8,)),
          "lambda_k2": 0.3 * jax.random.normal(k[4], (8,)),
          "subln": 1 + 0.1 * jax.random.normal(k[5], (16,))}
    lam0 = 0.35
    got = M.diff_combine(o, lp, jnp.float32(lam0), 1e-5, jnp.float32)
    lam = np.exp(float(lp["lambda_q1"] @ lp["lambda_k1"])) \
        - np.exp(float(lp["lambda_q2"] @ lp["lambda_k2"])) + lam0
    d = np.asarray(o[:, 2] - lam * o[:, 3])
    want = d / np.sqrt((d * d).mean(-1, keepdims=True) + 1e-5) \
        * np.asarray(lp["subln"]) * (1 - lam0)
    np.testing.assert_allclose(got[:, 16:], want, atol=1e-6)
    assert abs(lam - lam0) > 1e-3


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


def _products(jaxpr):
    """The shape of every matrix product's result, inner programs
    (``jit``, ``scan``, ``cond``) included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            yield eqn.outvars[0].aval.shape
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _products(sub)


def test_the_engine_serves_the_family_and_the_upper_half_sees_one_row(built):
    """Requests join and leave ONE engine (the same class, scheduler and
    allocator as every family's) while others are mid-prefill — a decode
    step must leave a prefilling slot's state, tail and ring alone —; every
    served token is the reference's best within float32's grain; each
    program compiled once; the prefill program's shapes say a chunk sends ONE
    row through the layers above the full layer; the build's line, the gauges and the
    snapshot name the four caches."""
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
    assert line and "1 layer paged (32 lanes a token) that 2 layers read, " \
        "2 window layers a ring of 4 pages a slot, 3 scan layers a state " \
        "of 8 x 128 and a tail of 3 rows a slot" in line[-1]
    assert eng.family is registry.family("SambaYModule")
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
    # the prefill program's own shapes: a feed-forward product a layer (a
    # scanned run of layers holds its body once), those of layers 0 .. 5 over
    # the chunk's rows, the two above them (the memory unit's layer and the
    # cross layer) over ONE row
    table = np.zeros((1, eng._block_tables.shape[1]), np.int32)
    jaxpr = jax.make_jaxpr(eng._fns["prefill"])(
        eng.params, *eng.cache, np.zeros((1, CHUNK), np.int32), table,
        np.int32(0), np.int32(CHUNK), *eng._draw(), np.int32(0))
    rows = sorted(int(np.prod(shape[:-1])) for shape in _products(jaxpr.jaxpr)
                  if shape[-1:] == (2 * cfg.intermediate_size,))
    assert rows[:2] == [1, 1] and set(rows[2:]) == {CHUNK}, rows
    state = int(eng.cache[4].nbytes + eng.cache[5].nbytes)
    assert state == 3 * 3 * 8 * 128 * 4 + 3 * 3 * 3 * 128 * 4
    assert m.gauge("serving_state_cache_bytes").value == state \
        == snap["serving_state_cache_bytes"]
    assert m.gauge("serving_kv_cache_bytes").value == eng.cache_bytes
    assert m.gauge("serving_latent_cache_bytes").value == 0
    assert not schema.validate_serving_record(snap)
    # the one paged layer holds every token, a ring the window's at most
    assert eng.family.kv_tokens(cfg, np.array([5, -1, 30])) == (35, 13)
    # a slot's state, tail and ring follow the slots, never max_seq_len
    longer = _engine(cfg, params, max_seq_len=192, num_pages=120)
    assert longer.metrics.gauge("serving_state_cache_bytes").value == state
    assert longer.cache[2].shape == eng.cache[2].shape


def test_a_preempted_request_resumes_to_the_same_tokens(built):
    """A pool too small for three growing requests preempts the youngest:
    its pages are freed, its ring, state and tail are whatever they are; it
    is prefilled again from its first token (which rebuilds all three from
    zero) and serves the tokens an unpressed engine serves — each the
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


def test_a_mesh_and_quantization_are_refused_with_a_sentence(built):
    from jax.sharding import Mesh

    cfg, params, _, _ = built
    mesh = Mesh(np.array(jax.devices()[:2]).reshape(1, 1, 2, 1, 1),
                ("data", "fsdp", "tensor", "seq", "pipe"))
    sc = dict(max_batch=2, page_size=PAGE, num_pages=20, max_seq_len=32,
              prefill_chunk=CHUNK)
    family = registry.family("SambaYModule")
    with pytest.raises(AssertionError, match="serves on one chip: its "
                       "programs place none of its four caches"):
        family.programs(cfg, ServingConfig(**sc), SamplingParams(), mesh, 8)
    with pytest.raises(AssertionError, match="quantized decode is not "
                       "written for SambaYFamily"):
        family.programs(cfg, ServingConfig(quantize_decode=True, **sc),
                        SamplingParams(), None, 8)
    with pytest.raises(AssertionError, match="quantized decode"):
        family.model_config(toy.model_section(), {"weight_bits": 8})


# ------------------------------------------------------------ what is assumed
def _gate_on_m(u, m, lp):
    g = jnp.einsum("sh,hc->sc", u, lp["in"])
    return jnp.einsum("sc,ch->sh", jax.nn.silu(m) * g, lp["out"])


M_ssm_in, M_gated_mlp, M_diff_lambda = M.ssm_in, M.gated_mlp, M.diff_lambda
#: each reading the comparison has to tell from the one taken: another one
#: in place of the one function of ``models/samba_y/model.py`` that holds it
OTHER_READINGS = {
    "the in-projection read as z, x": (
        "ssm_in", lambda u, lp: M_ssm_in(u, lp)[::-1]),
    "the MLP read as p, g": (
        "gated_mlp", lambda f, lp: M_gated_mlp(f, dict(
            lp, gate_up=jnp.concatenate(
                [lp["gate_up"][:, lp["gate_up"].shape[1] // 2:],
                 lp["gate_up"][:, :lp["gate_up"].shape[1] // 2]], axis=1)))),
    "the memory unit's SiLU on m": ("memory_unit", _gate_on_m),
    "no second map (lambda = 0)": (
        "diff_lambda", lambda lp, lam0: jnp.float32(0.0)),
    "no sub-norm": (
        "diff_combine", lambda o, lp, lam0, eps, dtype: (
            o.reshape(o.shape[0], -1, 2, o.shape[2])[:, :, 0]
            - M_diff_lambda(lp, lam0)
            * o.reshape(o.shape[0], -1, 2, o.shape[2])[:, :, 1]
        ).reshape(o.shape[0], -1).astype(dtype)),
}


@pytest.mark.parametrize("reading", sorted(OTHER_READINGS))
def test_the_comparison_sees_each_other_reading(built, monkeypatch, reading):
    """With one reading taken otherwise in the program its logits leave the
    reference's by far more than the sound program's 2e-5."""
    cfg, params, w, sizes = built
    name, other = OTHER_READINGS[reading]
    # the scan mixer's parts live in ``models/scan_mixer.py`` (one
    # definition for both scan families), whose own calls are what count
    monkeypatch.setattr(scan_mixer if hasattr(scan_mixer, name) else M,
                        name, other)
    toks = _prompt(24)
    # (a fresh function a case: ``jit`` keeps its trace by the function)
    got = np.asarray(jax.jit(lambda p, t: M.forward(p, cfg, t))(
        params, jnp.asarray(toks)))
    want = _reference_rows(w, sizes, toks)[:len(toks)]
    assert float(np.abs(got - want).max()) > 50 * ATOL, reading


def test_the_one_scaled_leaf_is_scaled_alike_on_both_sides(built):
    """The family file and the reference each hold the seeded draw of the
    scan's step / B / C matrix at a power of two (exact in bfloat16) by a
    table of their own: the two tables name the same leaf with the same
    exponent through the configuration's ``param_paths``, the program's leaf
    is the harness's times it, and without it the comparison fails."""
    cfg, params, w, sizes = built
    paths = SHIPPED["param_paths"]
    assert {paths[n]: e for n, e in ref.WEIGHT_SCALE_LOG2.items()} \
        == family.WEIGHT_SCALE_LOG2 == {"scan/ssm/x": -3}
    assert "0.02 / 8" in SHIPPED["assumed"]["weights"]
    np.testing.assert_array_equal(np.asarray(params["scan"]["ssm"]["x"]),
                                  np.asarray(w["sc_x"]) / 8)
    half = jnp.asarray(w["sc_x"], jnp.bfloat16)
    np.testing.assert_array_equal(
        np.asarray(family.seeded({"scan": {"ssm": {"x": half}}})
                   ["scan"]["ssm"]["x"], np.float32),
        np.asarray(half, np.float32) / 8)
    toks = _prompt(24)
    unscaled = dict(params, scan=dict(params["scan"], ssm=dict(
        params["scan"]["ssm"], x=w["sc_x"])))
    got = np.asarray(_forward(unscaled, cfg, jnp.asarray(toks)))
    want = _reference_rows(w, sizes, toks)[:len(toks)]
    assert float(np.abs(got - want).max()) > 50 * ATOL


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


def _count(template) -> tuple:
    leaves = jax.tree.leaves(template)
    return (sum(int(np.prod(l.shape)) for l in leaves),
            sum(int(np.prod(l.shape)) * l.dtype.itemsize for l in leaves))


def test_the_tree_is_3_85_b_parameters_whole_and_3_40_b_at_an_eighth():
    """The recipe's tree at the published 200,064 ids (``eval_shape``:
    nothing is made) — ISSUE 48's arithmetic, which is how the layer map was
    checked against the published 3.8 B — and the benchmark's at 25,008."""
    model_cfg, template = registry.served_template(_recipe_cfg())
    assert model_cfg.vocab_size == 200_064
    count, _ = _count(template)
    assert count == M.count_params(model_cfg) == 3_852_562_944
    assert model_cfg.kinds() == {"scan": 9, "window": 8, "full": 1,
                                 "gmu": 7, "cross": 7}
    assert [model_cfg.kind_of(l) for l in (0, 1, 16, 17, 18, 19, 30, 31)] \
        == ["scan", "window", "scan", "full", "gmu", "cross", "gmu", "cross"]
    part = lambda kind, group: sum(  # noqa: E731
        int(np.prod(l.shape)) for l in jax.tree.leaves(template[kind][group]))
    assert part("scan", "ssm") == 9 * 41_241_600           # 41.2 M
    assert part("window", "attn") == 8 * 19_668_864        # 19.7 M
    assert part("cross", "attn") == 7 * 13_112_704         # 13.1 M
    assert part("gmu", "gmu") == 7 * 26_214_400            # 26.2 M
    assert part("full", "mlp") == 78_643_200               # 78.6 M
    assert "head" not in template           # tied to the embedding
    cut_cfg, cut = registry.served_template(_recipe_cfg(
        [o for o in SHIPPED["serve"]["overrides"]
         if o.startswith("Model.")]))
    count, nbytes = _count(cut)
    assert cut_cfg.vocab_size == 25_008 == SHIPPED["vocab_size"]
    assert count == 3_404_419_584 == SHIPPED["bytes"]["parameters"]
    assert nbytes == SHIPPED["bytes"]["served_bytes"]
    assert abs(nbytes / 6.80e9 - 1) < 0.01
    f32 = {"/".join(str(getattr(p, "key", p)) for p in path)
           for path, l in jax.tree_util.tree_flatten_with_path(cut)[0]
           if l.dtype == jnp.float32}
    assert {"scan/ssm/A_log", "scan/ssm/D", "scan/ssm/dt_bias",
            "window/attn/lambda_q1", "cross/attn/subln", "gmu/norm1/bias",
            "final_norm/scale"} <= f32
    assert "scan/ssm/in" not in f32 and "cross/attn/qkv_bias" not in f32
    pool, ring, state, tail = S.cache_shapes(
        cut_cfg, num_pages=40961, page_size=16, max_batch=64,
        prefill_chunk=512)
    assert pool == (1, 40961, 16, 1280) and ring == (8, 1 + 64 * 64, 16, 1280)
    assert state == (9, 64, 16, 5120) and tail == (9, 3, 64, 5120)
    # a token's keys and values in the ONE paged layer: the issue's 5,120 B;
    # a layer's state a slot: 328 KB
    assert 2 * 1280 * 2 == 5120 and 16 * 5120 * 4 == 327_680
    assert not S.kernel_refusal(cut_cfg, page_size=16, pages_per_req=1600,
                                prefill_chunk=512)
    assert S.kernel_walk(cut_cfg, page_size=16, pages_per_req=1600,
                         prefill_chunk=512)[1] == {"full": (8, 8),
                                                   "window": (8, 1)}


@pytest.mark.parametrize("missing", ["sliding_window", "mb_per_layer",
                                     "num_key_value_heads",
                                     "tie_word_embeddings", "layer_norm_eps"])
def test_a_recipe_that_omits_a_published_key_is_refused_by_name(missing):
    model = toy.model_section()
    del model[missing]
    with pytest.raises(ValueError, match=missing):
        config_from_dict(model)


def test_the_shipped_recipe_states_every_published_key_at_its_value():
    """The recipe's ``Model:`` section against the catalog row's numbers as
    the benchmark's configuration file holds them — but the vocabulary,
    which the recipe keeps whole."""
    model = dict(_recipe_cfg()["Model"])
    for key in PUBLISHED_KEYS:
        want = SHIPPED["published"]["vocab_size"] if key == "vocab_size" \
            else SHIPPED[key]
        assert model[key] == want, key
    for key, value in SHIPPED["assumed"].items():
        if key in ("d_state", "d_conv", "expand", "dt_rank"):
            assert model[key] == value, key
    with pytest.raises(AssertionError, match="mb_per_layer"):
        config_from_dict(toy.model_section(mb_per_layer=4))
    with pytest.raises(AssertionError, match="N/2 even"):
        config_from_dict(toy.model_section(num_hidden_layers=10))


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
    assert type(eng.family).__name__ == "SambaYFamily"
    req = eng.submit([1, 2, 3, 4, 5], 4)
    eng.run_until_drained()
    assert req.state == "finished" and len(req.tokens) == 4


def test_the_new_scopes_are_the_tables_and_add_no_host_span(built):
    """``ssm.proj`` / ``ssm.conv`` / ``ssm.core`` / ``gmu`` / ``attn.cross``
    are in ``DEVICE_SCOPES`` and in both compiled programs beside the names
    every family uses, no instruction outside a scope; the tick's host spans
    are what they were."""
    from fleetx_tpu.observability import trace

    new = {"ssm.proj", "ssm.conv", "ssm.core", "gmu", "attn.cross"}
    assert new <= set(trace.DEVICE_SCOPES)
    assert len(trace.HOT_LOOP_SPANS) == 15
    cfg, params, _, _ = built
    fns = _fns(cfg, False)
    cache = S.init_cache(cfg, num_pages=9, page_size=PAGE, max_batch=2,
                         prefill_chunk=CHUNK)
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
        assert new | {"attn.proj", "attn.core", "attn.cache", "mlp", "norm",
                      "embed", "head"} <= scopes, (name, scopes)
