"""The hybrid family (``models/gdn_mla``, ``serving/gdn_mla.py``,
``ops/gated_delta.py``, ``ops/mla_paged_attention.py``) against its plain
reference (``benchmarks/reference/gigachat35_ref.py``), at toy widths on
the CPU.

Weights are seeded float32 (the benchmark's own ``weights.make``), so
program and reference differ by the order of float32 sums alone — and by
the form: the program runs the CHUNKED rule and the ABSORBED latent decode,
the reference the three-line recurrence and full per-head keys and values.
Logits (size ~0.4) are held to 2e-5.
"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import gdn_mla_toy as toy  # noqa: E402
from benchmarks import weights  # noqa: E402
from benchmarks.manifest import load_module  # noqa: E402
from fleetx_tpu.models.gdn_mla import model as M  # noqa: E402
from fleetx_tpu.models.gdn_mla.config import (PUBLISHED_KEYS,  # noqa: E402
                                              config_from_dict)
from fleetx_tpu.observability import schema  # noqa: E402
from fleetx_tpu.ops import gated_delta as GD  # noqa: E402
from fleetx_tpu.ops import mla_paged_attention as LA  # noqa: E402
from fleetx_tpu.serving import gdn_mla as S, registry  # noqa: E402
from fleetx_tpu.serving.decode import SamplingParams  # noqa: E402
from fleetx_tpu.serving.engine import (ServingConfig,  # noqa: E402
                                       ServingEngine)

ROOT = toy.ROOT
ref = load_module(os.path.join(ROOT, "benchmarks/reference/gigachat35_ref.py"))
with open(os.path.join(
        ROOT, "benchmarks/configs/gigachat3.5-432b-a28b.json")) as _f:
    SHIPPED = json.load(_f)
CHUNK, PAGE, ATOL = 8, 4, 2e-5


def _built(sizes=None, seed=7, **model):
    """``(model config, program tree, reference weights, sizes)``: the same
    seeded numbers on both sides, through ``param_paths``."""
    sizes = dict(sizes or toy.PUBLISHED)
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


def _serve(cfg, params, prompt, new, *, slot=1, max_batch=3, kernels=False,
           latent_kernel=False, page=PAGE, chunk=CHUNK, max_seq=96,
           cache=None):
    """Prefill ``prompt`` in chunks, decode ``new`` tokens greedily, in slot
    ``slot`` of an otherwise empty batch: ``(tokens, logits a step, cache,
    stats)``."""
    P = max_seq // page
    fns = S.make_step_fns(cfg, prefill_chunk=chunk,
                          sampling=SamplingParams(), kernels=kernels,
                          latent_kernel=latent_kernel)
    cache = cache or S.init_cache(cfg, num_pages=1 + max_batch * P,
                                  page_size=page, max_batch=max_batch)
    table = np.zeros((max_batch, P), np.int32)
    table[slot] = 1 + slot * P + np.arange(P)
    key = jax.random.PRNGKey(0)
    toks, logits, pos, stats = list(prompt), [], 0, None
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
    return toks, logits, cache, stats


def _reference_rows(w, sizes, toks):
    row = np.zeros((1, -(-len(toks) // 64) * 64), np.int32)
    row[0, :len(toks)] = toks
    return np.asarray(ref.logits(w, sizes, jnp.asarray(row)))[0]


def _prompt(n, seed=None, vocab=96):
    return np.random.default_rng(n if seed is None else seed).integers(
        0, vocab, size=n).tolist()


# ------------------------------------------------------------------ the rule
def _rule_inputs(T=40, hk=2, hv=4, dk=16, dv=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = GD.l2_normalise(jax.random.normal(ks[0], (T, hk, dk))) * dk ** -0.5
    k = GD.l2_normalise(jax.random.normal(ks[1], (T, hk, dk)))
    v = jax.random.normal(ks[2], (T, hv, dv))
    g = -2.0 * jax.nn.softplus(jax.random.normal(ks[3], (T, hv)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (T, hv)))
    return q, k, v, g, beta, jax.random.normal(ks[5], (hv, dk, dv))


def _recurrence(q, k, v, g, beta, s0):
    """The reference's three lines, in its own [value, key] orientation."""
    rep = v.shape[1] // k.shape[1]
    q, k = jnp.repeat(q, rep, axis=1), jnp.repeat(k, rep, axis=1)

    def step(S_, x):
        q_t, k_t, v_t, g_t, b_t = x
        S_ = jnp.exp(g_t)[:, None, None] * S_
        S_ = S_ + (b_t[:, None] * (v_t - jnp.einsum(
            "hvk,hk->hv", S_, k_t)))[:, :, None] * k_t[:, None, :]
        return S_, jnp.einsum("hvk,hk->hv", S_, q_t)

    s, o = jax.lax.scan(step, jnp.swapaxes(s0, 1, 2), (q, k, v, g, beta))
    return o, jnp.swapaxes(s, 1, 2)


@pytest.mark.parametrize("kernel", [False, True], ids=["xla", "pallas"])
@pytest.mark.parametrize("T,pieces", [(128, 1), (128, 4), (24, 3)])
def test_the_chunked_rule_is_the_recurrence(kernel, T, pieces):
    """From a NON-ZERO state, over the boundaries of its own chunks (64
    tokens, or what divides ``T``) and of the calls (``pieces`` calls, the
    state handed on): the chunked form gives the recurrence's outputs and
    its final state."""
    with jax.default_matmul_precision("highest"):
        q, k, v, g, beta, s0 = _rule_inputs(T)
        want_o, want_s = _recurrence(q, k, v, g, beta, s0)
        n, s, outs = T // pieces, s0, []
        for i in range(pieces):
            part = slice(i * n, (i + 1) * n)
            o, s = GD.chunk_rule(q[part], k[part], v[part], g[part],
                                 beta[part], s, kernel=kernel)
            outs.append(o)
    np.testing.assert_allclose(jnp.concatenate(outs), want_o, atol=5e-6)
    np.testing.assert_allclose(s, want_s, atol=5e-6)


def test_tokens_past_a_ragged_chunks_end_change_nothing():
    q, k, v, g, beta, s0 = _rule_inputs(16)
    real = jnp.arange(16) < 11
    o, s = GD.chunk_rule(q, k, v, jnp.where(real[:, None], g, 0.0),
                         jnp.where(real[:, None], beta, 0.0), s0,
                         kernel=False)
    want_o, want_s = _recurrence(q[:11], k[:11], v[:11], g[:11], beta[:11],
                                 s0)
    np.testing.assert_allclose(o[:11], want_o, atol=5e-6)
    np.testing.assert_allclose(s, want_s, atol=5e-6)


def _published_widths():
    """Two value heads to a key head at the published head sizes."""
    return _rule_inputs(128, hk=2, hv=4, dk=128, dv=128, seed=1)


def _strong_decays():
    """``g = -30`` a token: a chunk's cumulated log-decay reaches -1,920,
    whose exponential's inverse overflows float32."""
    q, k, v, g, beta, s0 = _rule_inputs(128)
    return q, k, v, jnp.full_like(g, -30.0), beta, s0


def _one_key():
    """One key through whole chunks with beta -> 1 and no decay: the
    chunk's triangular system has ``-beta`` at every place under its
    diagonal, the powers of that matrix grow like binomial coefficients (to
    10^18 at 64 rows) and cancel."""
    q, k, v, g, beta, s0 = _rule_inputs(128)
    return (q, jnp.broadcast_to(k[:1], k.shape), v, jnp.zeros_like(g),
            jnp.full_like(beta, 1.0 - 1e-3), s0)


@pytest.mark.parametrize("kernel", [False, True], ids=["xla", "pallas"])
@pytest.mark.parametrize("inputs,atol_o,atol_s", [
    (_published_widths, 5e-6, 5e-6),
    (_strong_decays, 5e-6, 5e-6),
    # what the row-by-row substitution of PR 49 met on these inputs: 6.85e-7
    # on outputs of size 1.05, 2.86e-6 on a state of size 3.8
    (_one_key, 6.9e-7, 2.9e-6),
    (lambda: _rule_inputs(96, seed=4), 5e-6, 5e-6),     # chunks of 32
    (lambda: _rule_inputs(24, seed=2), 5e-6, 5e-6),     # chunks of 8
    (lambda: _rule_inputs(8, seed=3), 5e-6, 5e-6),      # less than a block
], ids=["two-value-heads-a-key-head-at-128", "strong-decays",
        "one-key-through-a-chunk", "T96", "T24", "T8"])
def test_the_triangular_systems_inside_the_rule(kernel, inputs, atol_o,
                                                atol_s):
    """From a non-zero state, the chunked form is the recurrence where the
    chunk's triangular system is what can go wrong: a value head finds its
    key head where the key heads lie (no copy a value head); decays enter
    as differences alone (finite outputs); a key repeated through a chunk
    (blocked substitution, no series in the matrix's powers); a chunk of
    two blocks (one level of joins), of half a block."""
    with jax.default_matmul_precision("highest"):
        q, k, v, g, beta, s0 = inputs()
        want_o, want_s = _recurrence(q, k, v, g, beta, s0)
        o, s = GD.chunk_rule(q, k, v, g, beta, s0, kernel=kernel)
    assert bool(jnp.isfinite(o).all()) and bool(jnp.isfinite(s).all())
    np.testing.assert_allclose(o, want_o, atol=atol_o, rtol=0)
    np.testing.assert_allclose(s, want_s, atol=atol_s, rtol=0)


def _scoped_eqns(jaxpr, scope=""):
    """``(equation, the named scopes it lies under)`` for every equation of
    a jaxpr and of the jaxprs its equations hold (a loop's body, a Pallas
    kernel's): an inner jaxpr's names are relative to its equation's."""
    for eqn in jaxpr.eqns:
        here = f"{scope}/{eqn.source_info.name_stack}"
        yield eqn, here
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _scoped_eqns(sub, here)


_LOOPS = ("while", "scan")      # a ``fori_loop`` is one or the other


def test_the_chunked_rule_is_one_kernel_and_no_loop(built):
    """Guards what PR 50 took out of the prefill chunk: the triangular
    systems' 63-turn substitution loop, batched XLA over an 8 MB tensor in
    front of the kernel. At the published chunk the rule with its kernel is
    ONE ``pallas_call`` and no loop, inside the kernel or around it; the
    family's prefill step has no loop under its ``gdn.core`` scope on
    either path (the loops over runs of layers lie above it)."""
    q, k, v, g, beta, s0 = _rule_inputs(512, hk=1, hv=2, dk=128, dv=128)
    eqns = [e.primitive.name for e, _ in _scoped_eqns(jax.make_jaxpr(
        lambda *a: GD.chunk_rule(*a, kernel=True))(q, k, v, g, beta, s0
                                                   ).jaxpr)]
    assert eqns.count("pallas_call") == 1
    assert not [name for name in eqns if name in _LOOPS]
    cfg, params, _, _ = built
    P, max_batch = 96 // PAGE, 3
    cache = S.init_cache(cfg, num_pages=1 + max_batch * P, page_size=PAGE,
                         max_batch=max_batch)
    for kernels in (False, True):
        fns = S.make_step_fns(cfg, prefill_chunk=CHUNK,
                              sampling=SamplingParams(), kernels=kernels,
                              latent_kernel=False)
        jaxpr = jax.make_jaxpr(fns["prefill"])(
            params, *cache, np.zeros((1, CHUNK), np.int32),
            np.zeros((1, P), np.int32), np.int32(0), np.int32(CHUNK),
            jax.random.PRNGKey(0), np.uint32(0), np.int32(1))
        under = [(e.primitive.name, scope)
                 for e, scope in _scoped_eqns(jaxpr.jaxpr)
                 if "fx.gdn.core" in scope]
        assert under, "the prefill step opens no gdn.core scope"
        assert not [u for u in under if u[0] in _LOOPS]
        # (a scanned run of layers holds its body once)
        assert ("pallas_call" in [u[0] for u in under]) == kernels


@pytest.mark.parametrize("kernel", [False, True], ids=["xla", "pallas"])
def test_the_one_token_rule_is_the_recurrence_and_touches_live_rows_only(
        kernel):
    q, k, v, g, beta, _ = _rule_inputs(5, seed=3)
    buf = jax.random.normal(jax.random.PRNGKey(9), (2, 5, 4, 16, 16))
    live = jnp.array([True, False, True, True, False])
    o, new = GD.gdn_decode(buf, jnp.int32(1), q, k, v, jnp.exp(g), beta, live,
                           kernel=kernel)
    for b in range(5):
        want_o, want_s = _recurrence(q[b:b + 1], k[b:b + 1], v[b:b + 1],
                                     g[b:b + 1], beta[b:b + 1], buf[1, b])
        if bool(live[b]):
            np.testing.assert_allclose(o[b], want_o[0], atol=2e-6)
            np.testing.assert_allclose(new[1, b], want_s, atol=2e-6)
        else:       # the state to the bit, no output
            assert bool((new[1, b] == buf[1, b]).all())
            assert not bool(o[b].any())
    assert bool((new[0] == buf[0]).all())       # the other layer


# ------------------------------------------------- the latent decode kernel
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_the_latent_kernel_walks_what_the_gathered_view_reads(dtype):
    B, H, lanes, vw, ps, per = 4, 8, 256, 128, 16, 9
    ks = jax.random.split(jax.random.PRNGKey(0), 2)
    pool = jax.random.normal(ks[0], (2, 1 + B * per, ps, lanes)).astype(dtype)
    q = jax.random.normal(ks[1], (B, H, lanes)).astype(dtype)
    tables = jnp.asarray(1 + np.random.default_rng(0).permutation(
        B * per).reshape(B, per), jnp.int32)
    lens = jnp.array([5, -1, per * ps - 1, 70], jnp.int32)
    assert not LA.refusal(num_heads=H, lanes=lanes, value_width=vw,
                          page_size=ps, dtype=dtype)
    args = (q, pool, tables, lens, jnp.int32(1))
    got = LA.mla_paged_decode(*args, value_width=vw, scale=0.1)
    want = LA.gathered_decode(*args, value_width=vw, scale=0.1)
    np.testing.assert_allclose(got, want, atol=2e-5 if dtype == jnp.float32
                               else 2e-2)
    assert not bool(got[1].any())               # the inactive row
    assert LA.refusal(num_heads=H, lanes=128, value_width=16, page_size=4)
    assert LA.lanes_of(512 + 64) == 640
    assert LA.fold_pages(16, 640, 2660) == 16


# ---------------------------------------------------- program and reference
@pytest.mark.parametrize("prompt_len,new,kernels", [
    (3, 2, False),                  # less than a chunk, less than the taps
    (CHUNK, 3, False),              # the prompt is one chunk
    (2 * CHUNK + 5, 12, False),     # chunks, a ragged last one
    (4 * CHUNK + 1, 20, True),      # the Pallas kernels, interpreted
])
def test_prefill_then_decode_through_every_cache_is_the_reference_on_logits(
        built, prompt_len, new, kernels):
    """Prefill in chunks (the chunked rule from the slot's state, latent
    attention unabsorbed over the paged latents), then decode (the
    one-token rule in place, ABSORBED latent attention) = the reference's
    full forward (the recurrence, full keys and values), on logits."""
    cfg, params, w, sizes = built
    prompt = _prompt(prompt_len)
    toks, logits, _, stats = _serve(cfg, params, prompt, new,
                                    kernels=kernels)
    want = _reference_rows(w, sizes, toks)
    for i, got in enumerate(logits):
        np.testing.assert_allclose(got, want[prompt_len - 1 + i], atol=ATOL,
                                   rtol=0)
    layers = sum(n for kind, n in cfg.kinds().items() if kind.endswith("moe"))
    assert int(stats["rows"]) == 1 and layers == 4
    assert 0 <= float(stats["hit"]) == int(stats["pairs_held"]) \
        <= layers * cfg.num_experts_per_tok
    assert int(stats["passes"]) <= layers


def test_the_latent_kernel_serves_what_the_gathered_view_serves():
    """Widths the latent kernel admits (a 128-wide latent, pages of 8
    float32 rows): the kernel path's logits are the gathered view's and the
    reference's."""
    sizes = dict(toy.PUBLISHED, kv_lora_rank=128)
    cfg, params, w, sizes = _built(sizes, kv_lora_rank=128)
    assert not S.kernel_refusal(cfg, page_size=8)
    assert S.kernel_refusal(cfg, page_size=4)
    prompt = _prompt(21)
    runs = [_serve(cfg, params, prompt, 10, page=8, latent_kernel=on,
                   kernels=on) for on in (False, True)]
    assert runs[0][0] == runs[1][0]
    want = _reference_rows(w, sizes, runs[0][0])
    for a, b, at in zip(runs[0][1], runs[1][1], range(20, 31)):
        np.testing.assert_allclose(a, b, atol=ATOL, rtol=0)
        np.testing.assert_allclose(b, want[at], atol=ATOL, rtol=0)


def test_a_reused_slot_starts_from_a_zero_state_and_a_zero_tail(built):
    """A second request in a slot whose state, tail and pages a first one
    filled: its logits are those it gets in a fresh engine's slot — the
    first chunk reads zeros in place of what the slot holds."""
    cfg, params, _, _ = built
    first, second = _prompt(19, seed=1), _prompt(13, seed=2)
    _, _, used, _ = _serve(cfg, params, first, 6)
    assert float(jnp.abs(used[1][:, 1]).max()) > 0      # a state was left
    assert float(jnp.abs(used[2][:, :, 1].astype(jnp.float32)).max()) > 0
    toks_a, logits_a, _, _ = _serve(cfg, params, second, 6)
    toks_b, logits_b, _, _ = _serve(cfg, params, second, 6, cache=used)
    assert toks_a == toks_b
    for a, b in zip(logits_a, logits_b):
        np.testing.assert_array_equal(a, b)


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
    step must leave a prefilling slot's partial state alone —; every served
    token is the reference's best within float32's grain; each program
    compiled once; gauges and snapshot say what the caches are."""
    cfg, params, w, sizes = built
    eng = _engine(cfg, params)
    assert eng.family is registry.family("GDNMLAModule")
    assert len(eng.cache) == 3
    prompts = [_prompt(n) for n in (5, 29, 9, 26, 17)]
    reqs = [eng.submit(p, 12) for p in prompts[:2]]
    for _ in range(3):
        eng.step()
    reqs += [eng.submit(p, 12) for p in prompts[2:]]
    eng.run_until_drained()
    for req, prompt in zip(reqs, prompts):
        assert req.state == "finished" and len(req.tokens) == 12
        assert _widest_gap(w, sizes, prompt, req.tokens) < 1e-4
    assert eng._fns["decode"]._cache_size() == 1
    assert eng._fns["prefill"]._cache_size() == 1
    assert eng.allocator.allocated_pages == 0
    m, snap = eng.metrics, eng.serving_snapshot()
    state = int(eng.cache[1].nbytes + eng.cache[2].nbytes)
    assert m.gauge("serving_state_cache_bytes").value == state \
        == snap["serving_state_cache_bytes"]
    assert m.gauge("serving_latent_cache_bytes").value \
        == int(eng.cache[0].nbytes) == snap["serving_latent_cache_bytes"]
    assert m.gauge("serving_kv_cache_bytes").value == eng.cache_bytes \
        == state + int(eng.cache[0].nbytes)
    assert not schema.validate_serving_record(snap)
    assert snap["kv_folds"] == {}               # toy widths: gathered view
    for name in ("serving_state_cache_bytes", "serving_latent_cache_bytes",
                 "serving_kv_fold_pages_latent",
                 "serving_kv_fold_copies_latent"):
        assert name in schema.SERVING_METRIC_NAMES
    assert m.counter("serving_moe_passes_total").value > 0
    # a state's bytes follow the slots, never max_seq_len or the pool
    longer = _engine(cfg, params, max_seq_len=192, num_pages=120)
    assert longer.metrics.gauge("serving_state_cache_bytes").value == state


def test_the_fold_gauge_says_how_the_latent_pool_is_fetched():
    cfg, params, _, _ = _built(dict(toy.PUBLISHED, kv_lora_rank=128),
                               kv_lora_rank=128)
    eng = _engine(cfg, params, page_size=8, num_pages=40)
    assert eng.paged_kernel_active
    assert eng.serving_snapshot()["kv_folds"] == {"latent": [8, 8]}
    assert eng.metrics.gauge("serving_kv_fold_pages_latent").value == 8
    assert eng.metrics.gauge("serving_kv_fold_pages_full").value == 0
    req = eng.submit(_prompt(11), 5)
    eng.run_until_drained()
    assert len(req.tokens) == 5
    assert 0 < eng.metrics.gauge("serving_page_walk_share").value <= 1


def test_a_preempted_request_resumes_to_the_same_tokens(built):
    """A pool too small for three growing requests preempts the youngest:
    its pages are freed, its state and tail are whatever they are; it is
    prefilled again from its first token (which rebuilds the state from
    zero) and serves the tokens an unpressed engine serves — each the
    reference's best."""
    cfg, params, w, sizes = built
    prompts = [_prompt(n, seed=11 + n) for n in (9, 10, 11)]

    def run(num_pages):
        eng = _engine(cfg, params)
        eng.allocator = type(eng.allocator)(num_pages, PAGE)
        reqs = [eng.submit(p, 24) for p in prompts]
        eng.run_until_drained()
        return reqs

    calm, pressed = run(60), run(18)
    assert sum(r.preemptions for r in calm) == 0
    assert sum(r.preemptions for r in pressed) > 0
    for a, b, prompt in zip(calm, pressed, prompts):
        assert a.tokens == b.tokens and len(b.tokens) == 24
        assert _widest_gap(w, sizes, prompt, b.tokens) < 1e-4


# ------------------------------------------------- the assumed readings
#: each assumed reading, taken another way in the PROGRAM alone: through
#: the published key where one switches it, else by the other reading in
#: place of the one function of ``models/gdn_mla/model.py`` that holds it
OTHER_READINGS = {
    "no attention gate": ({"gated_attention": False}, None, None),
    "no clamp": ({"swiglu_limit": 0}, None, None),
    "a norm scale of 1 + w": ({}, "norm_scale", lambda w, cfg: 1.0 + w),
    "SiLU for the output gate": ({}, "output_gate",
                                 lambda z, cfg: jax.nn.silu(z)),
}


@pytest.mark.parametrize("reading", sorted(OTHER_READINGS))
def test_the_comparison_sees_each_assumed_reading(built, monkeypatch,
                                                  reading):
    """With one assumed reading taken otherwise in the program (no
    attention gate, no clamp, a norm scale of 1 + w, SiLU for the output
    gate) its logits leave the reference's by far more than the sound
    program's 2e-5 allows."""
    _, _, w, sizes = built
    keys, name, other = OTHER_READINGS[reading]
    if name:
        monkeypatch.setattr(M, name, other)
    cfg = config_from_dict(toy.model_section(**keys))
    spec = ref.weight_spec(sizes)
    named = {k: v for k, v in w.items()
             if cfg.gated_attention or k.split("_", 1)[-1] != "gate"}
    paths = {k: v for k, v in toy.param_paths(spec).items() if k in named}
    params = weights.to_program_tree(named, paths, M.served_template(cfg))
    prompt = _prompt(13)
    toks, logits, _, _ = _serve(cfg, params, prompt, 4)
    want = _reference_rows(w, sizes, toks)
    worst = max(float(np.abs(got - want[12 + i]).max())
                for i, got in enumerate(logits))
    assert worst > 50 * ATOL, (reading, worst)


# ---------------------------------------------------------------- the share
def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """Two shares of 8 experts (the router 16 wide in both), the shared
    expert counted ONCE: the sum of what each share's held experts add is
    what the uncut reference layer's 16 experts add."""
    sizes = dict(toy.PUBLISHED)
    h, f = sizes["hidden_size"], sizes["moe_intermediate_size"]
    ks = jax.random.split(jax.random.PRNGKey(4), 8)
    draw = lambda k, *s: 0.3 * jax.random.normal(k, s)  # noqa: E731
    full = {"router": draw(ks[0], h, 16), "bias": draw(ks[1], 16),
            "e_gate": draw(ks[2], 16, h, f), "e_up": draw(ks[3], 16, h, f),
            "e_down": draw(ks[4], 16, f, h), "s_gate": draw(ks[5], h, f),
            "s_up": draw(ks[6], h, f), "s_down": draw(ks[7], f, h)}
    v = jax.random.normal(jax.random.PRNGKey(5), (24, h))
    uncut = ref._experts(v, full, dict(sizes, n_routed_experts=16,
                                       first_expert_held=0), "float32")
    shared = ref._gated_mlp(v, full["s_gate"], full["s_up"], full["s_down"],
                            sizes, "float32")
    total = jnp.zeros_like(v)
    cfgs = [config_from_dict(toy.model_section(first_expert_held=lo))
            for lo in (0, 8)]
    from fleetx_tpu.models.mla_moe import moe as held_share
    from fleetx_tpu.models.swa_moe import model as shared_model

    for cfg in cfgs:
        lo = cfg.first_expert_held
        moe = {"experts_gate": full["e_gate"][None, lo:lo + 8],
               "experts_up": full["e_up"][None, lo:lo + 8],
               "experts_down": full["e_down"][None, lo:lo + 8]}
        ids, wts, _ = held_share.route(
            v, full["router"], full["bias"], cfg.num_experts_per_tok,
            cfg.routed_scaling_factor, cfg.norm_topk_prob)
        y, rows, _ = shared_model.held_experts(
            v, ids, wts, moe, 0, cfg, shared_model.pass_rows(cfg, 24),
            "moe_gmm_decode", glu=M.glu(cfg))
        total = total + y
        # ... and the reference given the same share agrees with it
        part = ref._experts(v, {**full, "e_gate": full["e_gate"][lo:lo + 8],
                                "e_up": full["e_up"][lo:lo + 8],
                                "e_down": full["e_down"][lo:lo + 8]},
                            dict(sizes, first_expert_held=lo), "float32")
        np.testing.assert_allclose(y + shared, part, atol=1e-5)
        assert int(rows.sum()) > 0
    np.testing.assert_allclose(total + shared, uncut, atol=1e-5)


# ------------------------------------------------------ recipe and the tree
def _recipe_cfg(overrides=()):
    from fleetx_tpu.utils import config as config_mod

    return config_mod.get_config(
        os.path.join(ROOT, SHIPPED["serve"]["recipe"]), list(overrides),
        num_devices=1)


def test_the_built_tree_is_4732_m_parameters_served_in_bfloat16():
    """The recipe's tree, leaf by leaf: ISSUE 42's count (4.73 B, 9.46 GB)
    and the configuration file's ``bytes``."""
    model_cfg, template = registry.served_template(_recipe_cfg())
    leaves = jax.tree.leaves(template)
    count = sum(int(np.prod(l.shape)) for l in leaves)
    nbytes = sum(int(np.prod(l.shape)) * l.dtype.itemsize for l in leaves)
    assert count == M.count_params(model_cfg) == 4_731_722_752 \
        == SHIPPED["bytes"]["parameters"]
    assert nbytes == 9_478_434_816 == SHIPPED["bytes"]["served_bytes"]
    part = lambda kind, group: sum(  # noqa: E731
        int(np.prod(l.shape)) for l in jax.tree.leaves(template[kind][group]))
    assert part("linear_dense", "mixer") == 235_864_320
    assert part("latent_moe", "mixer") == 159_844_352
    assert part("linear_dense", "mlp") == 396_361_728
    assert part("latent_moe", "moe") == 750_518_528
    assert model_cfg.kinds() == {"linear_dense": 1, "latent_moe": 1,
                                 "linear_moe": 3}
    f32 = {"/".join(str(getattr(p, "key", p)) for p in path)
           for path, l in jax.tree_util.tree_flatten_with_path(template)[0]
           if l.dtype == jnp.float32}
    assert "latent_moe/moe/router" in f32 and "final_norm/w" in f32 \
        and "linear_moe/mixer/A_log" in f32 \
        and "linear_moe/mixer/qkv" not in f32
    pool, state, tail = S.cache_shapes(model_cfg, num_pages=109376,
                                       page_size=16, max_batch=96)
    assert pool == (1, 109376, 16, 640) and state == (4, 96, 64, 128, 128) \
        and tail == (4, 3, 96, 16384)
    # a sequence's state a layer: the issue's 4,194,304 + 98,304 B
    assert 64 * 128 * 128 * 4 == 4_194_304 and 3 * 16384 * 2 == 98_304


@pytest.mark.parametrize("missing", [
    "full_attention_layers", "linear_conv_kernel_dim", "swiglu_limit",
    "layernorm_gating_weight", "kv_lora_rank"])
def test_a_recipe_that_omits_a_published_key_is_refused_by_name(missing):
    model = toy.model_section()
    del model[missing]
    with pytest.raises(ValueError, match=missing):
        config_from_dict(model)


def test_the_shipped_recipe_states_every_published_key_at_its_value():
    model = dict(_recipe_cfg()["Model"])
    for key in PUBLISHED_KEYS:
        assert key in model, key
        if key in SHIPPED and key != "n_routed_experts":
            got = model[key]
            assert (dict(got) if isinstance(got, dict) else
                    list(got) if isinstance(got, (list, tuple)) else got) \
                == SHIPPED[key], key
    assert model["n_routed_experts"] == SHIPPED["router_experts"] == 256
    assert model["experts_held"] == SHIPPED["n_routed_experts"] == 16
    with pytest.raises(AssertionError, match="norm_type"):
        config_from_dict(toy.model_section(norm_type="RMSNorm"))


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
             "Serving.max_seq_len=64", "Serving.prefill_chunk=8"]
    eng = serve_tool._build_engine(_recipe_cfg(over))
    assert isinstance(eng, ServingEngine)
    assert type(eng.family).__name__ == "GDNMLAFamily"
    req = eng.submit([1, 2, 3, 4, 5], 4)
    eng.run_until_drained()
    assert req.state == "finished" and len(req.tokens) == 4


def test_the_new_scopes_are_the_tables_and_add_no_host_span(built):
    """``gdn.proj`` / ``gdn.conv`` / ``gdn.core`` are in ``DEVICE_SCOPES``
    and in both compiled programs; the tick's host spans are what they
    were."""
    from fleetx_tpu.observability import trace

    assert {"gdn.proj", "gdn.conv", "gdn.core"} <= set(trace.DEVICE_SCOPES)
    assert not [s for s in trace.HOT_LOOP_SPANS if "gdn" in s or "state" in s]
    cfg, params, _, _ = built
    fns = S.make_step_fns(cfg, prefill_chunk=CHUNK,
                          sampling=SamplingParams())
    cache = S.init_cache(cfg, num_pages=9, page_size=PAGE, max_batch=2)
    key = jax.random.PRNGKey(0)
    text = fns["decode"].lower(
        params, *cache, np.zeros((2,), np.int32), np.int32(-1),
        np.zeros((1,), np.int32), np.zeros((2, 8), np.int32),
        np.zeros((2,), np.int32), key, np.uint32(0)).compile().as_text()
    scopes = {s for s, _ in trace.device_scope_table(text).values()}
    assert {"gdn.proj", "gdn.conv", "gdn.core", "attn.core", "attn.cache",
            "moe.experts", "moe.route", "norm", "head"} <= scopes
