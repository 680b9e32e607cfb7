"""The program names its device work (``observability/trace.py``):
``DEVICE_SCOPES`` and ``device_scope`` in the models, the engines and the
serving programs; ``device_scope_table`` from a compiled program's text to
``(scope, direction)``; ``compiled_programs()`` behind ``log_compile``;
``device_scopes.json`` beside an operator's trace. Toy widths, CPU."""

import ast
import gc
import glob
import json
import logging
import os
import sys

import jax
import jax.numpy as jnp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import device_scope_programs as programs  # noqa: E402
from fleetx_tpu.observability import trace  # noqa: E402
from fleetx_tpu.observability.trace import (DEVICE_SCOPES,  # noqa: E402
                                            device_scope, device_scope_table,
                                            hlo_instructions, scope_of)
from fleetx_tpu.utils import env  # noqa: E402

HEAVY = ("dot", "convolution", "custom-call", "fusion")


# ------------------------------------------------------------- the vocabulary
def _package_calls():
    """``(file, function name, first argument)`` of every call in the
    package whose callee is named ``device_scope`` or ``named_scope``."""
    for dirpath, _, files in os.walk(os.path.join(REPO, "fleetx_tpu")):
        for fn in files:
            if not fn.endswith(".py"):
                continue
            path = os.path.join(dirpath, fn)
            with open(path, encoding="utf-8") as f:
                tree = ast.parse(f.read(), path)
            for node in ast.walk(tree):
                if not isinstance(node, ast.Call):
                    continue
                callee = getattr(node.func, "attr",
                                 getattr(node.func, "id", None))
                if callee in ("device_scope", "named_scope"):
                    arg = node.args[0] if node.args else None
                    yield (os.path.relpath(path, REPO), callee,
                           arg.value if isinstance(arg, ast.Constant)
                           else None)


def test_the_vocabulary_holds_both_ways():
    """Every scope the package opens is in the table, every name of the
    table is opened somewhere, and nothing but the helper calls
    ``jax.named_scope``."""
    calls = list(_package_calls())
    named = [c for c in calls if c[1] == "named_scope"]
    assert named == [("fleetx_tpu/observability/trace.py", "named_scope",
                      None)], named
    opened = {c[2] for c in calls if c[1] == "device_scope"}
    assert None not in opened, "a device_scope whose name is not a literal"
    assert opened == set(DEVICE_SCOPES), (
        opened - set(DEVICE_SCOPES), set(DEVICE_SCOPES) - opened)
    assert len(DEVICE_SCOPES) <= 25
    assert all(isinstance(what, str) and what
               for what in DEVICE_SCOPES.values())
    with pytest.raises(KeyError, match="optimizer_update"):
        device_scope("optimizer_update")


# ------------------------------------------------------------------ the parser
@pytest.fixture(scope="module")
def toy_step():
    """A step with a checkpointed scope under ``value_and_grad`` and an
    optimizer scope: ``{instruction: (opcode, op_name)}`` and the table."""
    @jax.checkpoint
    def block(w, x):
        with device_scope("mlp"):
            return jnp.tanh(x @ w)

    def loss(w, x):
        with device_scope("embed"):
            x = x * 2.0
        y = block(w, block(w, x))
        with device_scope("loss"):
            return (y ** 2).mean()

    def step(w, x, lr):
        value, grad = jax.value_and_grad(loss)(w, x)
        with device_scope("optimizer"):
            w = w - lr * grad
        return w, value + lr       # ``value + lr``: under no scope

    text = jax.jit(step).lower(jnp.ones((64, 64)), jnp.ones((8, 64)),
                               jnp.float32(0.1)).compile().as_text()
    rows = {name: (opcode, op_name)
            for name, opcode, op_name, _ in hlo_instructions(text)}
    return text, rows, device_scope_table(text)


@pytest.mark.parametrize("scope,direction,opcodes", [
    ("mlp", "fwd", ("dot",)),
    ("mlp", "bwd", ("dot",)),
    ("mlp", "remat", ("dot", "fusion")),
    ("optimizer", "fwd", ("fusion", "multiply", "subtract")),
    ("loss", "fwd", ("fusion", "reduce")),
    ("embed", "fwd", ("fusion", "multiply")),
])
def test_each_part_of_a_step_lands_where_it_belongs(toy_step, scope,
                                                    direction, opcodes):
    _, rows, table = toy_step
    found = [n for n, got in table.items() if got == (scope, direction)
             and rows[n][0] in opcodes]
    assert found, (scope, direction, sorted(set(table.values())))
    for name in found:
        assert f"fx.{scope}" in rows[name][1] or not rows[name][1]


def test_an_instruction_under_no_scope_is_unscoped(toy_step):
    _, rows, table = toy_step
    named = {n: op_name for n, (_, op_name) in rows.items()
             if "/" in op_name and "fx." not in op_name}
    assert named, "the toy step has an add outside every scope"
    assert all(table[n] == ("", "") for n in named)
    assert set(table.values()) <= {("", "")} | {
        (s, d) for s in DEVICE_SCOPES for d in trace.DIRECTIONS}


HAND_MADE = """HloModule jit_step, is_scheduled=true

%fused_computation (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  %inner.1 = f32[8]{0} tanh(%param_0), metadata={op_name="jit(step)/jvp(fx.mlp)/tanh"}
  ROOT %root.1 = f32[8]{0} negate(%inner.1), metadata={op_name="jit(step)/transpose(jvp(fx.attn.core))/neg"}
}

%region_add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %sum.9 = f32[] add(%a, %b), metadata={op_name="jit(step)/fx.loss/reduce_sum"}
}

%body (arg: (s32[], f32[8])) -> (s32[], f32[8]) {
  %arg = (s32[], f32[8]{0}) parameter(0)
  %gte.1 = f32[8]{0} get-tuple-element(%arg), index=1
  %copy-start.3 = (f32[8]{0:S(1)}, f32[8]{0}, u32[]) copy-start(%gte.1)
  %copy-done.3 = f32[8]{0:S(1)} copy-done(%copy-start.3)
  %fusion.7 = f32[8]{0} fusion(%copy-done.3), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(step)/transpose(jvp(fx.attn.core))/neg"}
  %inner_while.2 = f32[8]{0} call(%fusion.7), to_apply=%nested, metadata={op_name="jit(step)/fx.stack/call"}
  %tail.4 = f32[8]{0} copy(%inner_while.2)
  ROOT %tuple.5 = (s32[], f32[8]{0}) tuple(%gte.0, %tail.4)
}

%nested (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %deep.6 = f32[8]{0} exponential(%p), metadata={op_name="jit(step)/jvp(fx.stack)/while/body/checkpoint/rematted_computation/layers/fx.norm/exp"}
}

%cond (arg.1: (s32[], f32[8])) -> pred[] {
  %arg.1 = (s32[], f32[8]{0}) parameter(0)
  ROOT %lt.8 = pred[] compare(%arg.1), direction=LT, metadata={op_name="jit(step)/fx.stack/while/cond/lt"}
}

ENTRY %main.10 (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0), metadata={op_name="x"}
  %joined.11 = f32[8]{0} abs(%x), metadata={op_name="jit(step)/jit(_where)/select_n;jit(step)/transpose(jvp(fx.embed))/abs"}
  %reduce.12 = f32[] reduce(%joined.11), dimensions={0}, to_apply=%region_add, metadata={op_name="jit(step)/fx.loss/reduce_sum"}
  %while.13 = (s32[], f32[8]{0}) while(%joined.11), condition=%cond, body=%body, metadata={op_name="jit(step)/fx.stack/while"}
  %lonely.14 = f32[8]{0} copy(%x)
  ROOT %out.15 = f32[8]{0} add(%lonely.14, %lonely.14), metadata={op_name="jit(step)/add"}
}
"""


def test_the_table_of_a_hand_made_program():
    table = device_scope_table(HAND_MADE)
    # a fusion goes by its own name (its root's); its insides, and a
    # reducer's, are no instructions of the timeline
    assert table["fusion.7"] == ("attn.core", "bwd")
    assert not {"inner.1", "root.1", "sum.9", "a", "b"} & set(table)
    # while body, its condition and a call under it are walked, once
    assert table["lt.8"] == ("stack", "fwd")
    assert table["deep.6"] == ("norm", "remat")     # innermost; remat wins
    # of several names joined, the first that has a scope
    assert table["joined.11"] == ("embed", "bwd")
    # what the compiler made without a name goes where its user goes ...
    assert table["copy-start.3"] == table["copy-done.3"] == \
        ("attn.core", "bwd")
    # ... else where its operand came from; else nowhere. A name that is
    # no path of the program's (a parameter's) counts as none
    assert table["tail.4"] == ("stack", "fwd")
    assert table["x"] == ("embed", "bwd")
    assert table["lonely.14"] == ("", "")   # its user is named, unscoped
    assert table["out.15"] == ("", "")
    assert "%" not in "".join(table)


@pytest.mark.parametrize("op_name,want", [
    ("jit(step)/jvp(fx.mlp)/dot_general", ("mlp", "fwd")),
    ("jit(step)/transpose(jvp(GPT))/gpt/fx.stack/while/body/fx.attn.proj/"
     "transpose", ("attn.proj", "bwd")),
    ("jit(step)/transpose(jvp(M))/while/body/closed_call/checkpoint/"
     "rematted_computation/layers/fx.mlp/tanh", ("mlp", "remat")),
    ("jit(step)/fx.optimizer/mul", ("optimizer", "fwd")),
    ("jit(decode)/gpt/layers/attn/dot_general", ("", "")),
    ("", ("", "")),
])
def test_scope_of_an_op_name(op_name, want):
    assert scope_of(op_name) == want


# ------------------------------------------------- the programs carry scopes
def _scoped_share(text: str) -> float:
    table = device_scope_table(text)
    heavy = [name for name, opcode, _, _ in hlo_instructions(text)
             if opcode in HEAVY]
    assert len(heavy) > 10
    return sum(table[n] != ("", "") for n in heavy) / len(heavy)


@pytest.mark.parametrize("family", list(programs.TRAIN))
def test_a_train_steps_products_and_fusions_carry_a_scope(family, devices8):
    _, jitted, args = programs.TRAIN[family](devices8)
    text = jitted.lower(*args).compile().as_text()
    assert _scoped_share(text) >= 0.9
    got = set(device_scope_table(text).values())
    assert {("optimizer", "fwd"), ("embed", "bwd"), ("head", "fwd"),
            ("loss", "bwd"), ("attn.core", "bwd")} <= got
    if family != "gpt":                 # the recipes that recompute
        assert ("attn.core", "remat") in got


@pytest.mark.parametrize("family", list(programs.SERVE))
def test_a_serving_programs_products_and_fusions_carry_a_scope(family):
    for what, jitted, args in programs.SERVE[family]():
        text = jitted.lower(*args).compile().as_text()
        assert _scoped_share(text) >= 0.9, what
        got = {scope for scope, _ in device_scope_table(text).values()}
        assert {"embed", "attn.proj", "attn.core", "attn.cache", "head",
                "sample", "stack"} <= got, (what, got)
        if family == "phi4flash":       # no experts; its own scopes
            assert {"ssm.proj", "ssm.conv", "ssm.core", "gmu",
                    "attn.cross"} <= got, (what, got)
        elif family == "jamba2":        # no experts; the scan's inner norms
            assert {"ssm.proj", "ssm.conv", "ssm.core",
                    "ssm.norm"} <= got, (what, got)
        else:
            assert ("moe.route" in got) == (family != "gpt")
        assert {d for _, d in device_scope_table(text).values()} <= \
            {"fwd", ""}


# ------------------------------------------------------- compiled_programs()
def _reachable(root) -> list:
    seen, todo, out = set(), [root], []
    while todo:
        obj = todo.pop()
        if id(obj) in seen:
            continue
        seen.add(id(obj))
        out.append(obj)
        todo.extend(gc.get_referents(obj))
    return out


def _holds_device_state(objects: list) -> list:
    return [type(o).__name__ for o in objects
            if isinstance(o, (jax.Array, jax.stages.Compiled,
                              jax.stages.Lowered))
            or "Executable" in type(o).__name__]


def test_compiled_programs_outlives_the_programs_and_holds_no_device_state():
    def tiny_step(w, x):
        with device_scope("mlp"):
            return jnp.tanh(x @ w)

    jitted = jax.jit(tiny_step)
    args = (jnp.ones((16, 16)), jnp.ones((4, 16)))
    env.log_compile("a tiny step", jitted, *args)
    kept = trace._programs["jit_tiny_step"]
    assert isinstance(kept, list), "parsed before anyone asked"
    assert not _holds_device_state(_reachable(kept))
    del jitted, args
    gc.collect()
    jax.clear_caches()                  # benchmarks/run.py: free_program
    table = trace.compiled_programs()["jit_tiny_step"]
    assert ("mlp", "fwd") in set(table.values())
    assert not _holds_device_state(_reachable(trace._programs))
    assert isinstance(trace._programs["jit_tiny_step"], dict)
    assert trace.compiled_programs()["jit_tiny_step"] == table


def test_the_newest_program_of_a_name_replaces_the_older():
    def renamed(x, scope):
        with device_scope(scope):
            return jnp.sin(x) * 2.0

    before = len(trace._programs)
    for scope in ("norm", "head", "sample"):
        fn = jax.jit(lambda x, scope=scope: renamed(x, scope))
        fn.__wrapped__.__name__ = "again"       # jit_again, three times
        env.log_compile("the same name again", jax.jit(fn.__wrapped__),
                        jnp.ones((8,)))
    assert len(trace._programs) == before + 1
    scopes = {s for s, _ in trace.compiled_programs()["jit_again"].values()}
    assert scopes - {""} == {"sample"}


def test_log_compiles_line_is_the_one_the_benchmark_parses():
    from benchmarks.run import _CompileLines
    from fleetx_tpu.utils.log import logger

    lines, records = _CompileLines(), []
    listen = logging.Handler()
    listen.emit = records.append
    logger.addHandler(lines)
    logger.addHandler(listen)
    try:
        env.log_compile("serving decode", jax.jit(lambda x: x + 1),
                        jnp.ones((4,)))
    finally:
        logger.removeHandler(lines)
        logger.removeHandler(listen)
    assert list(lines.seconds) == ["serving decode"]
    assert lines.kernels == {"serving decode": {}}
    assert len(records) == 1, "one log line a compile"


def test_profiler_window_writes_the_tables_beside_its_trace(tmp_path):
    def windowed(x):
        with device_scope("norm"):
            return x / jnp.sqrt((x * x).mean())

    jitted = jax.jit(windowed)
    x = jnp.ones((32,))
    env.log_compile("a windowed program", jitted, x)
    window = trace.ProfilerWindow({"enable": True, "start_step": 0,
                                   "stop_step": 1,
                                   "output_dir": str(tmp_path)})
    assert window.maybe_start(0)
    out = jitted(x)
    assert window.maybe_stop(1, sync=out)
    pb = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                       "*.xplane.pb"))
    assert len(pb) == 1
    with open(os.path.join(os.path.dirname(pb[0]),
                           "device_scopes.json")) as f:
        written = json.load(f)
    assert ["norm", "fwd"] in written["jit_windowed"].values()
    assert set(written) == set(trace.compiled_programs())
