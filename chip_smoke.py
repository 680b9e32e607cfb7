#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

Drives the main path once through the entry points a user calls, at the full
width of GPT-345M (24 x 1024, 16 heads, vocab 50304, bf16 compute) with
random weights from a seed:

- *train*: ``tools/train.py`` on the shipped synthetic recipe — five steps at
  seq 1024, 8 sequences per chip on every chip the machine shows;
- *serve*: ``tools/serve.py`` on the shipped serving recipe — four requests
  over the JSON-lines socket (one prompt longer than ``prefill_chunk``, two
  in flight together, one prompt sent twice), the ``stats`` verb, SIGTERM.

This parent is stdlib-only and never imports JAX: a chip belongs to one
process at a time, so the two children run one after the other and the
parent learns the device from the trainer's own ``devices:`` log line. It
fails — exit code 1, no result line — when the platform is not ``tpu``, a
child fails, a loss is not finite or the first is not ln(50304) +- 0.4, a
request is not answered at its asked length, the repeated greedy prompt
differs, the compiled train step lacks a Mosaic kernel the recipe turns on,
or decode did not take the paged kernel. Otherwise the last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

Needs no network, no git and no file that git would not commit; where
``JAX_COMPILATION_CACHE_DIR`` is set the children inherit it, else they keep
their compile cache at ``<checkout>/.jax_cache`` (``utils/env.py``).
"""

import json
import math
import os
import random
import re
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
CONFIGS = os.path.join(ROOT, "fleetx_tpu", "configs", "nlp", "gpt")
TRAIN_CMD = [sys.executable, os.path.join(ROOT, "tools", "train.py"),
             "-c", os.path.join(CONFIGS, "pretrain_gpt_345M_synthetic.yaml"),
             "-o", "Engine.max_steps=5"]
SERVE_CMD = [sys.executable, os.path.join(ROOT, "tools", "serve.py"),
             "-c", os.path.join(CONFIGS, "serving_gpt_345M.yaml")]

PLATFORM = "tpu"
VOCAB = 50304
TRAIN_STEPS = 5
TRAIN_KERNELS = ("flash_fwd", "flash_bwd_fused", "fused_norm_fwd",
                 "fused_norm_bwd")
DRAIN_CODE = 75          # tools/serve.py --preemption-code default
EOS = 50256              # serving_gpt_345M.yaml Generation.eos_token_id
TRAIN_TIMEOUT_S = 600.0
READY_TIMEOUT_S = 300.0
REQUEST_TIMEOUT_S = 400.0  # the first requests pay both serving compiles

DEVICES_RE = re.compile(r"devices: (\d+) x (\w+) \((.*)\)")
LOSS_RE = re.compile(r"\[train\] global step (\d+),.* loss: ([-\w.]+),")
COMPILE_RE = re.compile(
    r"compiled (.+?) in ([\d.]+)s; Mosaic kernels: (\{.*\})")
PLACEMENT_RE = re.compile(r"placement: (\[.*\])")


class SmokeFailure(Exception):
    """One phase failed; the message says which check and why."""


def check(ok: bool, why: str) -> None:
    if not ok:
        raise SmokeFailure(why)


def stop(proc: subprocess.Popen) -> None:
    """Kill a child and everything it started (it leads its own session)."""
    if proc.poll() is None:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
    proc.wait()


def child_env() -> dict:
    env = dict(os.environ)
    env["PYTHONUNBUFFERED"] = "1"
    return env


def parse_device(line: str):
    """``(platform, kind, count)`` from a child's ``devices:`` log line."""
    m = DEVICES_RE.search(line)
    return (m.group(2), m.group(3), int(m.group(1))) if m else None


def compiles(lines: list) -> dict:
    """``{program: (seconds, kernels)}`` from the ``compiled ...`` lines."""
    out = {}
    for line in lines:
        m = COMPILE_RE.search(line)
        if m:
            out[m.group(1)] = (float(m.group(2)), json.loads(m.group(3)))
    return out


# --------------------------------------------------------------------- train

def run_train() -> dict:
    """Five steps through ``tools/train.py``; the device, losses, kernels,
    compile seconds and per-device placement its log reports."""
    proc = subprocess.Popen(TRAIN_CMD, cwd=ROOT, env=child_env(),
                            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                            text=True, start_new_session=True)
    timer = threading.Timer(TRAIN_TIMEOUT_S, stop, args=(proc,))
    timer.start()
    lines, device = [], None
    try:
        for line in proc.stdout:
            lines.append(line)
            if device is None and parse_device(line):
                device = parse_device(line)
                # a trainer that landed off the chip would grind through a
                # 345M step on the host for minutes: stop it at once
                check(device[0] == PLATFORM,
                      f"train child runs on platform {device[0]!r} "
                      f"({device[1]}), not {PLATFORM}")
        rc = proc.wait()
    finally:
        timer.cancel()
        stop(proc)
        sys.stdout.write("".join(lines[-200:]))
    check(rc == 0, f"train child exited {rc}")
    check(device is not None, "train child never logged its devices")

    losses = [float(m.group(2)) for m in map(LOSS_RE.search, lines) if m]
    check(len(losses) == TRAIN_STEPS,
          f"expected {TRAIN_STEPS} train losses, got {losses}")
    check(all(math.isfinite(x) for x in losses), f"non-finite loss {losses}")
    check(abs(losses[0] - math.log(VOCAB)) <= 0.4,
          f"first loss {losses[0]} is not ln({VOCAB}) = "
          f"{math.log(VOCAB):.2f} +- 0.4")

    step = compiles(lines).get("train step")
    check(step is not None, "train child never logged its compile")
    missing = [k for k in TRAIN_KERNELS if k not in step[1]]
    check(not missing, f"compiled train step lacks Mosaic kernels {missing}; "
                       f"found {step[1]}")
    placement = [m.group(1) for m in map(PLACEMENT_RE.search, lines) if m]
    check(bool(placement), "train child never logged its placement")
    return {"device": device, "losses": losses, "compile_s": step[0],
            "kernels": step[1], "placement": json.loads(placement[-1])}


# --------------------------------------------------------------------- serve

def ask(port: int, payload: dict) -> dict:
    """One JSON line out, one back (``serving/server.py`` wire protocol)."""
    with socket.create_connection(("127.0.0.1", port),
                                  timeout=REQUEST_TIMEOUT_S) as conn:
        conn.sendall((json.dumps(payload) + "\n").encode())
        buf = b""
        while not buf.endswith(b"\n"):
            chunk = conn.recv(65536)
            if not chunk:
                break
            buf += chunk
    check(bool(buf.strip()), f"no answer to {payload.get('id') or payload}")
    return json.loads(buf)


def answered(resp: dict, asked: int) -> list:
    """The tokens of a request answered at its asked length (greedy decode
    stops early only on the end-of-sequence token)."""
    check("error" not in resp, f"request refused: {resp}")
    tokens = resp.get("tokens") or []
    check(len(tokens) == asked or (tokens and tokens[-1] == EOS),
          f"request {resp.get('id')} asked {asked} tokens, got {tokens}")
    return tokens


def run_serve() -> dict:
    """One replica through ``tools/serve.py``: requests, stats, SIGTERM."""
    rng = random.Random(0)
    short = [rng.randrange(VOCAB) for _ in range(12)]
    long = [rng.randrange(VOCAB) for _ in range(200)]  # > prefill_chunk 128
    with tempfile.TemporaryDirectory() as tmp:
        ready, log_path = os.path.join(tmp, "ready.json"), \
            os.path.join(tmp, "serve.log")
        with open(log_path, "w") as log:
            proc = subprocess.Popen(
                SERVE_CMD + ["--ready-file", ready], cwd=ROOT,
                env=child_env(), stdout=log, stderr=subprocess.STDOUT,
                start_new_session=True)
        try:
            deadline = time.monotonic() + READY_TIMEOUT_S
            port = None
            while port is None:
                check(proc.poll() is None,
                      f"serve child exited {proc.returncode} before ready")
                check(time.monotonic() < deadline, "serve child never ready")
                time.sleep(0.2)
                try:
                    with open(ready) as f:
                        port = json.load(f)["port"]
                except (OSError, ValueError):
                    pass  # not there yet, or caught mid-write

            # two in flight together: the long prompt prefills in two chunks
            # while the short one joins the decode batch
            answers = {}

            def send(rid, prompt, n):
                try:
                    answers[rid] = ask(port, {"id": rid, "prompt": prompt,
                                              "max_new_tokens": n})
                except (OSError, ValueError, SmokeFailure) as e:
                    answers[rid] = {"id": rid, "error": repr(e)}

            pair = [threading.Thread(target=send, args=("long", long, 16)),
                    threading.Thread(target=send, args=("short", short, 8))]
            for t in pair:
                t.start()
            for t in pair:
                t.join(REQUEST_TIMEOUT_S + 5)
                check(not t.is_alive(), "a request never returned")
            send("short-again", short, 8)
            send("tail", short[:3], 4)
            tokens = {"long": answered(answers["long"], 16),
                      "short": answered(answers["short"], 8),
                      "short-again": answered(answers["short-again"], 8),
                      "tail": answered(answers["tail"], 4)}
            check(tokens["short"] == tokens["short-again"],
                  f"the same greedy prompt answered {tokens['short']} then "
                  f"{tokens['short-again']}")
            stats = ask(port, {"verb": "stats"})
            check(stats.get("decode_path") == "paged_kernel",
                  f"stats decode_path is {stats.get('decode_path')!r}, "
                  f"not paged_kernel")
            proc.send_signal(signal.SIGTERM)
            rc = proc.wait(timeout=120)
            check(rc == DRAIN_CODE,
                  f"serve child exited {rc} on SIGTERM, not {DRAIN_CODE}")
        finally:
            stop(proc)
            with open(log_path) as f:
                lines = f.readlines()
            sys.stdout.write("".join(lines[-200:]))
    device = next((d for d in map(parse_device, lines) if d), None)
    check(device is not None and device[0] == PLATFORM,
          f"serve child ran on {device}, not {PLATFORM}")
    progs = compiles(lines)
    check("serving prefill" in progs and "serving decode" in progs,
          f"serve child logged compiles {sorted(progs)}")
    check("paged_decode" in progs["serving decode"][1],
          f"decode program lacks the paged_decode kernel: "
          f"{progs['serving decode'][1]}")
    return {"tokens": tokens, "decode_path": stats["decode_path"],
            "compile_s": {k: v[0] for k, v in progs.items()},
            "kernels": progs["serving decode"][1]}


def main() -> int:
    try:
        check(os.path.exists(TRAIN_CMD[1]) and os.path.exists(SERVE_CMD[1]),
              f"{ROOT} holds chip_smoke.py without the program it checks")
        train = run_train()
        serve = run_serve()
    except (SmokeFailure, OSError, ValueError,
            subprocess.SubprocessError) as e:
        print(f"chip_smoke: FAILED: {e!r}", file=sys.stderr)
        return 1
    platform, kind, count = train["device"]
    print(f"platform: {platform}\ndevice_kind: {kind}\ndevice count: {count}")
    print(f"train losses: {train['losses']}")
    print(f"train step Mosaic kernels: {json.dumps(train['kernels'])}")
    print(f"train compile seconds: {train['compile_s']}")
    print("per-device peak HBM bytes (buffers in use, program "
          "temporaries reserved): "
          f"{[(d['peak_bytes'], d['peak_reserved_bytes']) for d in train['placement']]}")
    print(f"train placement: {json.dumps(train['placement'])}")
    print(f"serve tokens: {json.dumps(serve['tokens'])}")
    print(f"decode_path: {serve['decode_path']}")
    print(f"serve decode Mosaic kernels: {json.dumps(serve['kernels'])}")
    print(f"serve compile seconds: {json.dumps(serve['compile_s'])}")
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
