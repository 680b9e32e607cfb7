"""Serving entry point: replica or router (docs/serving.md).

One process = one role:

- **replica** (default): build the model from ``-c cfg.yaml``, run one
  ``ServingEngine`` behind the JSON-lines TCP front. SIGTERM/SIGINT latch
  the PR 4/6 preemption handler → the replica stops admitting, finishes
  every in-flight decode, flushes its serving metrics, and exits with
  ``--preemption-code`` so ``tools/supervise.py`` treats the reclaim as a
  clean stop (never a crash-restart)::

      python tools/supervise.py --max-restart 3 -- \
          python tools/serve.py -c serving_gpt_345M.yaml --port 9000

- **router** (``--router``): the stdlib-only front over N replicas
  (round-robin + least-outstanding, loss-free re-dispatch on replica
  crash or drain)::

      python tools/serve.py --router --port 8999 \
          --backends 127.0.0.1:9000,127.0.0.1:9001

Under a supervisor gang (``FLEETX_PROCESS_ID`` set) the replica offsets
its port by the member id so one command line can launch N replicas on
consecutive ports.
"""

import argparse
import json
import os
import signal
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def _build_engine(cfg: dict):
    """Config sections → a ready ``ServingEngine`` (params from the
    ``Serving.ckpt_dir`` checkpoint when given, else seeded init). The
    model family — config, seeded tree, caches and programs — is the
    registry's (``fleetx_tpu/serving/registry.py``, keyed on
    ``Model.module``); this function adds what a replica process brings:
    its mesh, a checkpoint, an adapter."""
    import jax

    from fleetx_tpu.core.engine.inference_engine import serving_mesh
    from fleetx_tpu.serving import registry

    model_cfg = registry.model_config(cfg)
    mesh = serving_mesh(cfg.get("Distributed"))
    serving = dict(cfg.get("Serving") or {})
    ckpt_dir = serving.get("ckpt_dir")
    if ckpt_dir:
        from fleetx_tpu.core.checkpoint import load_params

        # registry-sharded replica weights (parallel/rules.py): every
        # leaf restores DIRECTLY onto its partition-rule sharding (family
        # from the checkpoint meta) instead of a replicated host load —
        # the weight-side counterpart of the sharded KV pool, so a large
        # checkpoint loads on a mesh whose per-device HBM cannot hold
        # the full tree. An unsharded replica loads through a trivial
        # 1-device mesh: the registry specs collapse to replicated AND
        # the restore stays topology-free (a mesh-trained checkpoint's
        # stored sharding references devices this process lacks — without
        # a concrete target sharding Orbax refuses the cross-topology
        # restore)
        from fleetx_tpu.parallel.mesh import build_mesh
        from fleetx_tpu.parallel.rules import SpecLayout

        load_mesh = mesh if mesh is not None else \
            build_mesh({}, devices=jax.devices()[:1])
        params = load_params(
            str(ckpt_dir), mesh=load_mesh,
            layout=SpecLayout.from_dist_config(
                dict(cfg.get("Distributed") or {})))
    else:
        params = registry.init_params(cfg, model_cfg)
    if serving.get("adapter_dir"):
        # fine-tuned serving (docs/finetune.md): merge the LoRA adapter
        # artifact into the base weights — verified against the stamped
        # base digests + registry fingerprint, refused loudly on drift
        assert ckpt_dir, "Serving.adapter_dir requires Serving.ckpt_dir " \
                         "(the adapter's frozen base)"
        from fleetx_tpu.finetune.checkpoint import apply_adapter_checkpoint

        params = apply_adapter_checkpoint(params,
                                          str(serving["adapter_dir"]))
    return registry.build_engine(cfg, model_cfg, params, mesh=mesh)


def _run_replica(args, cfg: dict) -> int:
    """Replica role: engine + socket front + preemption-drain loop."""
    from fleetx_tpu.observability.flight import FlightRecorder, install
    from fleetx_tpu.observability import flight
    from fleetx_tpu.resilience.faults import FaultPlan, install_plan
    from fleetx_tpu.resilience.preemption import PreemptionHandler
    from fleetx_tpu.serving.server import ReplicaServer
    from fleetx_tpu.utils.log import logger

    flight_dir = os.environ.get("FLEETX_FLIGHT_DIR") or "./flight_recorder"
    install(FlightRecorder(flight_dir))

    plan = FaultPlan.from_cfg(
        dict((cfg.get("Resilience") or {}).get("faults") or {}))
    install_plan(plan)

    port = args.port
    member = os.environ.get("FLEETX_PROCESS_ID")
    if port and member:
        port += int(member)

    engine = _build_engine(cfg)
    server = ReplicaServer(engine, host=args.host, port=port,
                           fault_plan=plan if plan.armed else None)
    bound = server.start()
    if args.ready_file:
        with open(args.ready_file, "w") as f:
            json.dump({"pid": os.getpid(), "port": bound}, f)
    handler = PreemptionHandler()
    with handler.installed():
        try:
            server.run(preemption=handler)
        finally:
            server.close()
    if args.metrics_out:
        with open(args.metrics_out, "a") as f:
            f.write(json.dumps(engine.serving_snapshot()) + "\n")
    flight.dump("serving preemption drain")
    logger.warning("replica drained — exiting with preemption code %d",
                   args.preemption_code)
    return args.preemption_code


def main(argv=None) -> int:
    """CLI dispatch across the two roles."""
    ap = argparse.ArgumentParser(description="fleetx serving runtime")
    ap.add_argument("-c", "--config", help="YAML config (replica)")
    ap.add_argument("-o", "--override", action="append", default=[],
                    help="dotted config overrides")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=0,
                    help="listen port (0 = OS-assigned; offset by "
                         "FLEETX_PROCESS_ID under a supervisor gang)")
    ap.add_argument("--ready-file", default=None,
                    help="write {pid, port} JSON here once listening")
    ap.add_argument("--metrics-out", default=None,
                    help="append the final serving snapshot JSONL here")
    ap.add_argument("--preemption-code", type=int, default=75,
                    help="exit code after a graceful drain (match "
                         "tools/supervise.py --preemption-code)")
    ap.add_argument("--router", action="store_true",
                    help="run the request router instead of a replica")
    ap.add_argument("--backends", default=None,
                    help="router mode: comma-separated host:port replicas")
    ap.add_argument("--fleet-out", default=None,
                    help="router mode: append merged fleet snapshots "
                         "(FLEET_RECORD_SCHEMA JSONL) here")
    ap.add_argument("--poll-interval", type=float, default=1.0,
                    help="router mode: seconds between backend stats polls")
    args = ap.parse_args(argv)

    if args.router:
        from fleetx_tpu.serving.router import main as router_main

        if not args.backends:
            ap.error("--router requires --backends host:port,host:port")
        router_argv = ["--port", str(args.port), "--host", args.host,
                       "--backends", args.backends,
                       "--poll-interval", str(args.poll_interval)]
        if args.fleet_out:
            router_argv += ["--fleet-out", args.fleet_out]
        if args.config:
            # the Serving.router YAML block rides to the (stdlib-only)
            # router process as JSON — validated eagerly here so a bad
            # knob fails before the fleet front binds
            from fleetx_tpu.utils import config as config_mod

            cfg = config_mod.parse_config(args.config)
            config_mod.override_config(cfg, args.override)
            config_mod.process_serving_config(cfg)
            block = dict((cfg.get("Serving") or {}).get("router") or {})
            if block:
                router_argv += ["--router-config", json.dumps(block)]
        return router_main(router_argv)

    if not args.config:
        ap.error("replica mode requires -c config.yaml")
    from fleetx_tpu.utils import config as config_mod

    # parse + override only: the training post-processing (batch-size
    # derivations, LR math) has no meaning for a serving process — but the
    # Serving block itself (slo targets, trace knobs) validates eagerly so
    # a typo'd SLO key fails at launch, not at the first snapshot
    cfg = config_mod.parse_config(args.config)
    config_mod.override_config(cfg, args.override)
    config_mod.process_serving_config(cfg)
    from fleetx_tpu.utils import env as env_mod
    from fleetx_tpu.utils.check import check_config

    env_mod.init_compile_cache()
    check_config(cfg)
    return _run_replica(args, cfg)


if __name__ == "__main__":
    # die by default signal only until the preemption handler is installed;
    # afterwards SIGTERM means "drain gracefully"
    signal.signal(signal.SIGTERM, signal.SIG_DFL)
    sys.exit(main())
