#!/usr/bin/env python
"""Deployment entry point: one engine pod of the chart's topology.

Runs a MiniEngine whose KV events ride a ZMQ PUB socket to the indexer
service (``deploy/chart`` wires the same triangle: indexer + engine pods +
evictor over a shared store). Work arrives through a file-based control
directory so the pod is drivable from shell scripts and the multi-process
cluster test (tests/test_cluster_e2e.py) without an HTTP stack:

    <control>/<name>.req.json   {"request_id": "...", "prompt": [ints],
                                 "max_new_tokens": N}
    <control>/<name>.out.json   {"request_id": "...", "output": [ints]}

The pod writes ``<control>/<pod-id>.ready`` once serving. SIGTERM exits.

One process per chip: a pod takes the accelerator JAX finds and holds it
for its lifetime, so a host runs as many pods as it has chips (with
``JAX_PLATFORMS=cpu`` any number run on the CPU, as the cluster test's
three do). It starts no children that need a device. Compiled programs go
to the persistent cache (``JAX_COMPILATION_CACHE_DIR`` if set, else
``<checkout>/.jax_cache``), so a restarted pod does not recompile.

``--admin-port`` (off by default; ``auto`` = ephemeral) starts the stdlib
admin endpoint with the engine-telemetry debug section (``/metrics``,
``/debug/vars`` → ``engine``, and — when ``--profile-dir`` is set —
``/debug/profile?duration_s=N``). The bound port is written to
``<control>/<pod-id>.admin_port`` so tests and ``hack/kvdiag.py`` can find
it.

Usage:
  python examples/engine_pod_main.py --pod-id pod-0 \
      --zmq-endpoint tcp://127.0.0.1:5557 --control-dir /tmp/ctl \
      [--offload-root /mnt/kv-store] [--model-name tiny] \
      [--admin-port auto] [--profile-dir /tmp/xplane]
"""

import argparse
import json
import os
import pathlib
import signal
import time

from llmd_kv_cache_tpu.events.publisher import KVEventPublisher
from llmd_kv_cache_tpu.models.engine import EngineConfig, MiniEngine
from llmd_kv_cache_tpu.models.llama import LlamaConfig
from llmd_kv_cache_tpu.offload.spec import SharedStorageOffloadSpec
from llmd_kv_cache_tpu.services.admin import AdminServer
from llmd_kv_cache_tpu.telemetry import EngineTelemetryConfig
from llmd_kv_cache_tpu.utils.compile_cache import enable_compile_cache
from llmd_kv_cache_tpu.utils.logging import configure_from_env


def main() -> None:
    configure_from_env()
    parser = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    parser.add_argument("--pod-id", required=True)
    parser.add_argument("--zmq-endpoint", required=True)
    parser.add_argument("--control-dir", required=True)
    parser.add_argument("--model-name", default="tiny")
    parser.add_argument("--offload-root", default=None)
    parser.add_argument("--role", default="both",
                        choices=["both", "prefill", "decode"],
                        help="disaggregated serving role: 'prefill' pods "
                             "commit each chunk's KV to the shared store "
                             "and stop at first token; 'decode' pods pull "
                             "transferred prefixes via the restore path. "
                             "Non-default roles require --offload-root.")
    parser.add_argument("--admin-port", default="0",
                        help='admin/metrics endpoint: "0" = off (default), '
                             '"auto" = ephemeral port, else a port number')
    parser.add_argument("--profile-dir", default="",
                        help="enable /debug/profile, writing jax.profiler "
                             "xplane captures here")
    parser.add_argument("--span-export", action="store_true",
                        help="fleet telemetry: record finished spans "
                             "(process identity = the pod id) into a ring "
                             "served at /debug/spans on --admin-port for "
                             "the telemetry collector to pull")
    parser.add_argument("--pyprof", action="store_true",
                        help="continuous profiling: always-on sampling "
                             "profiler serving folded stacks at "
                             "/debug/pyprof (+ /debug/pyprof/capture) on "
                             "--admin-port")
    parser.add_argument("--pyprof-hz", type=float, default=67.0,
                        help="sampling rate for --pyprof (default 67)")
    parser.add_argument("--pyprof-window-s", type=float, default=10.0,
                        help="profile window length for --pyprof "
                             "(default 10s)")
    parser.add_argument("--workingset", action="store_true",
                        help="working-set analytics: sample block reuse "
                             "on admission/eviction/offload and serve "
                             "reuse windows at /debug/workingset on "
                             "--admin-port for the collector's what-if "
                             "capacity table")
    parser.add_argument("--workingset-sample-rate", type=float, default=0.05,
                        help="spatial sampling rate for --workingset "
                             "(default 0.05)")
    parser.add_argument("--workingset-window-s", type=float, default=10.0,
                        help="window length for --workingset (default 10s)")
    parser.add_argument("--audit", action="store_true",
                        help="ground-truth audit: record every request's "
                             "realized prefix outcome (HBM hit vs restored "
                             "vs recomputed blocks) in a ring served at "
                             "/debug/audit on --admin-port for the "
                             "collector's score-vs-reality join; requests "
                             "may carry the prediction they were routed on "
                             "via a 'feedback' object in the req.json")
    parser.add_argument("--audit-max-records", type=int, default=2048,
                        help="audit ring depth for --audit (default 2048)")
    args = parser.parse_args()

    enable_compile_cache()
    cfg = LlamaConfig.tiny()
    publisher = KVEventPublisher(
        args.zmq_endpoint, pod_identifier=args.pod_id,
        model_name=args.model_name, bind=False,
    )
    spec = None
    if args.offload_root:
        spec = SharedStorageOffloadSpec(
            root=args.offload_root, model_name=args.model_name,
            page_size=cfg.page_size, num_layers=cfg.num_layers,
            kv_heads=cfg.num_kv_heads, head_dim=cfg.head_dim,
            io_threads=2, parallel_agnostic=True,
        )
    if args.role != "both" and spec is None:
        parser.error(f"--role {args.role} requires --offload-root (the "
                     "handoff moves KV through the shared store)")
    engine = MiniEngine(
        EngineConfig(
            model=cfg, num_pages=64, max_pages_per_seq=16,
            model_name=args.model_name, pod_identifier=args.pod_id,
            role=args.role,
            telemetry=EngineTelemetryConfig(profile_dir=args.profile_dir),
        ),
        event_sink=publisher.publish,
        offload_spec=spec,
        seed=0,  # all pods share deterministic params: cross-pod
        #         storage restores must be bit-exact resumable
    )
    handoff = None
    if args.role != "both":
        # Local coordinator: feeds the kvtpu_handoff_* metrics and, on a
        # prefill pod, streams chunk commits. Cross-pod availability rides
        # the store's own BlockStored advertisements in this file-driven
        # deployment shim.
        from llmd_kv_cache_tpu.offload.handoff import HandoffCoordinator

        handoff = HandoffCoordinator()
        engine.attach_handoff(handoff)

    control = pathlib.Path(args.control_dir)
    control.mkdir(parents=True, exist_ok=True)

    running = [True]
    signal.signal(signal.SIGTERM, lambda *_: running.__setitem__(0, False))

    admin = None
    if args.admin_port != "0":
        port = 0 if args.admin_port == "auto" else int(args.admin_port)
        admin = AdminServer(port=port, expose_debug=True)
        if engine.telemetry is not None:
            engine.telemetry.attach_admin(admin)
        if args.span_export:
            from llmd_kv_cache_tpu.telemetry import (
                FleetTelemetryConfig,
                enable_span_export,
            )

            source = enable_span_export(
                FleetTelemetryConfig(span_export=True),
                default_identity=args.pod_id)
            if source is not None:
                admin.register_spans_source(source)
        if args.pyprof:
            from llmd_kv_cache_tpu.telemetry import (
                FleetTelemetryConfig,
                SamplingProfilerConfig,
                enable_pyprof,
            )

            pyprof = enable_pyprof(
                FleetTelemetryConfig(
                    pyprof=SamplingProfilerConfig(
                        enabled=True, hz=args.pyprof_hz,
                        window_s=args.pyprof_window_s)),
                default_identity=args.pod_id)
            if pyprof is not None:
                prof_source, prof_capture = pyprof
                admin.register_pyprof_source(prof_source)
                admin.register_pyprof_capture(prof_capture)
        if args.workingset:
            from llmd_kv_cache_tpu.telemetry import (
                FleetTelemetryConfig,
                WorkingSetConfig,
                enable_workingset,
            )

            tracker = enable_workingset(
                FleetTelemetryConfig(
                    workingset=WorkingSetConfig(
                        enabled=True,
                        sample_rate=args.workingset_sample_rate,
                        window_s=args.workingset_window_s)),
                default_identity=args.pod_id)
            if tracker is not None:
                engine.attach_workingset(tracker)
                admin.register_workingset_source(tracker.export_since)
        if args.audit:
            from llmd_kv_cache_tpu.telemetry.audit import AuditLog

            audit_log = AuditLog(capacity=args.audit_max_records)
            engine.attach_audit(audit_log)
            admin.register_audit_source(audit_log.export_since)
            admin.register_debug("audit_state", audit_log.debug_view)
        # Fleet-controller surface: /debug/role reports this pod's
        # serving role plus the handoff coordinator's residency/
        # starvation stats; POST /debug/role?set=<role> re-roles the
        # engine (guarded — only wired because this entry point opts in);
        # POST /debug/drain runs the PR 4 graceful drain.
        def role_view() -> dict:
            view = {"pod": args.pod_id, "role": engine.cfg.role}
            if handoff is not None:
                view["starvation"] = handoff.starvation()
            return view

        def set_role(params) -> dict:
            role = params.get("set", "")
            previous = engine.set_role(role)  # ValueError → HTTP 400
            return {"ok": True, "pod": args.pod_id, "role": role,
                    "previous": previous}

        admin.register_debug("role", role_view)
        admin.register_action("role", set_role)

        from llmd_kv_cache_tpu.recovery.drain import DrainCoordinator

        drainer = DrainCoordinator(
            intake_stoppers=[lambda: running.__setitem__(0, False)],
            offload=getattr(engine, "offload_manager", None),
        )

        def drain_action(params) -> dict:
            if "deadline_s" in params:
                drainer.deadline_s = float(params["deadline_s"])
            return drainer.drain()

        admin.register_action("drain", drain_action)
        admin.start()
        (control / f"{args.pod_id}.admin_port").write_text(str(admin.port))

    # Warm the tiny model (first jit), then declare readiness.
    engine.generate(f"{args.pod_id}-warm", [1, 2, 3, 4], max_new_tokens=1)
    (control / f"{args.pod_id}.ready").write_text("ok")

    served = set()
    while running[0]:
        for req_file in sorted(control.glob(f"{args.pod_id}.*.req.json")):
            if req_file.name in served:
                continue
            served.add(req_file.name)
            req = json.loads(req_file.read_text())
            max_new = req.get("max_new_tokens", 4)
            if args.role == "prefill":
                # Prefill pods never decode: the request ends at the
                # bootstrap token, its KV committed to the shared store.
                max_new = 1
            if "traceparent" in req or "feedback" in req:
                # Audit-plane path: carry the routing prediction (and the
                # scorer's trace) onto the realized-outcome record.
                fb = None
                fb_dict = req.get("feedback")
                if fb_dict:
                    from llmd_kv_cache_tpu.services.indexer_service import (
                        ScoreFeedback,
                    )

                    fb = ScoreFeedback(
                        traceparent=fb_dict.get("traceparent", ""),
                        chosen_pod=fb_dict.get("chosen_pod", ""),
                        predicted_blocks=float(
                            fb_dict.get("predicted_blocks", 0.0)),
                        total_blocks=int(fb_dict.get("total_blocks", 0)),
                        scores=dict(fb_dict.get("scores", {})),
                        residency=dict(fb_dict.get("residency", {})),
                        staleness_s=float(fb_dict.get("staleness_s", 0.0)),
                    )
                req_obj = engine.enqueue(
                    req["request_id"], req["prompt"],
                    max_new_tokens=max_new,
                    traceparent=req.get("traceparent"),
                    feedback=fb,
                )
                while not req_obj.done:
                    engine.step()
                out = req_obj.output
            else:
                out = engine.generate(
                    req["request_id"], req["prompt"],
                    max_new_tokens=max_new,
                )
            if spec is not None:
                engine.flush_offload()
            # Atomic publish: readers poll for the .out.json name, so it
            # must never be observable half-written.
            out_file = req_file.with_suffix("").with_suffix(".out.json")
            tmp_file = out_file.with_suffix(".tmp")
            tmp_file.write_text(json.dumps(
                {"request_id": req["request_id"], "output": out}))
            os.replace(tmp_file, out_file)
        time.sleep(0.05)

    if admin is not None:
        admin.stop()


if __name__ == "__main__":
    main()
