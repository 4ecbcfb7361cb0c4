"""cake-tpu CLI entry point.

Capability parity with `cake-cli` (cake-cli/src/main.rs): parse args, build
the context, then either serve the REST API or run a one-shot generation.
There is no worker mode to dispatch — the reference's master/worker split
(main.rs:28-54) collapses into one SPMD process; `--mode worker` is accepted
and explained for compatibility.
"""

from __future__ import annotations

import logging
import os
import sys

# (nothing but the standard library: the process's first `import jax`
# is a phase of main's)
from cake_tpu.startup import STARTUP


def _serve_multihost(master, args) -> int:
    """Serve the REST API over a mesh that spans processes.

    Under multi-controller SPMD every process must dispatch each engine
    step, so the coordinator publishes one tiny op record per step over a
    TCP control channel and every other host replays it — the reference's
    master→worker request loop (worker.rs:289-303) re-expressed for a
    single SPMD program. Every host runs this same command; process roles
    come from jax.distributed (parallel/distributed.initialize)."""
    import jax

    from cake_tpu.api import start
    from cake_tpu.parallel.distributed import is_coordinator
    from cake_tpu.serve.control import (
        ControlClient, ControlServer, broadcast_control_address,
    )

    image_mode = master.llm is None
    if image_mode:
        # SD multi-host: Context.load_image_model sharded the whole
        # pipeline over a process-spanning ("dp",) mesh, so every
        # process must dispatch each generation's jit sequence. A
        # generation is deterministic from its request args (seed and
        # scheduler ride in them), so ONE op per request suffices:
        # the coordinator publishes the args, followers replay
        # master.generate_image with them (_run_image_follower).
        engine = None
    else:
        fwd = getattr(master.llm, "_forward_fn", None)
        if fwd is not None and getattr(fwd, "_dp", False):
            # dp x sp shards the SLOT axis over dp, so decode outputs
            # (logits/tokens) are dp-sharded — not fully addressable
            # per process, which the engine's multi-host fetch path
            # (replicated-logits localization) cannot consume
            raise ValueError(
                "dp x sp serving is single-host only (dp-sharded "
                "decode outputs are process-local); drop --dp or "
                "serve on one host")
        # every process builds the identical engine (the shared-cache
        # zeros allocation is a global computation, so construction
        # order matters and must match across hosts)
        with STARTUP.phase("engine"):
            engine = master.make_engine()
        if engine is None:
            raise ValueError(
                "this serving mode (an sp composition without an "
                "engine contract) has no multi-host step replay; "
                "serve it on one host")
        # the pre-fail capture must outlive the heartbeat stale window
        # (the monitor is exactly the late-arriving consumer)
        engine.fail_recs_ttl = args.heartbeat_timeout + 60.0
    # a model without a cross-process placement (no topology/tp/dp/sp)
    # runs entirely inside the coordinator: no step replay needed —
    # followers just idle on the control channel until the stop op,
    # preserving the pre-existing behavior for this configuration. An
    # sp-engined model (custom forward, no (plan, mesh)) IS
    # cross-process: its shard_maps span the global mesh, so every
    # process must replay each step op.
    replayed = (image_mode
                or getattr(master.llm, "parallel", None) is not None
                or getattr(master.llm, "_forward_fn", None) is not None)
    if is_coordinator():
        import os
        import secrets
        import signal
        import threading

        from cake_tpu.parallel.health import ServingHealth

        token = secrets.token_hex(16)
        adv = _advertised_host(args)
        try:
            control = ControlServer(jax.process_count() - 1, host=adv,
                                    token=token)
            bind_host = adv
        except OSError:
            # the advertised name may not be a bindable interface (NAT,
            # aliases); fall back to all interfaces — the token still
            # gates who can become a follower or see ops
            control = ControlServer(jax.process_count() - 1, token=token)
            bind_host = ""
        # failure detection (SURVEY §5): follower heartbeats feed the
        # serving health — a dead host 503s the API instead of letting
        # the next collective hang forever. Image mode serves through
        # the locked path (no engine to watch): no heartbeats, a dead
        # follower surfaces as the next generation's publish error.
        health = None
        hb_adv = ""
        if engine is not None:
            health = ServingHealth(engine,
                                   stall_after_s=args.stall_timeout)
            hb_addr = health.expect_workers(
                [f"proc{i}" for i in range(1, jax.process_count())],
                bind_host=bind_host,
                stale_after_s=args.heartbeat_timeout)
            hb_adv = f"{adv}:{hb_addr.rsplit(':', 1)[1]}"
        # fleet telemetry federation (obs/federation.py): followers
        # ship their metrics/events/applied-seq frames here; the
        # collector feeds /api/v1/fleet, ?host= event filters,
        # host-labeled /metrics families and cross-host timelines.
        # Token-gated with the SAME control secret — cluster members
        # only.
        collector = None
        tel_adv = ""
        tel_enabled, tel_interval = master.telemetry_settings()
        if tel_enabled:
            from cake_tpu.obs.federation import TelemetryCollector
            tel_kwargs = dict(
                token=token, control=control, local_host="proc0",
                stale_after_s=max(args.heartbeat_timeout,
                                  3 * tel_interval),
                max_hosts=max(8, 2 * jax.process_count()))
            try:
                collector = TelemetryCollector(host=bind_host,
                                               **tel_kwargs)
            except OSError:
                # same NAT/alias fallback the control bind takes
                collector = TelemetryCollector(**tel_kwargs)
            tel_adv = f"{adv}:{collector.port}"
        broadcast_control_address(
            f"{adv}:{control.port}|{token}|{hb_adv}|{tel_adv}")
        control.accept_followers()
        # (the collector reaches engine.telemetry — the cross-host
        # timeline merge — through ONE wiring site: ApiServer.__init__,
        # via start(collector=...) below)
        if image_mode:
            master.attach_image_control(control)
        elif replayed:
            engine.attach_control(control)

        done = threading.Event()

        def teardown():
            # ordering matters: stop (publishes the stop op) -> wait for
            # control-socket EOF (the follower's signal that it is about
            # to enter jax.distributed.shutdown()) -> enter our own
            # shutdown. The coordination service's shutdown BARRIER then
            # holds the leader service up until every follower has
            # finished disconnecting — so the leader can never die while
            # a follower is mid-disconnect (which would abort it from
            # its heartbeat thread).
            if done.is_set():
                return
            done.set()
            try:
                if health is not None:
                    health.close()
            except Exception:  # noqa: BLE001
                pass
            if engine is not None:
                engine.stop()
            if engine is None or not replayed:
                # image followers / idle followers never get a stop from
                # an engine; release them explicitly
                try:
                    control.publish({"op": "stop"})
                except Exception:  # noqa: BLE001
                    pass
            control.wait_closed()
            if collector is not None:
                # AFTER wait_closed: the stop op triggers each
                # follower's final exporter flush (terminal applied
                # seq -> lag drains to 0), and the control-socket EOF
                # proves that flush has been sent — only then stop
                # accepting frames
                try:
                    collector.close()
                except Exception:  # noqa: BLE001
                    pass
            control.close()
            _distributed_shutdown()

        def on_sigterm(signum, frame):
            # api.start (checkpoint mode) chains here AFTER its own
            # save_and_exit; exiting 0 replaces the default-handler death
            # that would strand the followers mid-heartbeat
            teardown()
            os._exit(0)

        try:
            signal.signal(signal.SIGTERM, on_sigterm)
        except ValueError:
            pass  # not the main thread; caller owns signals
        try:
            start(master, address=args.api, engine=engine,
                  checkpoint_path=args.checkpoint, health=health,
                  collector=collector,
                  announce=getattr(args, "router_announce", None),
                  announce_interval_s=args.announce_interval,
                  announce_token=os.environ.get("CAKE_ANNOUNCE_TOKEN"))
        finally:
            teardown()
    else:
        from cake_tpu.parallel.health import HeartbeatSender

        payload = broadcast_control_address(None)
        addr, _, rest = payload.partition("|")
        token, _, rest = rest.partition("|")
        hb_addr, _, tel_addr = rest.partition("|")
        client = ControlClient(addr, token=token or None)
        if getattr(args, "fault_plan", None):
            # follower-side chaos: control.recv rules fire in this
            # process (the plan string is identical on every host, so
            # the experiment stays reproducible)
            from cake_tpu.faults import build_injector
            client.faults = build_injector(args.fault_plan)
        proc_name = f"proc{jax.process_index()}"
        beat = (HeartbeatSender(hb_addr, proc_name)
                if hb_addr else None)
        # fleet telemetry exporter (obs/federation.py): the loop below
        # used to be an observability black hole — now this process's
        # metrics registry, event-bus events, step summaries, applied
        # control-op seq and a health snapshot ship to the
        # coordinator's collector every --telemetry-interval seconds
        exporter = None
        tel_enabled, tel_interval = master.telemetry_settings()
        if tel_enabled and tel_addr:
            from cake_tpu.obs.federation import TelemetryExporter

            def _health_snapshot(beat=beat):
                out = {}
                if beat is not None:
                    out["heartbeat_ok"] = beat.alive_within(
                        beat.worst_case_gap_s)
                return out

            exporter = TelemetryExporter(
                tel_addr, host=proc_name, token=token or None,
                interval_s=tel_interval,
                events=getattr(engine, "events", None)
                if engine is not None else None,
                flight=getattr(engine, "flight", None)
                if engine is not None else None,
                applied_seq=(
                    (lambda: engine.applied_op_seq)
                    if engine is not None else None),
                health_snapshot=_health_snapshot)
        try:
            if image_mode:
                _run_image_follower(master, client)
            else:
                # with a cross-process placement this replays every
                # engine step; without one no step ops ever arrive and
                # the loop just blocks until the coordinator's stop.
                # Liveness deadline: a coordinator that dies between
                # ops (no FIN) used to hang this process in recv()
                # forever — quiet intervals now re-check the heartbeat
                # channel (the monitor lives in the coordinator
                # process) and exit with a clear error when it is gone
                # the window must cover the sender's worst-case quiet
                # gap (a monitor blip parks the sender in a capped
                # backoff sleep — it is NOT evidence the coordinator
                # died), else the two features defeat each other
                hb_window = max(args.heartbeat_timeout,
                                beat.worst_case_gap_s
                                if beat is not None else 5.0)
                engine.run_follower_loop(
                    client,
                    op_timeout_s=hb_window if beat is not None else None,
                    liveness=(
                        (lambda: beat.alive_within(hb_window))
                        if beat is not None else None))
        finally:
            if exporter is not None:
                # flush the terminal frame (final applied seq -> the
                # coordinator's fleet lag drains to 0) BEFORE the
                # control-socket EOF below: the coordinator keeps its
                # collector open until that EOF arrives
                exporter.close(flush=True)
            if beat is not None:
                beat.close()
            # socket EOF first, THEN jax.distributed.shutdown() — this
            # order is load-bearing both ways: (a) the coordination
            # service has a shutdown BARRIER (a follower's shutdown()
            # blocks until the leader also enters shutdown), so closing
            # the socket after shutdown would mutual-wait with the
            # coordinator's wait_closed() and stall every clean exit;
            # (b) the same barrier is what keeps the leader service
            # alive until we are fully disconnected — EOF merely tells
            # the coordinator to enter the barrier, which then completes
            # only once we do too, so the leader can never die while we
            # are mid-disconnect
            client.close()
            _distributed_shutdown()
    return 0


def _run_image_follower(master, client) -> None:
    """Image-mode follower: replay whole-generation ops. A generation is
    deterministic from its request args (seed + scheduler ride in them),
    so executing master.generate_image with the coordinator's args
    dispatches the identical jit sequence — the SPMD analog of the
    reference's per-component SD workers (sd.rs:198-302)."""
    import logging as _logging

    from cake_tpu.args import ImageGenerationArgs
    log = _logging.getLogger(__name__)
    log.info("image follower: replaying generation ops")
    while True:
        op = client.recv()
        if op is None or op.get("op") == "stop":
            log.info("image follower: coordinator %s",
                     "stopped" if op else "closed the channel")
            return
        if op.get("op") != "image":
            log.error("image follower: unknown op %r", op.get("op"))
            continue
        try:
            master.generate_image(
                ImageGenerationArgs.from_json(op["args"]),
                lambda _pngs: None)
        except Exception:  # noqa: BLE001
            # a failed replay desyncs the SPMD dispatch; disconnecting
            # makes the coordinator's next publish fail loudly instead
            # of wedging a collective
            log.exception("image follower: generation replay failed; "
                          "disconnecting")
            return


def _distributed_shutdown() -> None:
    try:
        import jax
        jax.distributed.shutdown()
    except Exception:  # noqa: BLE001 — teardown must never mask the exit
        logging.getLogger(__name__).debug("distributed shutdown failed",
                                          exc_info=True)


def _advertised_host(args) -> str:
    """Address followers use to reach the coordinator's control socket:
    CAKE_CONTROL_HOST, else the host the jax coordinator was reached at
    (CAKE_COORDINATOR), else this host's name."""
    import os
    import socket

    if os.environ.get("CAKE_CONTROL_HOST"):
        return os.environ["CAKE_CONTROL_HOST"]
    coord = os.environ.get("CAKE_COORDINATOR", "")
    if ":" in coord:
        return coord.rsplit(":", 1)[0]
    return socket.gethostname()


def _serve_router(args) -> int:
    """The `cake-router` process role: no model weights, no devices —
    a thin HTTP front door (cake_tpu/router) over --replicas. With a
    --model directory holding tokenizer.json the affinity keys are
    page-aligned token fingerprints (the register_prefix rounding
    rule); otherwise they degrade to system-prompt text fingerprints
    (RouterServer logs the one-shot warning)."""
    import os

    from cake_tpu.args import parse_replicas
    from cake_tpu.router import start_router

    log = logging.getLogger(__name__)
    # with fleet discovery (--router-announce) the static seed is
    # optional — the fleet forms from replica announce frames
    replicas = parse_replicas(args.replicas) if args.replicas else []
    tokenizer = None
    if args.model:
        try:
            from cake_tpu.models.llama.generator import load_tokenizer
            tokenizer = load_tokenizer(args.model)
        except Exception as e:  # noqa: BLE001 — degraded, not fatal
            log.warning("router: could not load tokenizer from %s "
                        "(%s); affinity falls back to text "
                        "fingerprints", args.model, e)
    address = args.api or args.address
    log.info("router: fronting %d replica(s) on %s", len(replicas),
             address)
    start_router(replicas, address=address, tokenizer=tokenizer,
                 poll_interval_s=args.router_poll,
                 load_watermark=args.router_watermark,
                 policy_mode=args.router_policy,
                 # distributed tracing + sentinel (ISSUE 15): the
                 # router reuses the engine's obs flag surface —
                 # hop-span JSONL, typed event ring/log, --sentinel
                 trace_ring=args.trace_ring,
                 trace_events=args.trace_events,
                 event_ring=args.event_ring,
                 event_log=args.event_log,
                 sentinel=args.sentinel,
                 sentinel_interval_s=args.sentinel_interval,
                 # closed-loop anomaly weighting (ISSUE 16,
                 # obs/actions.py): de-weight/re-weight placement from
                 # router-tier anomalies — opt-in, report-only default
                 anomaly_weighting=args.router_anomaly_weighting,
                 # fleet discovery (ISSUE 18, router/discovery.py):
                 # bind the token-gated announce listener; replicas
                 # self-register, pushed frames supersede polling,
                 # departures drain-then-forget
                 announce=args.router_announce,
                 announce_interval_s=args.announce_interval,
                 announce_token=os.environ.get("CAKE_ANNOUNCE_TOKEN"))
    return 0


def router_main(argv=None) -> int:
    """The `cake-router` entry: the front-door role with --router
    implied (equivalent to `cake-tpu --router --replicas ...`); the
    hook a console-script or wrapper shim points at."""
    argv = list(sys.argv[1:] if argv is None else argv)
    if "--router" not in argv:
        argv = ["--router"] + argv
    return main(argv)


def main(argv=None) -> int:
    STARTUP.start()     # `boot` ends here
    with STARTUP.phase("args"):
        from cake_tpu.args import parse_args

        logging.basicConfig(
            level=logging.INFO,
            format="[%(asctime)s] %(levelname)s %(name)s: %(message)s",
        )
        args, sd_args, img_args = parse_args(argv)
    if args.router or not args.api:
        # only a serving process has a first healthy answer to run to
        STARTUP.stop()

    if args.router:
        # BEFORE Master.from_args/initialize: the router is a
        # model-less, device-less process role — it must not load
        # weights or join a mesh
        return _serve_router(args)
    if getattr(args, "replicas", None):
        # one-shot warning mirroring --step-log: the replica list only
        # feeds the router role
        logging.getLogger(__name__).warning(
            "--replicas has no effect without --router: the replica "
            "list names the backends of the front-door router "
            "(cake_tpu/router)")

    if getattr(args, "kv_host_pages", None) and not args.kv_pages:
        # one-shot warning mirroring --step-log: the host KV tier
        # spills PAGED pool pages, so without --kv-pages the flag does
        # nothing — say so instead of silently ignoring it
        logging.getLogger(__name__).warning(
            "--kv-host-pages has no effect without --kv-pages: the "
            "host tier spills paged KV pool pages (cake_tpu/kv)")

    if getattr(args, "router_announce", None) and not args.api:
        # same discipline: on a non-router process the flag points the
        # replica's announcer at a router, and only an --api serving
        # process has anything to announce
        logging.getLogger(__name__).warning(
            "--router-announce has no effect without --api (or "
            "--router): a replica announces its serving address to "
            "the front door (cake_tpu/router/discovery.py)")

    if getattr(args, "router_anomaly_weighting", False):
        # same discipline: the weighting actuator lives in the router
        # role's process — on an engine replica the flag does nothing
        logging.getLogger(__name__).warning(
            "--router-anomaly-weighting has no effect without "
            "--router: the placement de-weighting actuator runs in "
            "the front-door process (cake_tpu/router)")

    if (getattr(args, "journal_fsync", "batch") != "batch"
            and not getattr(args, "journal", None)):
        # same discipline: the fsync mode tunes the journal's
        # durability barrier, and without --journal there is no
        # journal to fsync
        logging.getLogger(__name__).warning(
            "--journal-fsync has no effect without --journal: it "
            "tunes the write-ahead request journal's durability "
            "barrier (serve/journal.py)")

    with STARTUP.phase("import_jax"):
        # the process's first `import jax` (cake_tpu.models and
        # cake_tpu.obs both reach it)
        if getattr(args, "require_model_type", None):
            # before any device or weight is touched: a directory that
            # resolves to another family must not be served under this
            # name
            from cake_tpu.models.llama.config import _read_config
            found = (_read_config(args.model).get("model_type", "llama")
                     if args.model else None)
            if found != args.require_model_type:
                print(f"--require-model-type {args.require_model_type}: "
                      f"{args.model!r} resolves to model_type {found!r}",
                      file=sys.stderr)
                return 2

        if args.mode == "worker":
            print(
                "cake-tpu runs the whole topology as one SPMD program over "
                "the device mesh; there is no separate worker process. Run "
                "in master mode on the host attached to the TPU slice.",
                file=sys.stderr,
            )
            return 2

        from cake_tpu.master import Master
        from cake_tpu.obs import startup
        from cake_tpu.utils.compile_cache import enable_compile_cache
        cache_dir = enable_compile_cache()
        startup.listen()
    logging.getLogger(__name__).info("compile cache: %s", cache_dir)

    with STARTUP.phase("backend"):
        # multi-host: every host runs this same program (SPMD);
        # coordinates auto-detected on TPU pods or taken from CAKE_*
        # env vars. jax.devices() is where the TPU client starts
        import jax

        from cake_tpu.parallel.distributed import initialize
        initialize()
        jax.devices()

    master = Master.from_args(args, sd_args)

    if args.api:
        from cake_tpu.api import start
        if jax.process_count() > 1:
            return _serve_multihost(master, args)
        if getattr(args, "telemetry_export", None):
            # one-shot warning mirroring --step-log: the federation
            # plane ships FOLLOWER telemetry to the coordinator; a
            # single-process deployment has no followers to federate
            logging.getLogger(__name__).warning(
                "--telemetry-export has no effect on single-host "
                "serving: there are no follower processes to "
                "federate (obs/federation.py); /api/v1/fleet will "
                "report only this host")
        start(master, address=args.api, checkpoint_path=args.checkpoint,
              announce=getattr(args, "router_announce", None),
              announce_interval_s=args.announce_interval,
              announce_token=os.environ.get("CAKE_ANNOUNCE_TOKEN"))
        return 0

    if args.step_log:
        # the step flight recorder lives in the serving engine; a
        # one-shot generation has none — be loud instead of writing an
        # empty file the operator then greps in vain
        logging.getLogger(__name__).warning(
            "--step-log applies to engine serving (--api); one-shot "
            "generation records no step flight")
    if getattr(args, "event_log", None) \
            or getattr(args, "slo_targets", None):
        # the event bus and the SLO accountant live in the serving
        # engine; a one-shot generation would write an empty event log
        # and account nothing — mirror the --step-log warning
        logging.getLogger(__name__).warning(
            "--event-log / --slo-targets apply to engine serving "
            "(--api); one-shot generation publishes no events and "
            "accounts no SLOs")
    if args.priority_classes or args.preemption or args.shed:
        # the whole scheduling subsystem lives in the serving engine
        # (priority queues / preemption / shed admission); a one-shot
        # generation has exactly one request and nothing to schedule —
        # be loud instead of the flags silently doing nothing
        logging.getLogger(__name__).warning(
            "--priority-classes / --preemption / --shed apply to "
            "engine serving (--api); one-shot generation runs a "
            "single request with nothing to schedule")
    if args.kv_pages or args.auto_prefix \
            or getattr(args, "kv_host_pages", None) \
            or getattr(args, "kv_dtype", None) in ("int8", "int4"):
        # all live in the serving engine (paged pool / prefix registry
        # / kv tiering); a one-shot generation
        # silently ignoring them would look like the feature "did
        # nothing"
        logging.getLogger(__name__).warning(
            "--kv-pages / --auto-prefix / --kv-dtype int8/int4 / "
            "--kv-host-pages apply to engine serving "
            "(--api); one-shot generation uses the sequential "
            "generator's dense cache")
    if getattr(args, "autotune", "off") != "off":
        # the autotuner hot-switches a LIVE engine's config between
        # iterations; a one-shot generation has no engine and no load
        # to adapt to — be loud instead of the flag silently vanishing
        logging.getLogger(__name__).warning(
            "--autotune applies to engine serving (--api); one-shot "
            "generation has no live engine to reconfigure")
    if getattr(args, "telemetry_export", None):
        # the exporter/collector pair lives in multi-host API serving;
        # a one-shot generation federates nothing — be loud instead of
        # the flag silently vanishing
        logging.getLogger(__name__).warning(
            "--telemetry-export applies to multi-host API serving "
            "(--api across processes); one-shot generation runs one "
            "process with nothing to federate")
    if getattr(args, "fault_plan", None) \
            or getattr(args, "recovery", None) is not None:
        # the fault plane's sites and the recovery loop live in the
        # serving engine; a one-shot generation injecting nothing
        # would read as "chaos found no bugs" — be loud instead
        logging.getLogger(__name__).warning(
            "--fault-plan / --recovery apply to engine serving "
            "(--api); one-shot generation dispatches no engine steps "
            "to inject into or recover")
    if getattr(args, "journal", None):
        # the write-ahead request journal records engine admissions
        # and emitted-token batches; a one-shot generation admits
        # nothing through the engine — mirror the --step-log warning
        logging.getLogger(__name__).warning(
            "--journal applies to engine serving (--api); one-shot "
            "generation journals nothing and replays nothing")
    if getattr(args, "disagg", None):
        # the prefill/decode split is a pair of SERVING engines wired
        # by the transfer channel; a one-shot generation has neither —
        # warn AND clear so Master.from_args does not bind/dial a
        # channel no request will ever cross
        logging.getLogger(__name__).warning(
            "--disagg applies to engine serving (--api): a one-shot "
            "generation has no peer to ship KV pages to "
            "(cake_tpu/kv/transfer.py); ignoring it")
        args.disagg = None

    if args.model_type.value == "image":
        count = [0]

        def save(pngs):
            for png in pngs:
                path = f"image_{count[0]}.png"
                with open(path, "wb") as f:
                    f.write(png)
                print(f"wrote {path}")
                count[0] += 1

        master.generate_image(img_args, save)
        return 0

    from cake_tpu.utils.profiling import trace
    with trace(args.tracing):
        master.run()
    return 0


if __name__ == "__main__":
    sys.exit(main())
