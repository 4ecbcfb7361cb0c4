"""Threaded HTTP server exposing the OpenAI-compatible API.

Reference behavior (api/mod.rs, api/text.rs, api/image.rs): the master is
shared state; the text endpoint resets chat state, appends the request
messages, runs the full generation, returns one JSON completion; the image
endpoint returns base64 PNGs; unknown routes 404.

Differences (deliberate upgrades, SURVEY.md §7.4):
  * `"stream": true` streams SSE `chat.completion.chunk`s token-by-token —
    the reference computes tokens incrementally but buffers the HTTP body.
  * Requests queue on an explicit generation lock with a `Retry-After` 503
    once the queue is deep, instead of silently serialising on a RwLock.
  * GET /api/v1/health and /api/v1/cluster expose device/topology
    introspection (the reference's WorkerInfo, proto/message.rs:42-58,
    becomes JAX device queries).
"""

from __future__ import annotations

import json
import logging
import re
import threading
import time
import uuid
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional

from cake_tpu.api import stream_writer as sw
from cake_tpu.api.openai import (
    chunk_response, completion_response, parse_chat_request,
)
from cake_tpu.args import ImageGenerationArgs
from cake_tpu.obs import metrics as obs_metrics
from cake_tpu.obs import startup as obs_startup
from cake_tpu.obs import steps as obs_steps
from cake_tpu.obs import tracing as obs_tracing
from cake_tpu.serve.errors import EngineRequestError
from cake_tpu.startup import STARTUP

log = logging.getLogger(__name__)

MAX_WAITING = 16

# routes worth a per-route counter series; anything else (scanners,
# typos) collapses into "other" so a 404 spray cannot explode the label
# cardinality
KNOWN_ROUTES = frozenset({
    "/api/v1/chat/completions", "/v1/chat/completions", "/api/v1/image",
    "/api/v1/health", "/api/v1/cluster", "/v1/models", "/api/v1/models",
    "/metrics", "/api/v1/metrics", "/api/v1/requests", "/api/v1/steps",
    "/api/v1/profile", "/api/v1/autotune", "/api/v1/events",
    "/api/v1/requests/{rid}/timeline", "/api/v1/fleet",
    "/api/v1/drain", "/api/v1/anomalies",
})

# rid-bearing paths are counted under their TEMPLATE: a per-rid route
# label would grow one metric series per request — exactly the
# cardinality explosion tools/lint_metrics.py bans rid labels for
_TIMELINE_RE = re.compile(r"^/api/v1/requests/(\d+)/timeline$")


class ApiServer:
    """Wraps a Master. With an engine, chat requests batch continuously —
    N requests decode together in one batched program; without one, they
    serialise on a generation lock (still an upgrade over the reference's
    silent RwLock, api/text.rs:67)."""

    # cakelint guards discipline: the federation collector is optional
    # (coordinator-with---telemetry-collect only)
    OPTIONAL_PLANES = ("collector",)

    def __init__(self, master, model_name: str = "cake-tpu", engine=None,
                 health=None, collector=None, replica_id=None):
        import os
        import socket
        self.master = master
        self.model_name = model_name
        self.engine = engine
        # stable id for THIS serving process, so a front-door router
        # (cake_tpu/router) and clients can attribute backpressure to a
        # specific replica: the x-cake-replica header on 429/503
        # responses and the `replica` health field both carry it.
        # start() passes the bind address; CAKE_REPLICA_ID overrides.
        self.replica_id = (replica_id
                           or os.environ.get("CAKE_REPLICA_ID")
                           or socket.gethostname())
        # last page size read under a successful non-blocking
        # _switch_lock acquire (see _page_size)
        self._page_size_cache = None
        # parallel.health.ServingHealth: when it flips to failed, chat
        # requests 503 and /api/v1/health reports the reason
        self.health_state = health
        # obs/federation.TelemetryCollector (multi-host serving): the
        # fleet endpoint, ?host= event filters and the host-labeled
        # federated /metrics families all read from it; attaching it to
        # the engine makes request timelines span hosts
        self.collector = collector
        if collector is not None and engine is not None:
            engine.telemetry = collector
        # the one thread that writes this server's streamed chunks
        # (api/stream_writer.py); the engine signals it once a step
        self.stream_writer = sw.StreamWriter()
        if engine is not None:
            engine.flight.stream_wake = self.stream_writer.signal
            engine.start()
        self._gen_lock = threading.Lock()
        self._waiting = 0
        self._waiting_lock = threading.Lock()
        # drain plumbing (POST /api/v1/drain): start() wires _shutdown
        # to its save-and-exit closure; the drain thread calls it once
        # in-flight work finishes (or the drain timeout expires)
        self._shutdown = None
        self._drain_thread = None
        self._drain_lock = threading.Lock()
        self.started_at = int(time.time())  # /v1/models "created"
        # POST /api/v1/profile capture target (--profile-dir; None =
        # a fresh temp dir per capture)
        self._profile_dir = getattr(
            getattr(master, "args", None), "profile_dir", None)
        self._m_http = obs_metrics.counter(
            "cake_http_requests_total",
            "HTTP requests served, by route and status code",
            labelnames=("route", "status"))

    def _count(self, path: str, code: int) -> None:
        route = path.split("?", 1)[0]
        if _TIMELINE_RE.match(route):
            route = "/api/v1/requests/{rid}/timeline"
        if route not in KNOWN_ROUTES:
            route = "other"
        self._m_http.labels(route=route, status=str(code)).inc()

    # -- text ---------------------------------------------------------------

    def chat(self, body: dict, send_chunk=None, on_start=None,
             idempotency_key=None, last_event_id=None,
             trace_id=None) -> Optional[dict]:
        """Run one chat completion. If send_chunk is set, stream deltas
        through it and return None; else return the full response dict.
        A send_chunk that carries its connection as `socket` (the HTTP
        handler's) has its live deltas written there by the server's
        stream writer thread (api/stream_writer.py); any other is
        called for each.
        `on_start` fires after admission and before any tokens — the
        streaming handler sends its response headers there, so queue
        rejections still surface as a clean 503; a callback accepting
        `rid=` additionally receives the engine rid (the handler echoes
        it as x-cake-rid, the front-door router's trace join key).

        idempotency_key (x-cake-idempotency-key): a retried submit with
        the same key attaches to the live/finished stream instead of
        double-admitting — safe client retry, across restarts too when
        --journal is armed. last_event_id (Last-Event-ID): on a
        streaming reconnect, replay the journaled/held suffix after
        that absolute token id, then continue live. trace_id
        (x-cake-trace): the originating distributed-trace id, threaded
        to the engine tracer/event bus at admission."""
        if self.engine is not None:
            return self._chat_engine(body, send_chunk, on_start,
                                     idempotency_key=idempotency_key,
                                     last_event_id=last_event_id,
                                     trace_id=trace_id)
        if idempotency_key is not None or last_event_id is not None:
            raise ValueError(
                "idempotency keys / Last-Event-ID resume require the "
                "batching engine (this deployment serves through the "
                "legacy locked path)")
        messages, opts = parse_chat_request(body)
        if opts.get("logprobs"):
            raise ValueError(
                "logprobs requires the batching engine (this deployment "
                "serves through the legacy locked path)")
        # clamp to the serving mode's decode budget (e.g. the --sp
        # adapter's replicated tail): generating past it raises mid-
        # stream, after headers are gone — the client would hang on a
        # never-terminated chunked response
        budget = getattr(getattr(self.master.llm, "_forward_fn", None),
                         "max_decode_tokens", None)
        if budget is not None:
            opts["max_tokens"] = min(opts["max_tokens"] or budget, budget)
        t0 = time.perf_counter()
        with self._admission():
            with self._gen_lock:
                m = self.master
                m.reset()
                if m.llm is not None and hasattr(m.llm, "set_sampling"):
                    m.llm.set_sampling(temperature=opts["temperature"],
                                       top_p=opts["top_p"])
                for msg in messages:
                    m.add_message(msg)
                rid = str(uuid.uuid4())
                if send_chunk is None:
                    text = m.generate_text(lambda t: None,
                                           sample_len=opts["max_tokens"])
                    # locked-path e2e latency: the engine path records
                    # this through its tracer; here the handler is the
                    # only seam that sees the whole request
                    obs_tracing.REQUEST_E2E.observe(
                        time.perf_counter() - t0)
                    return completion_response(text, self.model_name)
                if on_start is not None:
                    on_start()
                m.generate_text(
                    lambda t: send_chunk(
                        chunk_response(t.text, self.model_name, rid=rid)),
                    sample_len=opts["max_tokens"],
                )
                send_chunk(chunk_response("", self.model_name,
                                          finish="stop", rid=rid))
                obs_tracing.REQUEST_E2E.observe(time.perf_counter() - t0)
                return None

    def _chat_engine(self, body: dict, send_chunk=None,
                     on_start=None, idempotency_key=None,
                     last_event_id=None,
                     trace_id=None) -> Optional[dict]:
        """Continuous-batching path: no lock — the engine interleaves this
        request's decode steps with every other in-flight request."""
        from cake_tpu.serve.engine import QueueFullError
        messages, opts = parse_chat_request(body)
        want_lp = bool(opts.get("logprobs"))
        n_top = opts.get("top_logprobs") or 0
        kw = dict(
            max_new_tokens=opts["max_tokens"] or self.master.args.sample_len,
            temperature=opts["temperature"],
            top_p=opts["top_p"],
            want_top_logprobs=n_top > 0,
            priority=opts.get("priority"),
            idempotency_key=idempotency_key,
            trace_id=trace_id,
        )

        from cake_tpu.sched import ShedError
        from cake_tpu.serve.errors import DrainingError

        if send_chunk is None:
            try:
                h = self.engine.chat(messages, **kw)
            except (QueueFullError, ShedError) as e:
                raise QueueFull(getattr(e, "retry_after", 1.0),
                                shed=isinstance(e, ShedError))
            except DrainingError as e:
                raise QueueFull(e.retry_after, draining=True)
            h.wait()
            lp = None
            if want_lp:
                lp = [sw.lp_entry(self.engine.tokenizer, n_top, t, l, top)
                      for (t, l), top
                      in zip(h.token_logprobs, h.token_top_logprobs)]
            text = h.text()   # raises the typed error if the engine failed it
            rep = list(getattr(h._req, "replayed_tokens", ()) or ())
            if rep:
                # a journal/checkpoint-resumed stream: the client's
                # transcript is the WHOLE generation — the tokens
                # replayed from previous process generations plus this
                # epoch's (h.text() alone covers only the latter)
                eos = self.engine.config.eos_token_ids
                text = self.engine.tokenizer.decode(
                    [t for t in rep + list(h._req.out_tokens)
                     if t not in eos])
            return completion_response(text, self.model_name,
                                       logprobs=lp)

        # The engine thread only appends a delta to its stream; who
        # writes it depends on what the caller passed. A send_chunk that
        # carries its connection (`socket`: the HTTP handler's) hands the
        # stream to the server's one writer thread, which the engine
        # signals once a step and which sends without blocking; any other
        # (embedders, tests) is called from this thread, a wake-up a
        # delta. Either way a slow client never blocks the engine loop
        # (that would stall every other in-flight request), nor the other
        # streams: one whose socket would block comes back to this
        # thread, which can wait.
        sock = getattr(send_chunk, "socket", None)
        cs = sw.ChatStream(
            self.engine.tokenizer, self.engine.config.eos_token_ids,
            self.model_name, want_lp, n_top,
            writer=self.stream_writer if sock is not None else None)
        # back-compat with 1-arg send_chunk callables (embedders,
        # tests): only a callback that accepts event_id gets the SSE
        # resume ids; others receive plain chunks
        _wants_id = _accepts_kwarg(send_chunk, "event_id")
        raw_send = send_chunk

        def send_chunk(obj, event_id=None):
            if _wants_id and event_id is not None:
                raw_send(obj, event_id=event_id)
            else:
                raw_send(obj)

        try:
            h = self.engine.chat(messages, stream=cs.feed, **kw)
        except (QueueFullError, ShedError) as e:
            raise QueueFull(getattr(e, "retry_after", 1.0),
                            shed=isinstance(e, ShedError))
        except DrainingError as e:
            raise QueueFull(e.retry_after, draining=True)
        cs.bind(h._req)
        try:
            return self._serve_stream(cs, h, send_chunk, sock, on_start,
                                      last_event_id)
        finally:
            # the request holds the stream (its callback) and the stream
            # held the request: a finished request's token lists go when
            # the last count does, not at the next full collection
            cs.req = None

    def _serve_stream(self, cs, h, send_chunk, sock, on_start,
                      last_event_id):
        """An admitted (or attached) streaming request to its end: the
        headers, an attach's replay, the live deltas through the stream
        writer or on this thread, then the terminal error event or the
        finish chunk. None, or DISCONNECTED."""
        r = h._req
        if on_start is not None:
            # a callback accepting rid= gets the engine rid (the
            # handler echoes it as x-cake-rid before any tokens, so a
            # front-door router learns the trace join key at
            # admission); plain zero-arg callbacks (embedders, tests)
            # keep working
            if _accepts_kwarg(on_start, "rid"):
                on_start(rid=r.rid)
            else:
                on_start()
        if getattr(h, "attached", False):
            # idempotent reconnect: the missing suffix first, then live
            first = cs.replay(last_event_id)
            try:
                if first is not None:
                    send_chunk(*first)
            except OSError:
                return DISCONNECTED   # reconnect died mid-replay
        elif last_event_id:
            # fresh admission, resuming client: suppress what it holds
            cs.resume_after(last_event_id)

        outcome = (self.stream_writer.write(cs, sock) if sock is not None
                   else sw.BLOCKED)
        if outcome == sw.BLOCKED:
            outcome = cs.pump(send_chunk, sock)
        if outcome in (sw.GONE, sw.FAILED):
            # client disconnected mid-stream (or the writer failed and
            # the stream ends below): free the slot now instead of
            # decoding to max_tokens for nobody — UNLESS the request is
            # idempotency-keyed: the client told us it will reconnect
            # and resume, so the stream keeps decoding for its return
            if r.idempotency_key is None:
                log.info("client disconnected; cancelling request")
                self.engine.cancel(h)
            else:
                log.info("client disconnected; rid=%d keeps decoding "
                         "for an idempotent reconnect", r.rid)
            if outcome == sw.GONE:
                return DISCONNECTED
        try:
            if outcome == sw.FAILED:
                raise cs.error
            h.text()  # raises if the engine failed the request
        except Exception as e:  # noqa: BLE001
            # the headers are long gone: an open SSE stream gets a
            # TERMINAL error event (typed + retryable flag) instead of
            # a silent close the client cannot tell from success
            try:
                send_chunk({"error": {
                    "message": str(e), "type": type(e).__name__,
                    "retryable": bool(getattr(e, "retryable", False)),
                }})
            except OSError:
                return DISCONNECTED
            return None
        try:
            send_chunk(*cs.finish_chunk())
        except OSError:
            return DISCONNECTED  # request already complete; just stop
        return None

    # -- image --------------------------------------------------------------

    def image(self, body: dict) -> dict:
        import base64
        args = ImageGenerationArgs.from_json(body)
        images: list = []
        with self._admission():
            with self._gen_lock:
                self.master.generate_image(
                    args, lambda pngs: images.extend(pngs))
        return {"images": [base64.b64encode(p).decode() for p in images]}

    # -- introspection -------------------------------------------------------

    def _page_size(self):
        """The paged engine's kv page size (None for dense) — the
        router aligns its affinity fingerprints to it (the
        register_prefix rounding rule)."""
        eng = self.engine
        if eng is None or not getattr(eng, "paged", False):
            return None
        # the pager swaps wholesale during a live reconfigure; its
        # declared lock pins one consistent value. NON-blocking on
        # purpose (the refresh_page_gauges discipline): the health
        # endpoint — including the router's sub-second lite poll —
        # must never stall behind a fold-everything switch holding
        # the lock through jit compiles, or the router would eject a
        # healthy replica exactly when it is switching. On contention
        # the last-seen value serves one more poll.
        if eng._switch_lock.acquire(blocking=False):
            try:
                # cakelint: skip[affinity] _switch_lock held via the non-blocking acquire above (the with-form would block the health path behind a wedged switch)
                self._page_size_cache = eng._pager.page_size
            finally:
                eng._switch_lock.release()
        return self._page_size_cache

    def health(self, lite: bool = False) -> dict:
        """/api/v1/health. lite (?lite=1): ONLY the fields a front-door
        router polls every few hundred ms — queue depths, SLO
        attainment, config epoch, draining, breaker — each a SUBTREE of
        the full document (pinned by contract test). The full document
        walks every subsystem (journal state, recovery wire state,
        lifetime counters): too heavy for a 250ms poll loop."""
        failed = (self.health_state is not None
                  and self.health_state.failed)
        out = {"status": "failed" if failed else "ok",
               "replica": self.replica_id,
               # doc build-time wall clock: the router's per-replica
               # clock-offset estimate (min over polls of receive-wall
               # minus this) — the federated timeline's correction
               # input, same rule as obs/federation.py frames
               "now": round(time.time(), 6),
               "queue_depth": self._waiting}
        if not lite:
            out["model"] = self.model_name
            started = obs_startup.report()
            if started is not None:
                # the start-up clock's phases, its mark and the
                # programs made so far (obs/startup.py)
                out["startup"] = started
        if failed:
            out["reason"] = self.health_state.reason
        if self.engine is None:
            return out
        eng = self.engine
        out.update(
            queue_depth=eng.queue_depth,
            active_requests=eng.active,
            decode_slots=eng.max_slots,
        )
        depths = getattr(eng.scheduler, "class_depths", None)
        if depths is not None:
            # SLO scheduling on: per-class queue depths
            out["queue_depth_by_class"] = depths()
        if getattr(eng, "_draining", False):
            # drain in flight (POST /api/v1/drain / SIGTERM):
            # admissions 429 while this block counts down the
            # remaining in-flight work
            out["draining"] = True
            out["drain"] = eng.drain_state()
        ps = self._page_size()
        if ps is not None:
            out["page_size"] = ps
        if hasattr(eng, "current_config"):
            # the autotune epoch + switch flag: a router redirects
            # fresh admissions while a fold-everything switch runs
            out["config_epoch"] = getattr(eng, "config_epoch", 0)
            out["autotune"] = getattr(eng, "autotune_mode", "off")
            out["switch_in_flight"] = bool(
                getattr(eng, "_switch_inflight", False))
        slo = getattr(eng, "slo", None)
        if slo is not None:
            # serving quality (obs/slo.py): the router's weighted pick
            # reads attainment; the full doc carries the whole snapshot
            if lite:
                out["slo"] = {"attainment_1m": {
                    c: round(v, 4) for c, v in
                    slo.attainment_by_class("1m").items()}}
            else:
                out["slo"] = slo.snapshot()
        if hasattr(eng, "recovery_state"):
            if lite:
                # just the breaker bit (a tripped breaker means this
                # replica is a restart away — stop routing to it); the
                # full recovery_state walks the fault plan and control
                # wire state
                out["recovery"] = {"breaker": {"tripped": bool(
                    getattr(eng, "_breaker_tripped", False))}}
            else:
                out["recovery"] = eng.recovery_state()
        if lite:
            return out
        st = eng.stats
        out.update(
            requests_completed=st.requests_completed,
            tokens_generated=st.tokens_generated,
            decode_tokens_per_s=round(st.decode_tokens_per_s, 2),
        )
        if depths is not None:
            # per-class outcome counters ride the full doc only
            out["preemptions"] = st.preemptions
            out["requests_shed"] = st.shed
        jnl = getattr(eng, "_journal", None)
        if jnl is not None:
            # write-ahead journal state (--journal): appended
            # bytes/records, fsync mode, whether the sink failed
            # open, and the last replay's outcome
            out["journal"] = jnl.state()
        if hasattr(eng, "current_config"):
            # the LIVE effective engine config (slots, decode_scan,
            # kv_pages, kv_dtype, attn impl) so
            # operators can see what the autotuner chose; the epoch
            # pairs with per-request trace attribution
            out["engine_config"] = eng.current_config().to_dict()
            # what each step kind actually RUNS (the engine resolves
            # it from the dispatched shapes) beside the paged_attn
            # name the config asked for
            out["engine_config"]["attn_impl"] = dict(
                getattr(eng, "attn_impl", {}))
        return out

    def autotune(self) -> dict:
        """GET /api/v1/autotune: mode, live config, window signals and
        the switch/decision history (cake_tpu/autotune)."""
        if self.engine is None or not hasattr(self.engine,
                                              "autotune_state"):
            return {"mode": "off",
                    "note": "engine-less serving has no autotuner"}
        return self.engine.autotune_state()

    def autotune_switch(self, body: dict) -> dict:
        """POST /api/v1/autotune {"config": {...}}: manual live
        switch. 400 on a malformed/invalid config or when --autotune
        is off; 409 (SwitchInFlightError, mapped by the handler) while
        another switch is in flight."""
        if self.engine is None or not hasattr(self.engine,
                                              "reconfigure"):
            raise ValueError("engine-less serving has no autotuner")
        if getattr(self.engine, "autotune_mode", "off") == "off":
            raise ValueError(
                "autotune is off; restart with --autotune manual (or "
                "auto) to enable live config switching")
        cfg = body.get("config")
        if not isinstance(cfg, dict):
            raise ValueError('body must be {"config": {...}} with the '
                             "switchable engine knobs")
        switched = self.engine.reconfigure(cfg, reason="manual")
        return {"switched": bool(switched),
                "config": self.engine.current_config().to_dict(),
                "epoch": self.engine.config_epoch}

    def drain(self, body: dict) -> dict:
        """POST /api/v1/drain {"timeout_s": N?}: graceful shutdown.
        Closes admissions immediately (new submits get 429 + the
        computed drain ETA as Retry-After), lets in-flight work finish
        for up to timeout_s (default 30), then snapshots whatever
        remains (--checkpoint) or leaves it journaled (--journal),
        stops the engine and shuts the HTTP server down cleanly.
        Responds immediately with the drain state; idempotent — a
        second POST reports progress without rearming."""
        if self.engine is None:
            raise ValueError("engine-less serving has no drain "
                             "(requests serialise on the generation "
                             "lock; stop the process instead)")
        timeout_s = body.get("timeout_s", 30.0)
        if (not isinstance(timeout_s, (int, float))
                or isinstance(timeout_s, bool) or timeout_s <= 0):
            raise ValueError("timeout_s must be a positive number")
        st = self.engine.begin_drain()
        with self._drain_lock:
            if self._drain_thread is None:
                self._drain_thread = threading.Thread(
                    target=self._drain_then_exit,
                    args=(float(timeout_s),), daemon=True,
                    name="cake-drain")
                self._drain_thread.start()
        return st

    def _drain_then_exit(self, timeout_s: float) -> None:
        """Drain-thread body: wait for the queue and the in-flight set
        to empty (bounded), then run the shared shutdown tail."""
        eng = self.engine
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            st = eng.drain_state()
            if st["pending_requests"] == 0 and st["queue_depth"] == 0:
                break
            time.sleep(0.05)
        else:
            log.warning("drain: timeout after %.1fs with %d request(s) "
                        "still in flight (snapshotted/journaled for "
                        "the next start where armed)", timeout_s,
                        eng.drain_state()["pending_requests"])
        shutdown = self._shutdown
        if shutdown is not None:
            shutdown()
        else:
            # standalone ApiServer (no start() wiring, e.g. tests):
            # stop the engine; post-drain submits then raise the typed
            # reset error instead of hanging
            eng.stop()

    def _engine_retry_after(self, priority=None) -> float:
        """Honest Retry-After for a transient engine reset: the shed
        controller's measured-service-rate estimate for the REQUEST'S
        priority class when shedding is on (the same computation
        behind the 429 path), else a 1s floor."""
        shed = getattr(self.engine, "_shed", None) \
            if self.engine is not None else None
        if shed is not None:
            try:
                return shed.estimate_retry_after(
                    priority or "standard", self.engine.queue_depth)
            except Exception:  # noqa: BLE001 — estimate, not contract
                log.debug("retry-after estimate failed", exc_info=True)
        return 1.0

    def fleet(self) -> dict:
        """GET /api/v1/fleet: per-host liveness, last-export age,
        applied control seq + lag, clock offset, device HBM gauges and
        health state — the coordinator composes its own entry (it runs
        the API; it is live by construction unless health failed) with
        the collector's remote views (obs/federation.py)."""
        local_name = getattr(self.collector, "local_host", None) \
            or "coordinator"
        failed = (self.health_state is not None
                  and self.health_state.failed)
        local: dict = {"role": "coordinator", "live": not failed}
        if failed:
            local["health"] = {"status": "failed",
                               "reason": self.health_state.reason}
        if self.engine is not None:
            local["active_requests"] = self.engine.active
            local["queue_depth"] = self.engine.queue_depth
        try:
            from cake_tpu.utils.profiling import device_memory_stats
            # SAME key names as the remote rows (_hbm_from_metrics —
            # derived from the cake_device_hbm_* gauge families), so a
            # dashboard reads hosts[*].hbm uniformly across roles
            keymap = (("bytes_in_use", "bytes_in_use"),
                      ("peak_bytes_in_use", "peak_bytes"),
                      ("bytes_limit", "bytes_limit"))
            local["hbm"] = {
                str(s["device"]): {out: s[src] for src, out in keymap
                                   if s.get(src) is not None}
                for s in device_memory_stats()
                if s.get("bytes_in_use") is not None}
        except Exception:  # noqa: BLE001 — fleet view is best-effort
            log.debug("local hbm stats unavailable", exc_info=True)
        out = {"local_host": local_name, "hosts": {local_name: local}}
        if self.collector is None:
            out["note"] = ("telemetry federation disabled "
                           "(single-host serving, or "
                           "--no-telemetry-export)")
            return out
        remote = self.collector.fleet()
        out["published_seq"] = remote.get("published_seq")
        out["stale_after_s"] = remote.get("stale_after_s")
        if out["published_seq"] is not None:
            # the coordinator publishes the op stream: by definition it
            # has applied everything it published
            local["applied_seq"] = out["published_seq"]
            local["lag_ops"] = 0
        out["hosts"].update(remote.get("hosts", {}))
        return out

    def cluster(self) -> dict:
        import jax
        from cake_tpu.parallel.distributed import cluster_info
        out = cluster_info()
        out["devices"] = [
            {"id": d.id, "platform": d.platform,
             "kind": d.device_kind, "process": d.process_index}
            for d in jax.devices()
        ]
        return out

    def metrics(self) -> str:
        """Prometheus text exposition of the serving metrics (the
        observability face of the reference's periodic worker-stat logs,
        worker.rs:254-283 — scrape-able instead of grep-able).

        Rendered from the obs.metrics registry: the request-latency
        histograms (TTFT / e2e / queue wait / prefill / inter-token)
        and per-route counters accumulate where the work happens; the
        engine's aggregate counters are synced here at scrape time (one
        scrape = one consistent snapshot of EngineStats)."""
        m = obs_metrics
        if self.health_state is None or not hasattr(
                self.health_state, "observe_metrics"):
            # per-device HBM gauges fresh at scrape instant (graceful
            # no-op on CPU backends); with a health state attached its
            # observe_metrics() below does this refresh instead —
            # calling both would pay Device.memory_stats() twice per
            # scrape on a multi-device host
            obs_steps.refresh_device_gauges()
        # the collections hook takes no lock: its sums reach the series here
        obs_steps.refresh_gc_series()
        m.gauge("cake_requests_waiting",
                "Requests inside HTTP admission").set(self._waiting)
        m.gauge("cake_serving_healthy",
                "1 = serving, 0 = failed (parallel/health.py)").set(
            0 if (self.health_state is not None
                  and self.health_state.failed) else 1)
        if self.health_state is not None and hasattr(
                self.health_state, "observe_metrics"):
            # heartbeat staleness gauge + watchdog counters
            self.health_state.observe_metrics()
        if self.engine is not None:
            st = self.engine.stats
            for name, help_, val in (
                ("cake_engine_queue_depth",
                 "Admission queue depth", self.engine.queue_depth),
                ("cake_engine_active_requests",
                 "Requests holding a decode slot", self.engine.active),
                ("cake_engine_decode_slots",
                 "Configured decode slots", self.engine.max_slots),
                ("cake_engine_decode_tokens_per_second",
                 "Aggregate decode throughput",
                 round(st.decode_tokens_per_s, 2)),
                ("cake_engine_trace_active_requests",
                 "Requests with an open lifecycle trace",
                 self.engine.tracer.active_count),
            ):
                m.gauge(name, help_).set(val)
            for name, help_, val in (
                ("cake_engine_requests_completed_total",
                 "Requests retired by the engine",
                 st.requests_completed),
                ("cake_engine_tokens_generated_total",
                 "Tokens generated across all requests",
                 st.tokens_generated),
                ("cake_engine_decode_steps_total",
                 "Batched decode steps dispatched", st.steps),
                ("cake_engine_decode_seconds_total",
                 "Wall seconds inside decode dispatch",
                 round(st.decode_time_s, 4)),
                ("cake_engine_prefill_seconds_total",
                 "Wall seconds inside prefill dispatch",
                 round(st.prefill_time_s, 4)),
                ("cake_engine_prefix_hits_total",
                 "Prefills served from a registered prefix",
                 st.prefix_hits),
                ("cake_engine_errors_total",
                 "Engine iterations that failed and reset", st.errors),
            ):
                m.counter(name, help_).set_total(val)
            # scrape-fresh per-class queue depths through the engine's
            # one registration site (no-op without the SLO scheduler)
            self.engine._set_queue_gauges()
            obs_steps.refresh_page_gauges(self.engine)
            slo = getattr(self.engine, "slo", None)
            if slo is not None:
                # both attainment windows converge at scrape time even
                # between retirements (a quiet minute must roll the 1m
                # window forward, not freeze the last busy value)
                slo.refresh_gauges()
        if self.collector is not None:
            # per-host liveness/age gauges live in the LOCAL registry:
            # refresh them before rendering it
            try:
                self.collector.refresh_gauges()
            except Exception:  # noqa: BLE001 — a scrape must not fail
                log.debug("fleet gauge refresh failed", exc_info=True)
        text = m.REGISTRY.render()
        if self.collector is not None:
            # fleet federation: remote hosts' families appended with a
            # host label — families the coordinator also owns reuse its
            # HELP/TYPE block above, remote-only families bring their
            # own (one TYPE per family, the lint contract)
            try:
                text += self.collector.render_federated(
                    {f.name for f in m.REGISTRY.families()})
            except Exception:  # noqa: BLE001 — a scrape must not fail
                log.debug("federated render failed", exc_info=True)
        return text

    def requests(self, limit: Optional[int] = None,
                 rid: Optional[int] = None, cls: Optional[str] = None,
                 since: Optional[int] = None) -> dict:
        """Per-request lifecycle traces (GET /api/v1/requests): active
        requests first, then the finished ring, newest first —
        oldest-first with ?since= (cursor pagination pages forward).
        ?rid= / ?class= / ?since= filter (since is a rid cursor:
        strictly newer admissions only — poll with the previous
        response's `cursor`). The cursor is derived from the RETURNED
        records (a rid admitted mid-request, or truncated by ?limit=,
        stays strictly above it — never skipped)."""
        if self.engine is None:
            return {"requests": [], "note": "engine-less serving has "
                    "no request tracer"}
        recs = self.engine.tracer.dump(limit, rid=rid, cls=cls,
                                       since=since)
        if recs:
            cursor = max(r["rid"] for r in recs)
        else:
            cursor = since if since is not None else 0
        return {"requests": recs, "cursor": cursor}

    def request_timeline(self, rid: int) -> Optional[dict]:
        """Per-request explain (GET /api/v1/requests/{rid}/timeline):
        the request's trace spans, bus events and step records merged
        into one time-ordered view (obs/timeline.py). None -> 404."""
        if self.engine is None or not hasattr(self.engine,
                                              "request_timeline"):
            return None
        return self.engine.request_timeline(rid)

    def events(self, rid: Optional[int] = None,
               type: Optional[str] = None,
               since: Optional[int] = None,
               limit: Optional[int] = None,
               host: Optional[str] = None) -> dict:
        """Cross-subsystem event dump (GET /api/v1/events): ascending
        seq, ?rid= / ?type= / ?since= filtered (obs/events.py); the
        response `cursor` is the newest seq — pass it back as ?since=
        to read only what is new. ?host= selects a FLEET host's stream:
        the local host's name (or "local") serves this process's bus
        exactly as the unfiltered call does; a remote host name serves
        the collector-held view (timestamps clock-offset-corrected,
        seqs/cursors are that host's own). Unknown hosts are a 400 via
        ValueError — the caller named a host, silently dumping
        everything would be the opposite of the ask."""
        local_name = getattr(self.collector, "local_host", None)
        if host is not None and host not in ("local", local_name):
            if self.collector is None:
                raise ValueError(
                    f"?host={host!r}: telemetry federation is "
                    "disabled (no collector); only local events exist")
            known = self.collector.hosts()
            if host not in known:
                raise ValueError(
                    f"unknown host {host!r} (local: "
                    f"{local_name or 'local'}; exporting: "
                    f"{', '.join(known) or 'none yet'})")
            # the collector owns the cursor-pagination contract
            # (events_page mirrors EventBus.snapshot), so local and
            # remote streams page identically
            evs, cursor = self.collector.events_page(
                host, rid=rid, type=type, since=since, limit=limit)
            return {"events": evs, "host": host, "cursor": cursor}
        bus = getattr(self.engine, "events", None) \
            if self.engine is not None else None
        if bus is None:
            return {"events": [], "cursor": 0,
                    "note": "event bus disabled (--event-ring 0) or "
                            "engine-less serving"}
        evs, cursor = bus.snapshot(rid=rid, type=type, since=since,
                                   limit=limit)
        out = {"events": evs, "cursor": cursor}
        if host is not None:
            out["host"] = local_name or "local"
        return out

    def anomalies(self, limit: Optional[int] = None) -> dict:
        """Online regression-sentinel dump (GET /api/v1/anomalies):
        active anomalies, the recent-firing ring (?limit=), every
        detector's threshold/state (obs/sentinel.py; armed by
        --sentinel), and — with --sentinel-act — the closed-loop
        action history (obs/actions.py)."""
        sen = (self.engine.sentinel if self.engine is not None
               else None)
        if sen is None:
            return {"active": [], "anomalies": [],
                    "note": "sentinel disabled (restart with "
                            "--sentinel) or engine-less serving"}
        out = sen.state(limit=limit)
        plane = getattr(self.engine, "_actions", None)
        if plane is not None:
            out["actions"] = plane.history(limit)
            out["action_rate_per_min"] = plane.max_per_min
        return out

    def steps(self, limit: Optional[int] = None) -> dict:
        """Step flight-recorder dump (GET /api/v1/steps): newest step
        records first plus the aggregate summary (per-kind counts,
        compile counts, decode-side MFU / HBM utilization)."""
        if self.engine is None or not hasattr(self.engine, "flight"):
            return {"steps": [], "summary": {},
                    "note": "engine-less serving has no step recorder"}
        return {"steps": self.engine.flight.dump(limit),
                "summary": self.engine.flight.summary()}

    def profile(self, body: dict) -> dict:
        """On-demand profiler capture (POST /api/v1/profile
        {"seconds": N[, "perfetto": true]}): grab a jax.profiler trace
        of the next N seconds of live execution and return the artifact
        paths (`xplane`; `perfetto_trace` is null unless asked for — the
        conversion runs inside this process when the capture stops).
        Single-flight: a concurrent capture raises ProfileBusyError
        (HTTP 409). The capture directory comes from --profile-dir
        (never the request body — clients must not pick server paths)."""
        if not isinstance(body, dict):
            # valid JSON but not an object (e.g. `[2]`): client error,
            # not a 500 + exception log
            raise ValueError("body must be a JSON object")
        seconds = body.get("seconds", 2.0)
        if not isinstance(seconds, (int, float)) or isinstance(
                seconds, bool):
            raise ValueError("seconds must be a number")
        perfetto = body.get("perfetto", False)
        if not isinstance(perfetto, bool):
            raise ValueError("perfetto must be true or false")
        return obs_steps.PROFILER.capture(seconds, self._profile_dir,
                                          perfetto=perfetto)

    # -- admission -----------------------------------------------------------

    def _admission(self):
        server = self

        class _Adm:
            def __enter__(self):
                with server._waiting_lock:
                    if server._waiting >= MAX_WAITING:
                        raise QueueFull()
                    server._waiting += 1

            def __exit__(self, *exc):
                with server._waiting_lock:
                    server._waiting -= 1
        return _Adm()


# chat() return sentinel: the streaming client went away (handled; the
# HTTP layer must not touch the dead socket again)
DISCONNECTED = object()


def _accepts_kwarg(fn, name: str) -> bool:
    """Whether calling fn(..., name=...) is safe: the callback
    evolution contract for chat()'s send_chunk (event_id=) and
    on_start (rid=) — older zero/one-arg callables (embedders, tests)
    keep working, newer ones opt in by naming the kwarg (or taking
    **kwargs)."""
    import inspect
    try:
        params = inspect.signature(fn).parameters
    except (TypeError, ValueError):
        return False
    return (name in params
            or any(p.kind == inspect.Parameter.VAR_KEYWORD
                   for p in params.values()))


class QueueFull(Exception):
    """Admission rejected: queue full, load-shed (shed=True), or the
    server is draining (draining=True — POST /api/v1/drain or SIGTERM
    in flight). retry_after seconds ride the HTTP 429 Retry-After
    header — computed from the measured service rate when shedding is
    on (sched/shed.py), from the drain ETA when draining, a 1s floor
    otherwise."""

    def __init__(self, retry_after: float = 1.0, shed: bool = False,
                 draining: bool = False):
        super().__init__("server draining" if draining
                         else "request shed" if shed else "queue full")
        self.retry_after = retry_after
        self.shed = shed
        self.draining = draining


class _HTTPServer(ThreadingHTTPServer):
    # the listen backlog: socketserver's 5 resets connections when a
    # wave of clients (32 closed-loop callers starting at once) connects
    # faster than the accept loop runs
    request_queue_size = 128


def make_handler(api: ApiServer):
    class Handler(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def log_message(self, fmt, *args):
            log.debug("http: " + fmt, *args)

        def _json(self, code: int, obj: dict):
            data = json.dumps(obj).encode()
            # counted BEFORE the body goes out: a client that scrapes
            # /metrics as soon as it has its answer must find it counted
            api._count(self.path, code)
            self.send_response(code)
            if code >= 400 and getattr(self, "_trace", None):
                # echo the request's trace id on error responses: the
                # router relays non-200s verbatim, so a refused/failed
                # request still hands its caller the federated-
                # timeline key
                self.send_header("x-cake-trace", self._trace)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(data)))
            self.end_headers()
            self.wfile.write(data)

        def _retry_json(self, code: int, retry_after_s: float,
                        obj: dict):
            """_json plus a Retry-After header (ceil'd to whole
            seconds, floor 1) — the shared shape of the 429 overload
            and 503 engine-reset responses; retry_after_s also rides
            the body as retry_after_s."""
            retry = max(1, int(-(-retry_after_s // 1)))
            data = json.dumps({**obj, "retry_after_s": retry}).encode()
            api._count(self.path, code)
            self.send_response(code)
            self.send_header("Retry-After", str(retry))
            # attribute the backpressure to THIS replica: the router
            # relays the header verbatim, so clients and router logs
            # can tell which backend computed the Retry-After
            self.send_header("x-cake-replica", str(api.replica_id))
            if getattr(self, "_trace", None):
                self.send_header("x-cake-trace", self._trace)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(data)))
            self.end_headers()
            self.wfile.write(data)

        def _query(self) -> dict:
            """First value of each query param (the filter endpoints'
            input; repeated params keep the first — filters are
            scalar)."""
            if "?" not in self.path:
                return {}
            from urllib.parse import parse_qs
            return {k: v[0] for k, v in
                    parse_qs(self.path.split("?", 1)[1]).items() if v}

        @staticmethod
        def _int_arg(q: dict, key: str):
            """Integer query param or None; a malformed value is a 400
            (silently ignoring ?rid=abc would dump everything — the
            opposite of what the caller asked)."""
            v = q.get(key)
            if v is None:
                return None
            try:
                return int(v)
            except ValueError:
                raise ValueError(f"?{key}= must be an integer, got "
                                 f"{v!r}")

        def _read_body(self) -> dict:
            n = int(self.headers.get("Content-Length", 0))
            if n == 0:
                return {}
            try:
                return json.loads(self.rfile.read(n))
            except json.JSONDecodeError:
                raise ValueError("invalid JSON body")

        def do_GET(self):
            # re-stash per request: on a keep-alive connection a stale
            # value from an earlier POST would mis-attribute this
            # request's error responses to that POST's trace
            self._trace = self.headers.get("x-cake-trace")
            route = self.path.split("?", 1)[0]
            if route == "/api/v1/health":
                # ?lite=1: the router's cheap poll variant (a subtree
                # of the full document; any other value means full)
                lite = self._query().get("lite") == "1"
                doc = api.health(lite=lite)
                if doc["status"] == "ok":
                    # the first one is the start-up clock's mark
                    obs_startup.healthy()
                return self._json(200, doc)
            if self.path == "/api/v1/cluster":
                return self._json(200, api.cluster())
            if route == "/api/v1/requests":
                q = self._query()
                try:
                    cls = q.get("class")
                    if cls is not None:
                        from cake_tpu.sched.classes import (
                            validate_priority,
                        )
                        validate_priority(cls)
                    return self._json(200, api.requests(
                        limit=self._int_arg(q, "limit"),
                        rid=self._int_arg(q, "rid"), cls=cls,
                        since=self._int_arg(q, "since")))
                except ValueError as e:
                    return self._json(400, {"error": str(e)})
            m = _TIMELINE_RE.match(route)
            if m:
                tl = api.request_timeline(int(m.group(1)))
                if tl is None:
                    return self._json(404, {
                        "error": f"unknown rid {m.group(1)} (not "
                                 "admitted, or fell out of the "
                                 "finished-trace ring)"})
                return self._json(200, tl)
            if route == "/api/v1/events":
                q = self._query()
                try:
                    t = q.get("type")
                    if t is not None:
                        from cake_tpu.obs.events import EVENT_TYPES
                        if t not in EVENT_TYPES:
                            raise ValueError(
                                f"unknown event type {t!r} (choose "
                                f"one of {', '.join(EVENT_TYPES)})")
                    return self._json(200, api.events(
                        rid=self._int_arg(q, "rid"), type=t,
                        since=self._int_arg(q, "since"),
                        limit=self._int_arg(q, "limit"),
                        host=q.get("host")))
                except ValueError as e:
                    return self._json(400, {"error": str(e)})
            if route == "/api/v1/fleet":
                return self._json(200, api.fleet())
            if route == "/api/v1/steps":
                try:
                    return self._json(200, api.steps(
                        self._int_arg(self._query(), "limit")))
                except ValueError as e:
                    return self._json(400, {"error": str(e)})
            if route == "/api/v1/anomalies":
                try:
                    return self._json(200, api.anomalies(
                        self._int_arg(self._query(), "limit")))
                except ValueError as e:
                    return self._json(400, {"error": str(e)})
            if self.path == "/api/v1/autotune":
                return self._json(200, api.autotune())
            if self.path in ("/v1/models", "/api/v1/models"):
                # OpenAI client compatibility: SDKs list models on init
                return self._json(200, {
                    "object": "list",
                    "data": [{"id": api.model_name, "object": "model",
                              "created": api.started_at,
                              "owned_by": "cake-tpu"}],
                })
            if self.path in ("/metrics", "/api/v1/metrics"):
                data = api.metrics().encode()
                self.send_response(200)
                self.send_header("Content-Type",
                                 "text/plain; version=0.0.4")
                self.send_header("Content-Length", str(len(data)))
                self.end_headers()
                self.wfile.write(data)
                api._count(self.path, 200)
                return
            self._json(404, {"error": "not found"})  # api/mod.rs:19-21

        def do_POST(self):
            # stashed for the error-path x-cake-trace echo (_json /
            # _retry_json): SSE streams echo it via on_start instead
            self._trace = self.headers.get("x-cake-trace")
            try:
                body = self._read_body()
            except ValueError as e:
                return self._json(400, {"error": str(e)})
            # profiling must work on a FAILED server (a wedged mesh is
            # exactly when an operator wants a live trace), so it
            # dispatches before the health gate below
            if self.path == "/api/v1/profile":
                try:
                    return self._json(200, api.profile(body))
                except obs_steps.ProfileBusyError as e:
                    return self._json(409, {"error": str(e)})
                except ValueError as e:
                    return self._json(400, {"error": str(e)})
                except Exception as e:  # noqa: BLE001
                    log.exception("profile capture failed")
                    return self._json(
                        500, {"error": f"{type(e).__name__}: {e}"})
            if self.path == "/api/v1/drain":
                # dispatches before the health gate below: draining a
                # FAILED server is exactly how an operator evacuates it
                try:
                    return self._json(200, api.drain(body))
                except ValueError as e:
                    return self._json(400, {"error": str(e)})
                except Exception as e:  # noqa: BLE001
                    log.exception("drain failed")
                    return self._json(
                        500, {"error": f"{type(e).__name__}: {e}"})
            # after the body read: responding early would leave unread
            # body bytes desyncing this keep-alive connection
            if api.health_state is not None and api.health_state.failed:
                # fail fast instead of queueing work onto a dead mesh
                return self._json(503, {
                    "error": f"serving failed: {api.health_state.reason}"})
            try:
                if self.path in ("/api/v1/chat/completions",
                                 "/v1/chat/completions"):
                    # the /v1 alias serves OpenAI SDKs pointed at
                    # base_url=.../v1 (they discover via /v1/models)
                    return self._chat(body)
                if self.path == "/api/v1/autotune":
                    from cake_tpu.serve.errors import SwitchInFlightError
                    try:
                        return self._json(200, api.autotune_switch(body))
                    except SwitchInFlightError as e:
                        # one switch at a time: folding every stream is
                        # expensive and a queued second switch would
                        # thrash — the client retries after this one
                        return self._json(409, {"error": str(e)})
                    # ValueError (bad config / autotune off) falls to
                    # the generic 400 below
                if self.path == "/api/v1/image":
                    return self._json(200, api.image(body))
                return self._json(404, {"error": "not found"})
            except ValueError as e:
                # invalid option combinations (e.g. logprobs on the
                # engine-less path) are client errors, not server faults
                if getattr(self, "_stream_started", False):
                    return
                return self._json(400, {"error": str(e)})
            except QueueFull as e:
                if getattr(self, "_stream_started", False):
                    return  # headers already gone; just drop the connection
                # 429 + an HONEST Retry-After: computed seconds until
                # the backlog drains inside the class SLO at the
                # measured service rate (sched/shed.py) or the drain
                # completes (engine.drain_state), not a hardcoded
                # constant — for shed, queue-full and draining alike
                self._retry_json(429, e.retry_after, {
                    "error": ("server draining: admissions are closed"
                              if getattr(e, "draining", False)
                              else "request shed: server saturated for "
                              "this priority class" if e.shed
                              else "queue full"),
                })
            except EngineRequestError as e:
                # typed engine failures (serve/errors.py): a RETRYABLE
                # one (transient reset, storm-breaker stop) is 503 +
                # an honest computed Retry-After — the request itself
                # was fine; a non-retryable one (poison request) is a
                # terminal 500 the client must not blindly resubmit
                log.warning("engine failed request: %s", e)
                if getattr(self, "_stream_started", False):
                    return  # the stream already carried its error event
                if not e.retryable:
                    return self._json(500, {
                        "error": str(e), "retryable": False})
                # body["priority"] holds the merged body/header class
                # (set by _chat before submit), so the estimate is for
                # the failing request's own lane — matching the 429
                # path's per-class computation
                self._retry_json(
                    503,
                    api._engine_retry_after(body.get("priority")),
                    {"error": str(e), "retryable": True})
            except Exception as e:  # noqa: BLE001
                log.exception("request failed")
                if getattr(self, "_stream_started", False):
                    return
                self._json(500, {"error": f"{type(e).__name__}: {e}"})

        def _chat(self, body: dict):
            # x-cake-priority header names the SLO class for clients
            # that cannot edit the body (gateways, sidecars); an
            # explicit body "priority" wins — a JSON null counts as
            # unset (SDKs serialize optional fields as null), so the
            # header still applies then. Unknown values 400 via
            # parse_chat_request's validation.
            hdr = self.headers.get("x-cake-priority")
            if hdr is not None and body.get("priority") is None:
                body["priority"] = hdr
            # durable serving (serve/journal.py): a retried submit
            # carrying the same x-cake-idempotency-key attaches to the
            # existing stream instead of double-admitting; on a
            # streaming reconnect, Last-Event-ID (the standard SSE
            # resume header — the absolute token id of the last event
            # the client saw) replays exactly the missing suffix
            idem_key = self.headers.get("x-cake-idempotency-key")
            last_id = self.headers.get("Last-Event-ID")
            # distributed tracing (x-cake-trace, minted by the
            # front-door router or a client): threaded to the engine
            # tracer + event bus at admission, echoed on the SSE
            # response headers (with the engine rid) and on error
            # responses — the federated timeline's correlation key
            trace = self.headers.get("x-cake-trace")
            if last_id is not None:
                try:
                    last_id = int(last_id)
                except ValueError:
                    raise ValueError(
                        f"Last-Event-ID must be an integer event id, "
                        f"got {last_id!r}")
                if idem_key is None:
                    raise ValueError(
                        "Last-Event-ID resume requires "
                        "x-cake-idempotency-key (the key names the "
                        "stream across reconnects and restarts)")
            if not body.get("stream"):
                return self._json(200, api.chat(
                    body, idempotency_key=idem_key, trace_id=trace))
            self._stream_started = False

            def on_start(rid=None):
                # only once admission + the generation lock are held
                self.send_response(200)
                self.send_header("Content-Type", "text/event-stream")
                self.send_header("Cache-Control", "no-cache")
                self.send_header("Transfer-Encoding", "chunked")
                # attribution before any tokens: which replica serves
                # this stream, under which trace, as which engine rid
                # (the router relays these to its client and joins the
                # trace to this replica's timeline through the rid)
                self.send_header("x-cake-replica", str(api.replica_id))
                if trace is not None:
                    self.send_header("x-cake-trace", trace)
                if rid is not None:
                    self.send_header("x-cake-rid", str(rid))
                self.end_headers()
                self._stream_started = True

            def send_chunk(obj: dict, event_id=None):
                self.wfile.write(sw.sse_chunk(obj, event_id))
                self.wfile.flush()

            # the stream writer sends on the connection itself, without
            # blocking, until the stream ends or the socket would block
            send_chunk.socket = self.connection

            outcome = api.chat(body, send_chunk=send_chunk,
                               on_start=on_start,
                               idempotency_key=idem_key,
                               last_event_id=last_id,
                               trace_id=trace)
            if outcome is DISCONNECTED:
                # handled disconnect: the socket is dead, writing the
                # trailer would only manufacture an error traceback
                api._count(self.path, 200)
                return
            self.wfile.write(b"e\r\ndata: [DONE]\n\n\r\n0\r\n\r\n")
            api._count(self.path, 200)

    return Handler


def start(master, address: str = "127.0.0.1:10128",
          model_name: str = "cake-tpu", block: bool = True, engine=None,
          checkpoint_path: str | None = None, health=None,
          collector=None, announce: str | None = None,
          announce_interval_s: float = 2.0,
          announce_token: str | None = None):
    """Bind and serve (reference api/mod.rs:23-48). When the master holds a
    text model, a continuous-batching engine is built automatically so
    concurrent chat requests share the decode loop.

    checkpoint_path: restore any in-flight requests recorded by a previous
    shutdown, and snapshot unfinished requests on SIGTERM/serve_forever
    exit (serve/checkpoint.py).

    announce: a front-door router's announce listener ("host:port",
    --router-announce on the replica role) — this replica self-registers
    there and ships lite-health-superset telemetry frames every
    announce_interval_s (router/discovery.ReplicaAnnouncer); shutdown
    ships an explicit departure notice FIRST so the router
    drains-then-forgets instead of inferring death from silence."""
    host, port = address.rsplit(":", 1)
    if engine is None and master.llm is not None:
        with STARTUP.phase("engine"):
            engine = master.make_engine()
    if engine is None and master.llm is not None:
        # engine-less locked-path serving: unreachable for the built-in
        # compositions as of round-5 (every sp mode has an engine
        # contract), kept for custom forward adapters that provide no
        # engine_pieces. These flags gate on
        # the engine and silently doing nothing would surprise operators
        if checkpoint_path:
            log.warning("--checkpoint does not apply to engine-less "
                        "(locked-path) serving; no snapshots will be "
                        "taken")
        log.info("engine-less serving: stall watchdog and /metrics "
                 "engine counters are unavailable")
    if health is None and engine is not None:
        # always-on progress watchdog; multi-host callers pass a
        # ServingHealth that additionally heartbeats the followers
        from cake_tpu.parallel.health import ServingHealth
        health = ServingHealth(engine, stall_after_s=getattr(
            master.args, "stall_timeout", 600.0))
    # (starts the engine: a paged one files `warm_steps` and
    # `weights_ready` there, engine._warm_mixed_buckets)
    api = ApiServer(master, model_name, engine=engine, health=health,
                    collector=collector, replica_id=address)
    with STARTUP.phase("server"):
        httpd = _HTTPServer((host, int(port)), make_handler(api))
    log.info("REST API listening on %s", address)

    announcer = None
    if announce is not None:
        from cake_tpu.router.discovery import ReplicaAnnouncer
        # the announced identity doubles as the router's proxy target,
        # so it must be dialable FROM the router: the bound port (a
        # port-0 bind resolves here), and a concrete host when we
        # bound a wildcard
        ahost = host if host not in ("", "0.0.0.0", "::") else "127.0.0.1"
        announcer = ReplicaAnnouncer(
            announce, f"{ahost}:{httpd.server_address[1]}",
            token=announce_token, interval_s=announce_interval_s,
            health=lambda: api.health(lite=True), engine=engine)
        log.info("announcing to router at %s as %s", announce,
                 announcer.replica)

    journal_armed = (engine is not None
                     and getattr(engine, "_journal", None) is not None)
    if engine is not None and (checkpoint_path or journal_armed):
        import os

        from cake_tpu.serve import checkpoint as ckpt

        # arm the pre-fail snapshot: a serving failure (heartbeat loss,
        # engine error) checkpoints in-flight requests BEFORE failing
        # them (engine._fail_all), so a cluster restart resumes them.
        # The weight digest is computed NOW, while the mesh is healthy —
        # at fail time the device stream may be wedged (and the
        # journal's generation header wants it warm for the same
        # reason)
        if checkpoint_path:
            engine.snapshot_path = checkpoint_path
        ckpt.warm_fingerprint(engine)

        if journal_armed:
            from cake_tpu.serve import journal as jr
            try:
                # cold-restart recovery: checkpoint base + journal
                # replay, resubmitted through the fold path — every
                # non-retired stream a kill -9 interrupted completes
                # (greedy: token-identical at f32 KV)
                handles, _ = jr.recover(
                    engine, checkpoint_path=checkpoint_path,
                    strict=True)
                if handles:
                    log.info("journal replay resubmitted %d in-flight "
                             "request(s)", len(handles))
            except Exception as e:  # noqa: BLE001
                # a fingerprint mismatch / unreadable state must not
                # crash-loop startup; sideline the evidence so the
                # next save starts clean
                jpath = engine._journal.path
                for p in (checkpoint_path, jpath,
                          jpath + ".replaying"):
                    if p and os.path.exists(p):
                        try:
                            os.replace(p, p + ".invalid")
                        except OSError:
                            pass
                log.warning("journal/checkpoint replay failed (%s); "
                            "sidelined to *.invalid and starting with "
                            "an empty engine", e)
        elif checkpoint_path and os.path.exists(checkpoint_path):
            try:
                # strict: a fingerprint mismatch (e.g. different weights
                # with identical shapes) must NOT silently replay tokens —
                # the except below sidelines the snapshot instead
                handles, _ = ckpt.restore(engine, checkpoint_path,
                                          strict=True)
                log.info("restored %d in-flight request(s) from %s",
                         len(handles), checkpoint_path)
            except Exception as e:  # noqa: BLE001
                # an unreadable/old-version/incompatible snapshot must not
                # crash-loop server startup; sideline it so the evidence
                # survives and the next save starts clean
                bad = f"{checkpoint_path}.invalid"
                try:
                    os.replace(checkpoint_path, bad)
                except OSError:
                    bad = checkpoint_path
                log.warning("checkpoint restore failed (%s); moved to %s "
                            "and starting with an empty engine", e, bad)

    if engine is not None:
        done = threading.Event()

        def save_and_exit(*_sig):
            if done.is_set():
                return
            done.set()
            # order matters: the router hears the departure notice
            # FIRST (it stops routing NEW work here while our
            # in-flight streams finish — drain-then-forget), then
            # close admissions (new submits 429 with the drain ETA
            # instead of racing the stop), then stop the engine
            # (post-stop submits raise the typed reset error), then
            # snapshot, then tear down HTTP. shutdown() must run on a
            # helper thread — called from the serve_forever thread
            # (the block=True signal path) it deadlocks.
            if announcer is not None:
                announcer.depart()
            try:
                engine.begin_drain()
            except Exception:  # noqa: BLE001
                pass
            engine.stop()
            pm = getattr(engine, "_postmortem", None)
            if pm is not None:
                # black-box bundle on the termination path too: the
                # engine thread is stopped, so every ring is final
                pm.dump("sigterm", engine=engine, force=True)
            if checkpoint_path:
                # keep-or-save decision lives in the engine
                # (shutdown_save), under the same lock as the pre-fail
                # writer: a pre-fail snapshot written by THIS process
                # is authoritative and kept; a checkpoint consumed by
                # this process's restore is overwritten so completed
                # resumes don't replay forever. (With --journal, the
                # write also truncates the journal — the handshake
                # keeping the two restart sources disjoint.)
                engine.shutdown_save(checkpoint_path)
            elif not journal_armed:
                # nothing will resume these after restart: release any
                # still-open waiters with the typed reset error
                # instead of letting them hang until process death
                from cake_tpu.serve.errors import EngineResetError
                engine._fail_all(EngineResetError(
                    "server stopped while this request was in flight"))
            if announcer is not None:
                # terminal frame: the departure notice again, now with
                # the drained (zero-load) health doc — the router's
                # forget condition
                announcer.close()
            threading.Thread(target=httpd.shutdown, daemon=True).start()

        api._shutdown = save_and_exit
        try:
            import signal

            prev_handler = signal.getsignal(signal.SIGTERM)

            def on_sigterm(signum, frame):
                save_and_exit()
                # chain whatever handler was installed before us (an
                # application-level cleanup, jax.distributed teardown, …)
                # instead of silently clobbering it
                if callable(prev_handler):
                    prev_handler(signum, frame)
                elif prev_handler == signal.SIG_DFL:
                    signal.signal(signal.SIGTERM, signal.SIG_DFL)
                    signal.raise_signal(signal.SIGTERM)

            signal.signal(signal.SIGTERM, on_sigterm)
        except ValueError:
            pass  # not the main thread; caller owns signal handling
    else:
        save_and_exit = None

    def serve():
        try:
            httpd.serve_forever()
        finally:
            # snapshot on EVERY exit path (SIGINT, external shutdown()),
            # not just SIGTERM
            if save_and_exit is not None:
                save_and_exit()
            elif announcer is not None:
                # engine-less serving: no save_and_exit path to ship
                # the departure notice — do it here
                announcer.close()
            if health is not None:
                health.close()
            # what it held goes back to the handler threads
            api.stream_writer.close()

    if block:
        serve()
    else:
        t = threading.Thread(target=serve, daemon=True)
        t.start()
    return httpd
