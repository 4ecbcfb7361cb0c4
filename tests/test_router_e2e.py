"""End-to-end acceptance: 2 REAL in-process engine replicas behind the
router over localhost HTTP (ISSUE 14).

  * shared-prefix requests route to ONE replica: its per-engine prefix
    hits advance (the source feeding cake_prefix_paged_hits_total —
    asserted per-engine because both in-process replicas share the one
    process-global metrics registry), the other replica's stay 0;
  * a drained replica receives ZERO new admissions while its in-flight
    stream finishes, and the drain 429 carries x-cake-replica;
  * a killed replica's keyed SSE client reconnects through the router
    with Last-Event-ID and completes token-identical at f32 KV on the
    surviving replica (fresh-admission suppression in api/server.py);
  * the lite health document is a subtree of the full one (the
    ?lite=1 contract the router polls).
"""

import http.client
import json
import threading
import time
import urllib.error
import urllib.request
from http.server import ThreadingHTTPServer

import jax.numpy as jnp
import pytest

T = 256
PAGE = 8
GEN = 10


@pytest.fixture(scope="module")
def params(tiny_config):
    import jax

    from cake_tpu.models.llama.params import init_params
    return init_params(tiny_config, jax.random.PRNGKey(0),
                       dtype=jnp.float32)


def _engine(tiny_config, params, **kw):
    from cake_tpu.models.llama.generator import ByteTokenizer
    from cake_tpu.ops.sampling import SamplingConfig
    from cake_tpu.serve.engine import InferenceEngine
    kw.setdefault("max_slots", 2)
    kw.setdefault("max_seq_len", T)
    kw.setdefault("kv_pages", 48)
    kw.setdefault("kv_page_size", PAGE)
    kw.setdefault("paged_attn", "fold")
    kw.setdefault("auto_prefix_system", True)
    return InferenceEngine(
        tiny_config, params, ByteTokenizer(tiny_config.vocab_size),
        sampling=SamplingConfig(temperature=0.0, repeat_penalty=1.0),
        # f32 KV: token identity must exercise routing/failover, not
        # bf16 tie-breaks
        cache_dtype=jnp.float32,
        **kw)


def _replica(tiny_config, params, tag, **kw):
    """One engine + ApiServer + HTTP server; returns (engine, api,
    httpd, addr)."""
    from cake_tpu.api.server import ApiServer, make_handler
    from cake_tpu.args import Args
    from cake_tpu.master import Master
    eng = _engine(tiny_config, params, **kw)
    master = Master(Args(sample_len=GEN), text_generator=None)
    master.llm = object()
    api = ApiServer(master, engine=eng, replica_id=tag)
    httpd = ThreadingHTTPServer(("127.0.0.1", 0), make_handler(api))
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    addr = f"127.0.0.1:{httpd.server_address[1]}"
    api.replica_id = addr
    return eng, api, httpd, addr


def _router_over(replicas, tiny_config, **kw):
    from cake_tpu.models.llama.generator import ByteTokenizer
    from cake_tpu.router import start_router
    kw.setdefault("poll_interval_s", 0.05)
    kw.setdefault("stale_after_s", 1.0)
    httpd, router = start_router(
        replicas, address="127.0.0.1:0", block=False,
        tokenizer=ByteTokenizer(tiny_config.vocab_size), **kw)
    router.tracker.poll_once()
    return httpd, router, f"127.0.0.1:{httpd.server_address[1]}"


def _messages(tenant: str, turn: str):
    return [{"role": "system",
             "content": f"You are {tenant}, a terse test assistant."},
            {"role": "user", "content": turn}]


def _post(addr, body, headers=None, timeout=600):
    req = urllib.request.Request(
        f"http://{addr}/api/v1/chat/completions",
        data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json", **(headers or {})})
    return urllib.request.urlopen(req, timeout=timeout)


def _read_sse(resp, until_done=True, max_events=10_000):
    """Parse an SSE byte stream into [(id, doc)] pairs; stops at [DONE]
    or EOF."""
    events, cur_id = [], None
    for raw in resp:
        line = raw.decode()
        if line.startswith("id: "):
            cur_id = int(line[4:].strip())
        elif line.startswith("data: "):
            payload = line[6:].strip()
            if payload == "[DONE]":
                break
            events.append((cur_id, json.loads(payload)))
            if len(events) >= max_events:
                break
    return events


def _text_of(events):
    return "".join(
        e.get("choices", [{}])[0].get("delta", {}).get("content") or ""
        for _, e in events if "choices" in e)


# -- affinity: one replica holds the pages ------------------------------------

def test_shared_prefix_requests_route_to_one_replica(tiny_config,
                                                     params):
    engA, apiA, httpdA, addrA = _replica(tiny_config, params, "A")
    engB, apiB, httpdB, addrB = _replica(tiny_config, params, "B")
    rhttpd, router, raddr = _router_over([addrA, addrB], tiny_config)
    try:
        key = router.affinity_key(
            {"messages": _messages("tenant-x", "q")})
        assert key is not None   # paged fingerprint, from lite health
        for i in range(4):
            out = json.loads(_post(raddr, {
                "messages": _messages("tenant-x", f"turn {i}"),
                "max_tokens": 4}).read())
            assert out["choices"][0]["message"]["content"] is not None
        done = (engA.stats.requests_completed,
                engB.stats.requests_completed)
        assert sorted(done) == [0, 4], done
        home, cold = (engA, engB) if done[0] else (engB, engA)
        # the home replica's prefix-hit counter (the per-engine source
        # of cake_prefix_paged_hits_total) advanced; the cold one's
        # did not, and it holds no registration either
        assert home.stats.prefix_hits >= 3
        assert cold.stats.prefix_hits == 0
        assert len(cold._prefixes) == 0
        assert len(home._prefixes) == 1
        # a different tenant may land elsewhere, but never splits:
        # both its requests go to ONE replica too
        beforeA, beforeB = (engA.stats.requests_completed,
                            engB.stats.requests_completed)
        for i in range(2):
            _post(raddr, {"messages": _messages("tenant-y", f"t{i}"),
                          "max_tokens": 2}).read()
        deltas = sorted((engA.stats.requests_completed - beforeA,
                         engB.stats.requests_completed - beforeB))
        assert deltas == [0, 2], deltas
    finally:
        rhttpd.shutdown()
        router.close()
        for h in (httpdA, httpdB):
            h.shutdown()
        for e in (engA, engB):
            e.stop(timeout=10)


# -- lite health contract -----------------------------------------------------

def _subtree(lite, full, path=""):
    assert isinstance(lite, dict) and isinstance(full, dict), path
    for k, v in lite.items():
        assert k in full, f"lite key {path}/{k} missing from full health"
        if isinstance(v, dict):
            _subtree(v, full[k], f"{path}/{k}")


def test_lite_health_is_subtree_of_full(tiny_config, params):
    engA, apiA, httpdA, addrA = _replica(
        tiny_config, params, "A", priority_classes=True)
    try:
        full = apiA.health()
        lite = apiA.health(lite=True)
        _subtree(lite, full)
        # the poll set the router needs is present
        for k in ("status", "replica", "queue_depth",
                  "active_requests", "decode_slots", "page_size",
                  "config_epoch", "switch_in_flight", "recovery",
                  "queue_depth_by_class"):
            assert k in lite, k
        assert lite["page_size"] == PAGE
        assert lite["recovery"]["breaker"]["tripped"] is False
        # the heavy blocks stay OUT of lite
        for k in ("engine_config", "requests_completed",
                  "tokens_generated", "model"):
            assert k not in lite, k
        # HTTP: ?lite=1 serves the lite doc; bare path the full one
        via_http = json.loads(urllib.request.urlopen(
            f"http://{addrA}/api/v1/health?lite=1", timeout=30).read())
        assert set(via_http) == set(lite)
        via_full = json.loads(urllib.request.urlopen(
            f"http://{addrA}/api/v1/health", timeout=30).read())
        assert "engine_config" in via_full
        assert via_full["replica"] == addrA
    finally:
        httpdA.shutdown()
        engA.stop(timeout=10)


# -- drain: zero new admissions, in-flight finishes ---------------------------

def test_drained_replica_gets_zero_new_admissions(tiny_config, params):
    engA, apiA, httpdA, addrA = _replica(tiny_config, params, "A")
    engB, apiB, httpdB, addrB = _replica(tiny_config, params, "B")
    rhttpd, router, raddr = _router_over([addrA, addrB], tiny_config)
    gate, held = threading.Event(), threading.Event()
    try:
        # place tenant-d's home deterministically by asking the router
        body = {"messages": _messages("tenant-d", "warm"),
                "max_tokens": 2}
        json.loads(_post(raddr, body).read())
        homeA = engA.stats.requests_completed == 1
        home_eng, home_api, home_addr = \
            (engA, apiA, addrA) if homeA else (engB, apiB, addrB)
        cold_eng = engB if homeA else engA

        # long in-flight stream on the home replica, PACED at a
        # quarter second a token until the drain below has landed: the
        # tiny model finishes 24 tokens before this thread can look,
        # so polling for a held slot raced the stream's end and lost
        # in every run. Paced, not frozen: the engine thread must keep
        # running its commands (the refused submit below registers a
        # system prompt on it)
        emit = home_eng._emit

        def emit_paced(req, *a, **kw):
            emit(req, *a, **kw)
            held.set()
            gate.wait(0.25)

        home_eng._emit = emit_paced
        # posted from a thread: random weights decode to no text, so
        # the router has no line to flush (and urlopen no status to
        # return) before the held stream ends
        box = {}
        poster = threading.Thread(
            target=lambda: box.update(resp=_post(raddr, {
                "messages": _messages("tenant-d", "long answer please"),
                "stream": True, "max_tokens": 24}, timeout=600)),
            daemon=True)
        poster.start()
        assert held.wait(60), "the stream never started decoding"
        assert home_eng.active >= 1

        # drain the home replica directly (the operator's move)
        dreq = urllib.request.Request(
            f"http://{home_addr}/api/v1/drain",
            data=json.dumps({"timeout_s": 60}).encode(),
            headers={"Content-Type": "application/json"})
        st = json.loads(urllib.request.urlopen(dreq, timeout=30).read())
        assert st["draining"] is True

        # a direct submit to the draining replica 429s WITH the
        # x-cake-replica attribution header (the satellite bugfix)
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(home_addr, {"messages": _messages("t", "x")})
        assert ei.value.code == 429
        assert ei.value.headers["x-cake-replica"] == home_addr
        assert int(ei.value.headers["Retry-After"]) >= 1

        # the router observes the drain on its next poll…
        router.tracker.poll_once()
        assert not router.tracker.get(home_addr).admitting
        base_home = home_eng.stats.requests_completed
        # …and routes EVERY new admission (any tenant — including the
        # drained home's own) to the other replica
        for i in range(3):
            out = json.loads(_post(raddr, {
                "messages": _messages("tenant-d", f"post-drain {i}"),
                "max_tokens": 2}).read())
            assert out["choices"]
        assert cold_eng.stats.requests_completed >= 3
        # the in-flight stream FINISHED on the draining home (drain
        # lets in-flight work complete; zero new admissions landed)
        gate.set()
        poster.join(60)
        events = _read_sse(box["resp"])
        assert _text_of(events)
        assert home_eng.stats.requests_completed == base_home + 1
    finally:
        gate.set()
        rhttpd.shutdown()
        router.close()
        for h in (httpdA, httpdB):
            h.shutdown()
        for e in (engA, engB):
            e.stop(timeout=10)


# -- kill + keyed reconnect through the router --------------------------------

def test_failover_merged_timeline_spans_both_replicas(tiny_config,
                                                      params):
    """ISSUE 15 acceptance: one keyed SSE request; the owning
    replica's ENGINE dies mid-stream (its HTTP front stays up — the
    wedged-accelerator shape); the client resumes through the router
    on the survivor, token-identical; the router-merged
    GET /api/v1/requests/{rid}/timeline then shows the router hops AND
    BOTH replicas' spans in one wall-clock order with a
    failover_resume cause."""
    from cake_tpu.serve.errors import EngineResetError
    engA, apiA, httpdA, addrA = _replica(tiny_config, params, "A")
    engB, apiB, httpdB, addrB = _replica(tiny_config, params, "B")
    rhttpd, router, raddr = _router_over([addrA, addrB], tiny_config)
    conn = None
    try:
        body = {"messages": _messages("tenant-t", "trace me a story"),
                "stream": True, "max_tokens": 24}
        hdrs = {"Content-Type": "application/json",
                "x-cake-idempotency-key": "trace-drill"}
        conn = http.client.HTTPConnection(raddr, timeout=600)
        conn.request("POST", "/api/v1/chat/completions",
                     body=json.dumps(body).encode(), headers=hdrs)
        resp = conn.getresponse()
        assert resp.status == 200
        # the router minted a trace and joined it to the home
        # replica's engine rid before the first token
        tid = resp.getheader("x-cake-trace")
        home = resp.getheader("x-cake-replica")
        rid_home = int(resp.getheader("x-cake-rid"))
        assert tid and home in (addrA, addrB)
        pre_events, cur_id = [], None
        while len(pre_events) < 3:
            line = resp.readline().decode()
            if line.startswith("id: "):
                cur_id = int(line[4:].strip())
            elif line.startswith("data: ") and line.strip() != "data:":
                doc = json.loads(line[6:])
                if doc.get("choices", [{}])[0].get("delta", {}) \
                        .get("content"):
                    pre_events.append((cur_id, doc))
        last_seen = max(i for i, _ in pre_events)
        pre_text = _text_of(pre_events)

        # kill the home ENGINE only: in-flight stream gets the typed
        # retryable error event; the HTTP front stays up, so the dead
        # home can still SERVE ITS TIMELINE (and refuses new work
        # with a roamable 503)
        h_eng = engA if home == addrA else engB
        s_addr = addrB if home == addrA else addrA
        h_eng._fail_all(EngineResetError("accelerator wedged"))
        h_eng.stop(timeout=10)
        tail = resp.read().decode()
        assert '"error"' in tail
        conn.close()
        conn = None

        # keyed reconnect through the router: sticky home refuses
        # (engine stopped -> retryable 503) -> roams to the survivor,
        # fresh admission + Last-Event-ID exact-suffix resume — and
        # the SAME trace id continues (the sticky map remembers it)
        conn = http.client.HTTPConnection(raddr, timeout=600)
        conn.request("POST", "/api/v1/chat/completions",
                     body=json.dumps(body).encode(),
                     headers={**hdrs, "Last-Event-ID": str(last_seen)})
        resp2 = conn.getresponse()
        assert resp2.status == 200
        assert resp2.getheader("x-cake-trace") == tid
        assert resp2.getheader("x-cake-replica") == s_addr
        rid_surv = int(resp2.getheader("x-cake-rid"))
        post_events = _read_sse(resp2)
        assert all(i is None or i > last_seen
                   for i, _ in post_events), post_events
        post_text = _text_of(post_events)
        conn.close()
        conn = None

        # token identity preserved across the resume (f32 KV): the
        # non-stream attach on the same key returns the survivor's
        # whole transcript
        out = json.loads(_post(raddr, {
            "messages": _messages("tenant-t", "trace me a story"),
            "max_tokens": 24}, headers={
                "x-cake-idempotency-key": "trace-drill"}).read())
        assert pre_text + post_text == \
            out["choices"][0]["message"]["content"]

        # THE merged timeline, queried by the SURVIVOR's rid through
        # the router
        tl = json.loads(urllib.request.urlopen(
            f"http://{raddr}/api/v1/requests/{rid_surv}/timeline",
            timeout=30).read())
        assert tl["trace"] == tid
        # both replicas named, with their own rids
        rows = {r["replica"]: r for r in tl["replicas"]}
        assert rows[home]["rid"] == rid_home
        assert rows[s_addr]["rid"] == rid_surv
        # the failover_resume cause is in the summary
        assert tl["summary"]["causes"].get("failover_resume", 0) >= 1
        # BOTH replicas' engine spans present (source=trace entries
        # tagged with each replica), plus the router's own hops
        ev = [(e.get("source"), e.get("event"), e.get("replica"))
              for e in tl["timeline"]]
        assert ("trace", "admitted", home) in ev
        assert ("trace", "error", home) in ev
        assert ("trace", "admitted", s_addr) in ev
        assert ("trace", "retired", s_addr) in ev
        assert any(s == "router" and n == "failover_resume"
                   for s, n, _ in ev)
        # ... in ONE wall-clock order: the home's story strictly
        # precedes the resume, which precedes the survivor's admission
        ts = [e["t"] for e in tl["timeline"]]
        assert ts == sorted(ts)
        idx = {k: i for i, k in enumerate(ev)}
        resume_i = next(i for i, (s, n, _) in enumerate(ev)
                        if s == "router" and n == "failover_resume")
        assert idx[("trace", "admitted", home)] < resume_i \
            < idx[("trace", "admitted", s_addr)]
        # the home's rid resolves to the same merged story
        tl2 = json.loads(urllib.request.urlopen(
            f"http://{raddr}/api/v1/requests/{rid_home}/timeline",
            timeout=30).read())
        assert tl2["trace"] == tid
    finally:
        if conn is not None:
            conn.close()
        rhttpd.shutdown()
        router.close()
        for h in (httpdA, httpdB):
            h.shutdown()
        for e in (engA, engB):
            e.stop(timeout=10)


def test_killed_replica_keyed_sse_reconnects_token_identical(
        tiny_config, params):
    from cake_tpu.serve.errors import EngineResetError
    engA, apiA, httpdA, addrA = _replica(tiny_config, params, "A")
    engB, apiB, httpdB, addrB = _replica(tiny_config, params, "B")
    rhttpd, router, raddr = _router_over([addrA, addrB], tiny_config)
    conn = None
    try:
        body = {"messages": _messages("tenant-k", "tell me a story"),
                "stream": True, "max_tokens": 24}
        hdrs = {"Content-Type": "application/json",
                "x-cake-idempotency-key": "kill-drill"}
        conn = http.client.HTTPConnection(raddr, timeout=600)
        conn.request("POST", "/api/v1/chat/completions",
                     body=json.dumps(body).encode(), headers=hdrs)
        resp = conn.getresponse()
        assert resp.status == 200
        # read a few events, tracking the client's high-water mark
        pre_events, cur_id = [], None
        while len(pre_events) < 3:
            line = resp.readline().decode()
            if line.startswith("id: "):
                cur_id = int(line[4:].strip())
            elif line.startswith("data: ") and line.strip() != "data:":
                doc = json.loads(line[6:])
                if doc.get("choices", [{}])[0].get("delta", {}) \
                        .get("content"):
                    pre_events.append((cur_id, doc))
        last_seen = max(i for i, _ in pre_events)
        pre_text = _text_of(pre_events)
        assert 0 < last_seen < 24

        # identify + KILL the home replica: fail in-flight (the typed
        # terminal event clients see on a dying box), stop the engine,
        # and close its listening socket so reconnects are refused
        home = router.policy.sticky_home("kill-drill")
        assert home in (addrA, addrB)
        h_eng, h_httpd = (engA, httpdA) if home == addrA \
            else (engB, httpdB)
        s_eng = engB if home == addrA else engA
        h_eng._fail_all(EngineResetError("replica killed"))
        h_eng.stop(timeout=10)
        h_httpd.shutdown()
        h_httpd.server_close()
        # drain the rest of the broken stream (terminal error event or
        # socket close — either way, NOT a silent success)
        try:
            tail = resp.read().decode()
            assert '"error"' in tail or tail == ""
        except (OSError, http.client.HTTPException):
            pass
        conn.close()
        conn = None

        # keyed reconnect THROUGH the router with Last-Event-ID: the
        # sticky home is dead -> hard-eject failover -> fresh admission
        # on the survivor, which re-runs the prompt deterministically
        # and serves exactly the unseen suffix
        conn = http.client.HTTPConnection(raddr, timeout=600)
        conn.request("POST", "/api/v1/chat/completions",
                     body=json.dumps(body).encode(),
                     headers={**hdrs, "Last-Event-ID": str(last_seen)})
        resp2 = conn.getresponse()
        assert resp2.status == 200
        post_events = _read_sse(resp2)
        text_events = [(i, e) for i, e in post_events
                       if e.get("choices", [{}])[0].get("delta", {})
                       .get("content")]
        assert text_events, post_events
        # no event at or below the client's high-water mark: no dups
        assert all(i is None or i > last_seen
                   for i, _ in post_events), post_events
        post_text = _text_of(post_events)
        assert router.tracker.get(home).ejected

        # token identity at f32 KV: (pre-kill text from the dead home)
        # + (resumed suffix from the survivor) == the survivor's WHOLE
        # transcript, fetched via a non-stream attach on the same key
        out = json.loads(_post(raddr, {
            "messages": _messages("tenant-k", "tell me a story"),
            "max_tokens": 24}, headers={
                "x-cake-idempotency-key": "kill-drill"}).read())
        full_text = out["choices"][0]["message"]["content"]
        assert pre_text + post_text == full_text
        assert s_eng.stats.requests_completed >= 1
    finally:
        if conn is not None:
            conn.close()
        rhttpd.shutdown()
        router.close()
        for h in (httpdA, httpdB):
            try:
                h.shutdown()
            except Exception:  # noqa: BLE001
                pass
        for e in (engA, engB):
            e.stop(timeout=10)


# -- fleet discovery: announce-only replicas end-to-end (ISSUE 18) ------------

def test_discovered_replicas_serve_depart_and_failover(tiny_config,
                                                       params):
    """ISSUE 18 acceptance (real HTTP, CPU lane): a replica in NO
    --replicas list self-registers over the announce channel and
    receives routed traffic; a second hot-joins mid-fleet; the keyed
    SSE client of a KILLED replica fails over to the survivor with no
    duplicate events; the corpse is forgotten from /api/v1/fleet
    (inferred departure); and the survivor's explicit departure notice
    drains-then-forgets — ZERO new admissions while its in-flight
    stream finishes."""
    from cake_tpu.models.llama.generator import ByteTokenizer
    from cake_tpu.router import start_router
    from cake_tpu.router.discovery import ReplicaAnnouncer
    from cake_tpu.serve.errors import EngineResetError

    rhttpd, router = start_router(
        [], address="127.0.0.1:0", block=False,
        tokenizer=ByteTokenizer(tiny_config.vocab_size),
        poll_interval_s=0.05, stale_after_s=1.0,
        announce="127.0.0.1:0", announce_interval_s=0.1,
        forget_grace_s=0.5)
    raddr = f"127.0.0.1:{rhttpd.server_address[1]}"
    aport = router.discovery.port

    def _announce(api, eng, addr):
        return ReplicaAnnouncer(
            f"127.0.0.1:{aport}", addr, interval_s=0.1,
            health=lambda: api.health(lite=True), engine=eng)

    def _until(pred, timeout_s=60):
        deadline = time.monotonic() + timeout_s
        while not pred() and time.monotonic() < deadline:
            time.sleep(0.02)
        assert pred()

    engA, apiA, httpdA, addrA = _replica(tiny_config, params, "A")
    engB, apiB, httpdB, addrB = _replica(tiny_config, params, "B")
    annA = annB = conn = None
    try:
        # -- join: the router was started with an EMPTY replica list --
        annA = _announce(apiA, engA, addrA)
        _until(lambda: (st := router.tracker.get(addrA)) is not None
               and st.admitting)
        out = json.loads(_post(raddr, {
            "messages": _messages("tenant-disc", "hello"),
            "max_tokens": 2}).read())
        assert out["choices"]
        assert engA.stats.requests_completed == 1
        fleet = json.loads(urllib.request.urlopen(
            f"http://{raddr}/api/v1/fleet", timeout=10).read())
        assert fleet["replicas"][addrA]["source"] == "announced"
        assert fleet["replicas"][addrA]["live"] is True

        # -- hot-join the second replica mid-fleet --
        annB = _announce(apiB, engB, addrB)
        _until(lambda: (st := router.tracker.get(addrB)) is not None
               and st.admitting)

        # -- keyed stream; kill its home; reconnect onto the survivor
        body = {"messages": _messages("tenant-disc", "a story"),
                "stream": True, "max_tokens": 24}
        hdrs = {"Content-Type": "application/json",
                "x-cake-idempotency-key": "disc-drill"}
        conn = http.client.HTTPConnection(raddr, timeout=600)
        conn.request("POST", "/api/v1/chat/completions",
                     body=json.dumps(body).encode(), headers=hdrs)
        resp = conn.getresponse()
        assert resp.status == 200
        pre_events, cur_id = [], None
        while len(pre_events) < 3:
            line = resp.readline().decode()
            if line.startswith("id: "):
                cur_id = int(line[4:].strip())
            elif line.startswith("data: ") and line.strip() != "data:":
                doc = json.loads(line[6:])
                if doc.get("choices", [{}])[0].get("delta", {}) \
                        .get("content"):
                    pre_events.append((cur_id, doc))
        last_seen = max(i for i, _ in pre_events)
        home = router.policy.sticky_home("disc-drill")
        assert home in (addrA, addrB)
        h_eng, h_httpd, h_ann = (engA, httpdA, annA) \
            if home == addrA else (engB, httpdB, annB)
        s_eng, s_api, s_addr, s_ann = (engB, apiB, addrB, annB) \
            if home == addrA else (engA, apiA, addrA, annA)
        # the crash: no departure notice — announce frames just STOP
        h_ann.close(depart=False)
        h_eng._fail_all(EngineResetError("replica killed"))
        h_eng.stop(timeout=10)
        h_httpd.shutdown()
        h_httpd.server_close()
        try:
            resp.read()
        except (OSError, http.client.HTTPException):
            pass
        conn.close()
        conn = None
        conn = http.client.HTTPConnection(raddr, timeout=600)
        conn.request("POST", "/api/v1/chat/completions",
                     body=json.dumps(body).encode(),
                     headers={**hdrs, "Last-Event-ID": str(last_seen)})
        resp2 = conn.getresponse()
        assert resp2.status == 200
        post_events = _read_sse(resp2)
        assert _text_of(post_events)
        assert all(i is None or i > last_seen
                   for i, _ in post_events), post_events
        conn.close()
        conn = None
        assert s_eng.stats.requests_completed >= 1

        # -- the corpse is REAPED: quiet past staleness + grace, the
        # poll fallback ejected it, discovery infers the departure --
        _until(lambda: router.tracker.get(home) is None, timeout_s=60)
        fleet = json.loads(urllib.request.urlopen(
            f"http://{raddr}/api/v1/fleet", timeout=10).read())
        assert home not in fleet["replicas"]
        evs = json.loads(urllib.request.urlopen(
            f"http://{raddr}/api/v1/events?type=replica_departed",
            timeout=10).read())["events"]
        assert any(e.get("replica") == home and e.get("inferred")
                   for e in evs), evs

        # -- explicit departure drains-then-forgets on the survivor --
        conn = http.client.HTTPConnection(raddr, timeout=600)
        conn.request("POST", "/api/v1/chat/completions",
                     body=json.dumps({
                         "messages": _messages("tenant-disc", "again"),
                         "stream": True, "max_tokens": 24}).encode(),
                     headers={"Content-Type": "application/json"})
        resp3 = conn.getresponse()
        assert resp3.status == 200
        _until(lambda: s_eng.active >= 1)
        base_done = s_eng.stats.requests_completed
        assert s_ann.depart(timeout_s=5.0) is True
        _until(lambda: (st := router.tracker.get(s_addr)) is None
               or st.departing)
        # ZERO new admissions after the notice: the fleet-wide refusal
        # is a 503 with NO invented Retry-After (warm-up is over)
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(raddr, {"messages": _messages("t", "x"),
                          "max_tokens": 2})
        assert ei.value.code == 503
        assert ei.value.headers.get("Retry-After") is None
        # ...while the in-flight stream FINISHES on the departing
        # survivor, which is then forgotten (load drained to zero)
        events = _read_sse(resp3)
        assert _text_of(events)
        assert s_eng.stats.requests_completed == base_done + 1
        _until(lambda: router.tracker.get(s_addr) is None)
    finally:
        if conn is not None:
            conn.close()
        for a in (annA, annB):
            if a is not None:
                a.close(depart=True)
        rhttpd.shutdown()
        router.close()
        for h in (httpdA, httpdB):
            try:
                h.shutdown()
            except Exception:  # noqa: BLE001
                pass
        for e in (engA, engB):
            e.stop(timeout=10)
