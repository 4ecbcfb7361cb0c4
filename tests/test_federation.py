"""Fleet-scope observability (obs/federation.py + serve/control.py
wire metrics): telemetry federation over real localhost sockets.

THE acceptance pin: a request served while a remote exporter ships a
rid-linked event over a real telemetry socket gets ONE
GET /api/v1/requests/{rid}/timeline whose merged chronology includes
the follower-origin event interleaved in correct wall-clock order with
the coordinator's trace spans, GET /api/v1/fleet reports both hosts
live with applied-seq lag 0 after the control stream drains, and the
federated /metrics exposition (host-labeled remote families) passes
tools/lint_metrics.py. Plus the wire-protocol units: seq-gap -> typed
ControlDesyncError, token-gated exporter rejection, clock-offset
correction, and the 200-op control wire-metrics contract."""

import importlib.util
import json
import pathlib
import threading
import time
import urllib.error
import urllib.request

import pytest

import jax
import jax.numpy as jnp

from cake_tpu.obs import metrics as m
from cake_tpu.obs.events import EventBus
from cake_tpu.obs.federation import (
    TelemetryCollector, TelemetryExporter,
)
from cake_tpu.serve.control import (
    ControlClient, ControlDesyncError, ControlServer, _send_msg,
)

TOOLS = pathlib.Path(__file__).resolve().parents[1] / "tools"
TOKEN = "test-fleet-token"


def _load_lint():
    spec = importlib.util.spec_from_file_location(
        "lint_metrics", TOOLS / "lint_metrics.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _wait_for(cond, timeout=10.0, what="condition"):
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < timeout:
        if cond():
            return
        time.sleep(0.005)
    raise AssertionError(f"timed out waiting for {what}")


def _counter_value(name, **labels):
    fam = m.REGISTRY.get(name)
    if fam is None:
        return 0.0
    return (fam.labels(**labels) if labels else fam).value


# -- control wire protocol ----------------------------------------------------


def _pair(n_followers=1):
    server = ControlServer(n_followers, host="127.0.0.1", token=TOKEN)
    clients = []

    def connect():
        clients.append(ControlClient(f"127.0.0.1:{server.port}",
                                     token=TOKEN))

    ts = [threading.Thread(target=connect) for _ in range(n_followers)]
    for t in ts:
        t.start()
    server.accept_followers()
    for t in ts:
        t.join(5)
    return server, clients


def test_seq_gap_raises_typed_desync():
    """An op seq gap means missed ops = a diverged mirror: recv must
    raise ControlDesyncError instead of silently replaying on."""
    server, (client,) = _pair()
    try:
        server.publish({"op": "noop"})
        op = client.recv()
        assert op["op"] == "noop" and op["seq"] == 1
        # inject a gap: a frame claiming seq 3 while the client last
        # applied seq 1 (op 2 was never delivered)
        _send_msg(server._conns[0],
                  json.dumps({"op": "noop", "seq": 3}).encode())
        with pytest.raises(ControlDesyncError, match="seq gap"):
            client.recv()
    finally:
        client.close()
        server.close()


def test_first_seen_seq_initializes_not_raises():
    """A follower's FIRST op may carry any seq (it joined the channel
    when the stream started, whatever the server's counter says) —
    only subsequent gaps are desyncs."""
    server, (client,) = _pair()
    try:
        for _ in range(3):
            server.publish({"op": "noop"})   # seqs 1..3 pre-connect? no:
        # the client was connected before publish, so it sees 1,2,3;
        # simulate a late joiner with a fresh gap check instead
        client._last_seq = 0
        assert client.recv()["seq"] == 1
        assert client.recv()["seq"] == 2
        client._last_seq = 0                 # fresh follower state
        assert client.recv()["seq"] == 3     # first-seen: accepted
    finally:
        client.close()
        server.close()


def test_control_wire_metrics_advance_under_200_op_exchange():
    """cake_control_ops_total / cake_control_bytes_total{tx,rx} /
    cake_control_publish_seconds all advance across a 200-op
    exchange — the control plane is no longer metrics-dark."""
    ops0 = _counter_value("cake_control_ops_total", op="noop")
    tx0 = _counter_value("cake_control_bytes_total", dir="tx")
    rx0 = _counter_value("cake_control_bytes_total", dir="rx")
    pub_fam = m.REGISTRY.get("cake_control_publish_seconds")
    pub0 = pub_fam.count
    server, (client,) = _pair()
    try:
        got = []

        def drain():
            while True:
                op = client.recv()
                if op is None or op.get("op") == "stop":
                    return
                got.append(op["seq"])

        t = threading.Thread(target=drain, daemon=True)
        t.start()
        for _ in range(200):
            server.publish({"op": "noop", "rows": [1, 2, 3]})
        server.publish({"op": "stop"})
        t.join(10)
        assert not t.is_alive()
        assert got == list(range(1, 201)), "gapless ordered seq stream"
    finally:
        client.close()
        server.close()
    # both sides count in this (shared) process registry: 200 published
    # + 200 received
    assert _counter_value("cake_control_ops_total",
                          op="noop") - ops0 == 400
    assert _counter_value("cake_control_bytes_total", dir="tx") - tx0 > 0
    assert _counter_value("cake_control_bytes_total", dir="rx") - rx0 > 0
    assert pub_fam.count - pub0 == 201
    assert server.published_seq == 201


def test_publish_disconnect_carries_wire_state():
    """The control-hardening satellite: a follower lost at publish
    time surfaces WITH its last-sent seq and the acks map, and
    wire_state() exposes the same for post-mortems."""
    server, (client,) = _pair()
    try:
        server.publish({"op": "noop"})
        assert client.recv()["seq"] == 1
        server.note_ack("proc1", 1)
        state = server.wire_state()
        assert state["published_seq"] == 1
        assert state["acks"] == {"proc1": 1}
        assert state["followers"][0]["last_sent_seq"] == 1
        client.close()
        # the server's next publish hits the dead socket (possibly a
        # send or two later, once the RST lands) — the error must name
        # the follower's last-sent seq and the acks map
        with pytest.raises(RuntimeError) as exc:
            for _ in range(50):
                server.publish({"op": "noop"})
                time.sleep(0.01)
        assert "last_sent_seq=" in str(exc.value)
        assert "'proc1': 1" in str(exc.value)
        assert _counter_value("cake_control_follower_lag_ops",
                              follower="proc1") >= 0
    finally:
        server.close()


def test_broadcast_payload_four_fields_roundtrip():
    """The cli handshake now ships FOUR |-separated fields (control,
    token, heartbeat, telemetry): a worst-case payload fits the
    broadcast buffer and the follower-side partition parse recovers
    every field (empty telemetry field = federation off)."""
    from cake_tpu.serve.control import broadcast_control_address
    long_host = "h" * 253
    payload = (f"{long_host}:65535|{'a' * 32}|{long_host}:65534|"
               f"{long_host}:65533")
    got = broadcast_control_address(payload)   # 1-process collective
    assert got == payload
    addr, _, rest = got.partition("|")
    token, _, rest = rest.partition("|")
    hb_addr, _, tel_addr = rest.partition("|")
    assert addr.endswith(":65535") and token == "a" * 32
    assert hb_addr.endswith(":65534") and tel_addr.endswith(":65533")
    # federation off: the telemetry field is empty, not absent
    addr, _, rest = f"{long_host}:1|tok|{long_host}:2|".partition("|")
    token, _, rest = rest.partition("|")
    hb_addr, _, tel_addr = rest.partition("|")
    assert tel_addr == ""


# -- telemetry federation ------------------------------------------------------


def test_federation_two_exporters_per_host_views():
    """Two in-process exporters over localhost: the collector keeps
    per-host namespaced views (metrics, events, applied seq), both
    hosts read live, and ?host= style reads stay separated."""
    col = TelemetryCollector(host="127.0.0.1", token=TOKEN,
                             local_host="proc0")
    exps = []
    try:
        for i, applied in ((1, 7), (2, 9)):
            reg = m.Registry()
            c = m.Counter("fed_demo_total", "demo",
                          labelnames=("k",), registry=reg)
            c.labels(k=f"host{i}").inc(i)
            bus = EventBus(capacity=64, observe_metrics=False)
            bus.publish("kv_spill", rid=100 + i, pages=i)
            exp = TelemetryExporter(
                f"127.0.0.1:{col.port}", host=f"proc{i}", token=TOKEN,
                interval_s=30.0, registry=reg, events=bus,
                applied_seq=lambda a=applied: a, start=False)
            assert exp.flush()
            exps.append(exp)
        _wait_for(lambda: sorted(col.hosts()) == ["proc1", "proc2"],
                  what="both hosts ingested")
        _wait_for(lambda: all(
            col.fleet()["hosts"][h]["frames"] >= 1
            for h in ("proc1", "proc2")), what="frames ingested")
        fleet = col.fleet()
        assert fleet["hosts"]["proc1"]["applied_seq"] == 7
        assert fleet["hosts"]["proc2"]["applied_seq"] == 9
        assert all(fleet["hosts"][h]["live"]
                   for h in ("proc1", "proc2"))
        # per-host event views: host-tagged, filterable
        evs1 = col.events_for(host="proc1")
        assert [e["rid"] for e in evs1] == [101]
        assert evs1[0]["host"] == "proc1"
        both = col.events_for(type="kv_spill")
        assert {e["host"] for e in both} == {"proc1", "proc2"}
        assert col.events_for(host="nosuch") == []
        # federated render: one TYPE block, both hosts' samples
        text = col.render_federated(set())
        assert text.count("# TYPE fed_demo_total counter") == 1
        assert 'fed_demo_total{k="host1",host="proc1"} 1' in text
        assert 'fed_demo_total{k="host2",host="proc2"} 2' in text
        assert _load_lint().lint(text) == []
    finally:
        for exp in exps:
            exp.close(flush=False)
        col.close()


def test_clock_offset_corrects_skewed_host():
    """An exporter whose wall clock is 120s ahead: the collector's
    per-host offset (min over frames of rx - t_wall) recovers the
    skew, and its events merge at their TRUE time next to an
    unskewed host's events — the wall-clock-ordered-timeline
    contract."""
    SKEW = 120.0

    class SkewBus:
        """Event source stamping with the SAME skewed clock the
        exporter samples — the contract the exporter documents."""

        def __init__(self, skew):
            self.skew = skew
            self.evs = []

        def publish(self, type_, rid, **fields):
            self.evs.append({"seq": len(self.evs) + 1,
                             "ts": time.time() + self.skew,
                             "type": type_, "rid": rid, **fields})

        def snapshot(self, since=None):
            evs = [e for e in self.evs
                   if since is None or e["seq"] > since]
            return list(evs), (evs[-1]["seq"] if evs
                               else (since or 0))

    col = TelemetryCollector(host="127.0.0.1", token=TOKEN,
                             local_host="proc0")
    skew_bus, true_bus = SkewBus(SKEW), SkewBus(0.0)
    skewed = TelemetryExporter(
        f"127.0.0.1:{col.port}", host="skewed", token=TOKEN,
        interval_s=30.0, events=skew_bus,
        registry=m.Registry(),
        clock=lambda: time.time() + SKEW, start=False)
    honest = TelemetryExporter(
        f"127.0.0.1:{col.port}", host="honest", token=TOKEN,
        interval_s=30.0, events=true_bus,
        registry=m.Registry(), start=False)
    try:
        t_first = time.time()
        skew_bus.publish("kv_spill", rid=1, order=1)
        time.sleep(0.05)
        true_bus.publish("kv_restore", rid=1, order=2)
        time.sleep(0.05)
        skew_bus.publish("prefix_hit", rid=1, order=3)
        assert skewed.flush() and honest.flush()
        _wait_for(lambda: len(col.events_for(rid=1)) == 3,
                  what="three events ingested")
        fleet = col.fleet()
        off = fleet["hosts"]["skewed"]["clock_offset_s"]
        assert off is not None and abs(off + SKEW) < 1.0, \
            f"offset should recover ~-{SKEW}s, got {off}"
        assert abs(fleet["hosts"]["honest"]["clock_offset_s"]) < 1.0
        merged = col.events_for(rid=1)
        # corrected order is the TRUE publish order, despite the
        # skewed host's raw stamps being 120s in the future
        assert [e["order"] for e in merged] == [1, 2, 3]
        assert abs(merged[0]["ts"] - t_first) < 1.0
    finally:
        skewed.close(flush=False)
        honest.close(flush=False)
        col.close()


def test_wall_clock_step_resets_offset():
    """A remote host whose wall clock steps BACKWARD (NTP) after the
    offset converged: min-over-frames alone would pin the stale
    pre-step offset forever (the post-step deltas are all larger).
    The frame's mono sample detects the step (t_wall - t_mono moved)
    and resets the estimate so it re-converges on the new epoch."""
    col = TelemetryCollector(host="127.0.0.1", token=TOKEN,
                             local_host="proc0")
    step = {"wall": 0.0}
    exp = TelemetryExporter(
        f"127.0.0.1:{col.port}", host="stepper", token=TOKEN,
        interval_s=30.0, registry=m.Registry(),
        clock=lambda: time.time() + step["wall"], start=False)
    try:
        assert exp.flush()
        _wait_for(lambda: col.fleet()["hosts"].get("stepper", {})
                  .get("frames", 0) >= 1, what="first frame")
        off0 = col.fleet()["hosts"]["stepper"]["clock_offset_s"]
        assert abs(off0) < 1.0
        step["wall"] = -50.0                  # NTP stepped back 50s
        assert exp.flush()
        _wait_for(lambda: col.fleet()["hosts"]["stepper"]["frames"]
                  >= 2, what="post-step frame")
        off = col.fleet()["hosts"]["stepper"]["clock_offset_s"]
        assert abs(off - 50.0) < 1.0, \
            f"offset must re-converge on the new epoch, got {off}"
    finally:
        exp.close(flush=False)
        col.close()


def test_collector_rejects_unauthenticated_exporter():
    """Token gating (the ControlServer hello discipline): a wrong or
    missing token never registers a host view and the connection is
    closed — a rogue peer on the serving network cannot pose as a
    fleet host or feed the coordinator fake telemetry."""
    col = TelemetryCollector(host="127.0.0.1", token=TOKEN,
                             local_host="proc0")
    try:
        bad = TelemetryExporter(
            f"127.0.0.1:{col.port}", host="evil", token="wrong",
            interval_s=30.0, registry=m.Registry(),
            connect_timeout_s=2.0, start=False)
        bad.flush()          # hello goes out; the collector drops it
        bad.close(flush=False)
        import socket as _socket
        raw = _socket.create_connection(("127.0.0.1", col.port),
                                        timeout=5)
        raw.sendall(b"\x00\x00\x00\x02{}")   # tokenless hello
        raw.settimeout(5)
        assert raw.recv(1) == b"", "collector must close the socket"
        raw.close()
        time.sleep(0.1)
        assert col.hosts() == [], "no host view for rejected peers"
    finally:
        col.close()


def test_max_hosts_cap_refuses_invented_names():
    """Per-host state is bounded at topology scale: a peer inventing
    host names beyond max_hosts is refused, not accumulated."""
    col = TelemetryCollector(host="127.0.0.1", token=TOKEN,
                             local_host="proc0", max_hosts=2)
    exps = []
    try:
        for name in ("a", "b", "c"):
            exp = TelemetryExporter(
                f"127.0.0.1:{col.port}", host=name, token=TOKEN,
                interval_s=30.0, registry=m.Registry(),
                connect_timeout_s=2.0, start=False)
            exp.flush()
            exps.append(exp)
            # the collector serves each peer on a thread of its own:
            # let this one register before the next connects, or "c"
            # can take the second place ahead of "b"
            _wait_for(lambda: name in col.hosts() or len(col.hosts()) == 2,
                      what=f"host {name} registered or refused")
        time.sleep(0.1)
        assert sorted(col.hosts()) == ["a", "b"]
    finally:
        for exp in exps:
            exp.close(flush=False)
        col.close()


# -- THE acceptance: one request, two hosts, one timeline ---------------------


@pytest.fixture(scope="module")
def fleet_server():
    """Tiny engine + HTTP API + a live federation plane: a control
    server drained by a fake follower thread (applied-seq source) and
    a remote exporter shipping host proc1's events/metrics over a real
    localhost telemetry socket."""
    from cake_tpu.api.server import start
    from cake_tpu.args import Args
    from cake_tpu.master import Master
    from cake_tpu.models.llama.config import LlamaConfig
    from cake_tpu.models.llama.generator import (
        ByteTokenizer, LlamaGenerator,
    )
    from cake_tpu.models.llama.params import init_params
    from cake_tpu.ops.sampling import SamplingConfig

    cfg = LlamaConfig.tiny(num_hidden_layers=2)
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    gen = LlamaGenerator(cfg, params, ByteTokenizer(cfg.vocab_size),
                         max_seq_len=256,
                         sampling=SamplingConfig(temperature=0.0),
                         cache_dtype=jnp.float32)
    master = Master(Args(sample_len=4), text_generator=gen)
    engine = master.make_engine()

    control = ControlServer(1, host="127.0.0.1", token=TOKEN)
    applied = {"seq": 0}

    def follower():
        client = ControlClient(f"127.0.0.1:{control.port}",
                               token=TOKEN)
        try:
            while True:
                op = client.recv()
                if op is None:
                    return
                if isinstance(op.get("seq"), int):
                    applied["seq"] = op["seq"]
                if op.get("op") == "stop":
                    return
        finally:
            client.close()

    drain = threading.Thread(target=follower, daemon=True)
    drain.start()
    control.accept_followers()

    collector = TelemetryCollector(host="127.0.0.1", token=TOKEN,
                                   control=control, local_host="proc0")
    remote_reg = m.Registry()
    m.Gauge("fed_remote_demo", "remote-only federated family",
            registry=remote_reg).set(1)
    remote_bus = EventBus(capacity=256, observe_metrics=False)
    exporter = TelemetryExporter(
        f"127.0.0.1:{collector.port}", host="proc1", token=TOKEN,
        interval_s=30.0, registry=remote_reg, events=remote_bus,
        applied_seq=lambda: applied["seq"], start=False)

    httpd = start(master, address="127.0.0.1:0", block=False,
                  engine=engine, collector=collector)
    host, port = httpd.server_address[:2]
    ctx = {
        "url": f"http://{host}:{port}", "engine": engine,
        "control": control, "collector": collector,
        "exporter": exporter, "remote_bus": remote_bus,
        "applied": applied, "drain": drain,
    }
    yield ctx
    httpd.shutdown()
    exporter.close(flush=False)
    collector.close()
    control.close()


def _get(url, path):
    try:
        with urllib.request.urlopen(url + path, timeout=30) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def test_fleet_timeline_spans_hosts_and_lag_drains(fleet_server):
    """The PR's acceptance criterion, end to end over HTTP: a request
    whose timeline includes a follower-origin event shipped over a
    real localhost telemetry socket, interleaved in wall-clock order
    with coordinator spans; /api/v1/fleet with both hosts live and
    applied-seq lag 0 after the control stream drains; ?host= event
    filtering; and a lint-clean federated /metrics exposition."""
    eng = fleet_server["engine"]
    control = fleet_server["control"]
    exporter = fleet_server["exporter"]
    url = fleet_server["url"]

    # a few replayed ops before the request (the follower drains them)
    for _ in range(3):
        control.publish({"op": "noop"})

    h = eng.submit([5] * 6, max_new_tokens=48, temperature=0.0,
                   repeat_penalty=1.0)
    rid = h._req.rid
    _wait_for(lambda: len(h._req.out_tokens) >= 2, timeout=120,
              what="stream under way")
    # the follower-origin event, shipped over the REAL telemetry
    # socket while the request is mid-decode
    fleet_server["remote_bus"].publish("kv_spill", rid=rid, pages=3)
    assert exporter.flush()
    _wait_for(lambda: fleet_server["collector"].events_for(rid=rid),
              what="remote event ingested")
    assert h.wait(timeout=120)

    # drain the control stream, ship the terminal applied seq
    control.publish({"op": "stop"})
    fleet_server["drain"].join(10)
    assert not fleet_server["drain"].is_alive()
    assert exporter.flush()
    _wait_for(lambda: (fleet_server["collector"].fleet()["hosts"]
                       ["proc1"]["applied_seq"]
                       == control.published_seq),
              what="terminal applied seq ingested")

    # -- the timeline spans hosts, in wall-clock order
    code, tl = _get(url, f"/api/v1/requests/{rid}/timeline")
    assert code == 200 and tl["rid"] == rid
    ts = [e["t"] for e in tl["timeline"]]
    assert ts == sorted(ts)
    remote = [e for e in tl["timeline"] if e.get("host") == "proc1"]
    assert len(remote) == 1 and remote[0]["event"] == "kv_spill"
    names = [e["event"] for e in tl["timeline"]]
    i_ev = tl["timeline"].index(remote[0])
    assert names.index("admitted") < i_ev < names.index("retired"), \
        "follower event must interleave inside the request's life"
    assert tl["summary"]["causes"].get("kv_spill", 0) >= 1
    assert tl["summary"]["hosts"] == ["proc0", "proc1"]

    # -- fleet: both hosts live, lag 0 after drain
    code, fleet = _get(url, "/api/v1/fleet")
    assert code == 200
    assert fleet["local_host"] == "proc0"
    assert set(fleet["hosts"]) >= {"proc0", "proc1"}
    assert fleet["hosts"]["proc0"]["live"] is True
    assert fleet["hosts"]["proc0"]["lag_ops"] == 0
    assert fleet["hosts"]["proc1"]["live"] is True
    assert fleet["hosts"]["proc1"]["lag_ops"] == 0
    assert fleet["published_seq"] == control.published_seq
    assert fleet["hosts"]["proc1"]["frames"] >= 2

    # -- ?host= filters
    code, evs = _get(url, f"/api/v1/events?host=proc1&rid={rid}")
    assert code == 200 and evs["host"] == "proc1"
    assert [e["type"] for e in evs["events"]] == ["kv_spill"]
    assert all(e["host"] == "proc1" for e in evs["events"])
    code, _local = _get(url, "/api/v1/events?host=proc0")
    assert code == 200 and _local["host"] == "proc0"
    code, err = _get(url, "/api/v1/events?host=bogus")
    assert code == 400 and "unknown host" in err["error"]

    # query strings must not 404 a known route
    code, fleet_q = _get(url, "/api/v1/fleet?x=1")
    assert code == 200 and fleet_q["local_host"] == "proc0"

    # -- federated /metrics: host-labeled remote families, lint-clean
    text = urllib.request.urlopen(url + "/api/v1/metrics",
                                  timeout=30).read().decode()
    assert 'fed_remote_demo{host="proc1"} 1' in text
    assert "# TYPE fed_remote_demo gauge" in text
    assert 'cake_fleet_host_up{host="proc1"} 1' in text
    lm = _load_lint()
    assert lm.lint(text) == []
    # recovery_state-style wire introspection reaches the fleet rows
    assert control.wire_state()["acks"]["proc1"] \
        == control.published_seq


def test_host_events_limit_cursor_never_skips(fleet_server):
    """The local-bus cursor contract holds for remote ?host= streams:
    a limit-truncated page's cursor resumes at the last RETURNED
    event, so paging with ?since=cursor walks the whole stream instead
    of skipping the truncated remainder forever."""
    bus = fleet_server["remote_bus"]
    exporter = fleet_server["exporter"]
    url = fleet_server["url"]
    first = bus.publish("kv_restore", rid=999, n=0).seq
    for i in (1, 2):
        bus.publish("kv_restore", rid=999, n=i)
    assert exporter.flush()
    _wait_for(lambda: len(fleet_server["collector"].events_for(
        rid=999)) == 3, what="three events ingested")
    seen, since = [], first - 1
    for _ in range(3):
        code, page = _get(url, "/api/v1/events?host=proc1&rid=999"
                               f"&limit=1&since={since}")
        assert code == 200 and len(page["events"]) == 1
        seen.append(page["events"][0]["n"])
        since = page["cursor"]
    assert seen == [0, 1, 2], \
        f"limit-truncated cursor skipped events: {seen}"
    # an un-truncated page's cursor is the host's newest seq
    code, page = _get(url, f"/api/v1/events?host=proc1&rid=999"
                           f"&since={since}")
    assert code == 200 and page["events"] == []
    assert page["cursor"] == fleet_server["collector"] \
        .host_cursor("proc1")
