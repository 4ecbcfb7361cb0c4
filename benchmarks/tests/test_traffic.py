import json
import os
import time

import pytest

import fake_server
from harness import e2e, traffic as tfc
from harness.spec import BENCH_DIR, SpecError


def load(name):
    with open(os.path.join(BENCH_DIR, "traffic", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", ["chat-closed", "decode-long",
                                  "chat-closed-4chip"])
def test_every_run_sends_the_same_multiset(name):
    t = load(name)
    a, b = tfc.Mix(t, 1, 512), tfc.Mix(t, 3000000007, 512)
    key = lambda m: sorted((m.items[i]["class"], m.items[i]["prompt"],
                            m.items[i]["out"]) for i in m.order)
    assert key(a) == key(b)
    assert a.order == b.order                 # the order is the file's
    assert a.item(7)["content"] != b.item(7)["content"]   # the words: seed
    assert tfc.Mix(dict(t, order_seed=5), 1, 512).order != a.order
    assert a.item(7)["content"] == tfc.Mix(t, 1, 512).item(7)["content"]
    assert a.item(7)["content"] != a.item(7 + len(a.order))["content"]
    for it in a.items:
        c = tfc.class_by_name(t)[it["class"]]
        assert c["lo"] <= it["prompt"] <= c["hi"]


def test_chat_closed_is_the_stated_mix():
    m = tfc.Mix(load("chat-closed"), 0, 512)
    assert len(m.items) == 50
    counts = {}
    for it in m.items:
        counts[it["class"]] = counts.get(it["class"], 0) + 1
    assert counts == {"w1": 15, "w3": 15, "w6": 10, "w10": 6, "w14": 4}
    outs = sorted(it["out"] for it in m.items)
    assert outs.count(48) == 20 and outs.count(96) == 20 \
        and outs.count(160) == 10
    assert max(it["prompt"] + it["out"] for it in m.items) <= 2048


def test_probe_is_fixed_content_just_behind_the_first_wave():
    t = load("chat-closed")
    a, b = tfc.Mix(t, 1, 512, overhead=2), tfc.Mix(t, 2, 512, overhead=2)
    at = t["clients"] + 2
    assert a.item(at)["probe"] and b.item(at)["probe"]
    assert a.item(at)["content"] == b.item(at)["content"]
    assert len(a.item(at)["content"].split()) == a.item(at)["prompt"] - 2


def test_weights_must_be_the_multisets_proportions():
    t = load("decode-long")
    t["prompt_classes"][0]["weight"] = 0.5
    with pytest.raises(SpecError):
        tfc.expand_multiset(t)


TINY = {"loop": "closed", "clients": 2, "ramp_s": 0,
        "prompt_classes": [{"name": "a", "lo": 4, "hi": 8, "weight": 1.0}],
        "multiset": [{"class": "a", "out": 5, "n": 4}]}


def test_closed_loop_against_a_known_service_time():
    first_s, gap_s = 0.05, 0.02
    httpd, state = fake_server.start(first_s, gap_s)
    try:
        loop = tfc.ClosedLoop(httpd.server_address[1],
                              tfc.Mix(TINY, 0, 64), clients=2)
        t0 = time.monotonic()
        loop.start()
        time.sleep(1.0)
        t1 = time.monotonic()
        loop.stop()
    finally:
        httpd.shutdown()
    done = [r for r in loop.records if r["finished"]]
    service = first_s + 4 * gap_s             # 0.13 s a request
    # two clients, each sending again as soon as it has its answer
    assert 2 * 1.0 / service * 0.6 <= len(done) <= 2 * 1.0 / service + 2
    assert not any(r["failed"] for r in loop.records)
    assert all(len(r["token_t"]) == 5 for r in done)
    ttfts = [e2e.ttft_s(r) for r in done]
    assert e2e.median(ttfts) == pytest.approx(first_s, abs=0.03)
    assert e2e.tpot_p50_ms(done, t0, t1) == pytest.approx(1000 * gap_s,
                                                          abs=8)
    # never more than two in flight: a closed loop
    assert state["rid"] <= len(loop.records) + 2
    assert e2e.median(d for _t, d in loop.turnarounds) < 0.02


def test_open_loop_sends_on_schedule_and_counts_from_due():
    t = dict(TINY, loop="open", rate_rps=20.0)
    del t["clients"]
    assert tfc.open_schedule(t, 0.5) == pytest.approx(
        [k / 20.0 for k in range(10)])
    burst = dict(t, burst={"every_s": 0.25, "size": 3})
    assert tfc.open_schedule(burst, 0.5) == [0.0] * 3 + [0.25] * 3
    httpd, _state = fake_server.start(0.2, 0.0)
    try:
        loop = tfc.make_loop(httpd.server_address[1], tfc.Mix(t, 0, 64), 0.5)
        loop.start()
        time.sleep(1.2)
        loop.stop()
    finally:
        httpd.shutdown()
    assert len(loop.records) == 10            # sent whatever was in flight
    assert all(r["finished"] for r in loop.records)
    assert max(r["late_s"] for r in loop.records) < 0.05
    for r in loop.records:
        assert e2e.ttft_s(r) == pytest.approx(0.2 + r["late_s"], abs=0.03)
