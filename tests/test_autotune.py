"""cake_tpu/autotune units: config space + offline fit + controller.

The controller tests drive synthetic signal streams with a fake clock —
the discipline contracts (hysteresis holds, cooldown respected, the
rollback guard fires EXACTLY once and pins) are pure host-side logic,
so no engine or device is involved here. The engine-coupled half
(token identity across a live switch, page conservation, the API
contract) lives in tests/test_autotune_engine.py.
"""

import importlib.util
import json
import pathlib

import pytest

from cake_tpu.autotune import (
    AutotuneController, AutotuneSignals, ControllerConfig, EngineConfig,
    Observation, PolicyTable, config_key, extract_observations, fit,
    switch_guard, validate_config,
)

TOOLS = pathlib.Path(__file__).resolve().parents[1] / "tools"


# -- space ------------------------------------------------------------------


# `mixed_batch` was a knob until PR 28 (a paged engine has one prefill
# path now): a policy file or a POST /api/v1/autotune body that still
# carries it is refused by name, like any key that never was one
@pytest.mark.parametrize("key,value", [("max_seq_len", 512),
                                       ("mixed_batch", "off")])
def test_config_roundtrip_and_unknown_keys(key, value):
    cfg = EngineConfig(slots=16, decode_scan=4, kv_pages=64,
                       kv_page_size=128, kv_dtype="int8",
                       paged_attn="fold")
    assert EngineConfig.from_dict(cfg.to_dict()) == cfg
    with pytest.raises(ValueError,
                       match=f"unknown engine config keys .'{key}'"):
        EngineConfig.from_dict({"slots": 4, key: value})
    with pytest.raises(ValueError, match=key):
        PolicyTable.from_dict({"version": 1, "regimes": [
            {"max_offered_rps": None,
             "config": {"slots": 4, key: value}}]})


def test_validate_reuses_args_rules():
    # int8 without pages: the args.py rule, surfaced through the space
    with pytest.raises(ValueError, match="int8 requires --kv-pages"):
        validate_config(EngineConfig(kv_dtype="int8"))
    with pytest.raises(ValueError, match="paged_attn"):
        validate_config(EngineConfig(paged_attn="nope"))
    with pytest.raises(ValueError, match="max-slots"):
        validate_config(EngineConfig(slots=0))
    with pytest.raises(ValueError, match=">= 1"):
        validate_config(EngineConfig(kv_pages=0, kv_page_size=16))
    # a pool smaller than one max-length stream stays LEGAL (the
    # engine's submit() fail-fasts oversized requests; live switches
    # additionally refuse pools an in-flight stream does not fit)
    validate_config(EngineConfig(kv_pages=2, kv_page_size=16),
                    max_seq_len=128)


def test_config_key_normalizes_spellings():
    # dense points: paged-only knobs are irrelevant and must not split
    a = EngineConfig(slots=8, kv_page_size=128, paged_attn="auto")
    b = EngineConfig(slots=8, kv_page_size=64, paged_attn="fold",
                     kv_dtype="f8_e4m3")
    assert config_key(a) == config_key(b)
    # paged points: auto resolves to the backend impl (fold on CPU)
    p = EngineConfig(slots=8, kv_pages=16, paged_attn="auto")
    q = EngineConfig(slots=8, kv_pages=16, paged_attn="fold")
    assert config_key(p) == config_key(q)
    assert config_key(a) != config_key(p)
    # dtype spellings normalize ("f32" == "float32"); int8 is its own
    # point and None (follow the engine cache dtype) stays distinct
    assert (config_key(EngineConfig(kv_pages=16, kv_dtype="f32"))
            == config_key(EngineConfig(kv_pages=16,
                                       kv_dtype="float32")))
    assert (config_key(EngineConfig(kv_pages=16, kv_dtype="int8"))
            != config_key(EngineConfig(kv_pages=16, kv_dtype="f32")))
    # default-aware: with the engine's base dtype supplied, an unset
    # kv_dtype compares equal to the default spelled explicitly (the
    # engine passes this so a policy naming the default is a no-op)
    assert (config_key(EngineConfig(kv_pages=16),
                       default_kv_dtype="bf16")
            == config_key(EngineConfig(kv_pages=16, kv_dtype="bf16"),
                          default_kv_dtype="bf16"))
    assert (config_key(EngineConfig(kv_pages=16))
            != config_key(EngineConfig(kv_pages=16, kv_dtype="bf16")))


def test_switch_guard_gates_int8_to_float_only():
    i8 = EngineConfig(kv_pages=16, kv_dtype="int8")
    f32 = EngineConfig(kv_pages=16)
    reason = switch_guard(i8, f32)
    assert reason is not None and "int8" in reason
    assert switch_guard(f32, i8) is None          # quantize forward: ok
    assert switch_guard(i8, EngineConfig(kv_pages=32,
                                         kv_dtype="int8")) is None
    assert switch_guard(f32, EngineConfig(slots=32)) is None


def test_switch_guard_gates_widening_from_int4():
    """int4 sits below int8 in the precision lattice: every
    rank-RAISING hot switch is refused (already-streamed tokens were
    decoded against the narrower pool; a re-prefill at wider KV could
    diverge from them), every narrowing or same-rank move is legal."""
    i4 = EngineConfig(kv_pages=16, kv_dtype="int4")
    i8 = EngineConfig(kv_pages=16, kv_dtype="int8")
    f32 = EngineConfig(kv_pages=16)
    r = switch_guard(i4, i8)
    assert r is not None and "int4-pool -> int8-pool" in r
    r = switch_guard(i4, f32)
    assert r is not None and "int4-pool -> float-pool" in r
    # the int8 -> float text stays pinned (PR 9 contract)
    assert "int8-pool -> float-pool" in switch_guard(i8, f32)
    # the narrowing chain and geometry moves stay legal
    assert switch_guard(f32, i4) is None
    assert switch_guard(i8, i4) is None
    assert switch_guard(i4, EngineConfig(kv_pages=32,
                                         kv_dtype="int4")) is None


# -- policy table + fit -----------------------------------------------------


def _obs(slots, rps, tps):
    return Observation(config=EngineConfig(slots=slots, kv_pages=64),
                       offered_rps=rps, tok_s=tps)


def test_fit_picks_best_config_per_regime_and_merges():
    obs = (
        # low load: 8 slots wins
        [_obs(8, 1.0, 200), _obs(32, 1.0, 120)] * 3
        # high load: 32 slots wins (the BENCH_MEASURED migration)
        + [_obs(8, 20.0, 300), _obs(32, 20.0, 1200)] * 3
    )
    policy = fit(obs, max_regimes=4)
    assert policy.regimes[-1]["max_offered_rps"] is None  # catch-all
    assert policy.lookup(0.5).slots == 8
    assert policy.lookup(50.0).slots == 32
    # adjacent same-config bins merged: at most one boundary remains
    assert len(policy.regimes) == 2


def test_fit_rejects_empty():
    with pytest.raises(ValueError, match="no usable"):
        fit([])


def _qobs(slots, rps, tps, ttft=None, attain=None):
    return Observation(config=EngineConfig(slots=slots, kv_pages=64),
                       offered_rps=rps, tok_s=tps, ttft_p99_s=ttft,
                       attainment=attain)


def test_fit_auto_emits_quality_guards_from_the_winner(tmp_path):
    """ISSUE 16: non-catch-all regimes get max_ttft_p99_s (headroom x
    the WINNING config's worst observed p99) and min_attainment
    (margin x its worst attainment) — the losing config's numbers
    must not shape the guards, and the catch-all never carries any
    (lookup returns it unconditionally: a guard there is dead)."""
    obs = (
        [_qobs(8, 1.0, 200, ttft=0.05, attain=0.99),
         # the loser is WORSE on both axes: leaking it into the guard
         # would inflate the envelope
         _qobs(32, 1.0, 120, ttft=0.4, attain=0.5)] * 3
        + [_qobs(8, 20.0, 300),
           _qobs(32, 20.0, 1200, ttft=0.3, attain=0.97)] * 3
    )
    policy = fit(obs, max_regimes=4)
    low = policy.regimes[0]
    assert low["max_offered_rps"] is not None
    assert low["max_ttft_p99_s"] == pytest.approx(1.5 * 0.05)
    assert low["min_attainment"] == pytest.approx(0.9 * 0.99)
    assert "max_ttft_p99_s" not in policy.regimes[-1]
    assert "min_attainment" not in policy.regimes[-1]
    # the guards survive a save/load round-trip and still validate
    p = tmp_path / "policy.json"
    policy.save(str(p))
    loaded = PolicyTable.load(str(p))
    assert loaded.regimes[0]["max_ttft_p99_s"] == \
        pytest.approx(1.5 * 0.05)
    # custom headroom/margin knobs flow through
    wide = fit(obs, ttft_headroom=3.0, attainment_margin=0.5)
    assert wide.regimes[0]["max_ttft_p99_s"] == pytest.approx(0.15)
    assert wide.regimes[0]["min_attainment"] == pytest.approx(0.495)


def test_fit_guards_optional_and_signal_gated():
    obs = ([_qobs(8, 1.0, 200, ttft=0.05, attain=0.99)] * 3
           + [_qobs(32, 20.0, 1200)] * 3)
    # emit_guards=False: plain PR-era tables
    off = fit(obs, emit_guards=False)
    assert all("max_ttft_p99_s" not in r and "min_attainment" not in r
               for r in off.regimes)
    # observations without quality signals fit guard-free regimes
    plain = fit([_qobs(8, 1.0, 200)] * 3 + [_qobs(32, 20.0, 1200)] * 3)
    assert all("max_ttft_p99_s" not in r and "min_attainment" not in r
               for r in plain.regimes)


def test_extract_observations_reads_attainment_shapes():
    doc = {"lines": [
        {"config": {"slots": 8}, "offered_rps": 2.0, "tok_s": 100,
         # per-class dict (obs/slo.py shape): worst class wins
         "attainment": {"interactive": 0.9, "batch": 1.0}},
        {"config": {"slots": 16}, "offered_rps": 2.0, "tok_s": 100,
         "attainment": 0.7},
        {"config": {"slots": 32}, "offered_rps": 2.0, "tok_s": 100},
    ]}
    obs = sorted(extract_observations(doc),
                 key=lambda o: o.config.slots)
    assert [o.attainment for o in obs] == [0.9, 0.7, None]


def test_policy_save_load_validate(tmp_path):
    policy = fit([_obs(8, 1.0, 100), _obs(32, 9.0, 900)],
                 max_regimes=2)
    p = tmp_path / "policy.json"
    policy.save(str(p))
    loaded = PolicyTable.load(str(p))
    assert (config_key(loaded.lookup(100.0))
            == config_key(policy.lookup(100.0)))
    # a table without a catch-all is refused (lookup must be total)
    with pytest.raises(ValueError, match="catch-all"):
        PolicyTable(regimes=[{"max_offered_rps": 2.0,
                              "config": {"slots": 8}}]).validate()
    with pytest.raises(ValueError, match="version"):
        PolicyTable.from_dict({"version": 99, "regimes": []})


def test_extract_observations_walks_nested_bench_json():
    doc = {
        "note": "round file",
        "lines": [
            {"metric": "x", "value": 1.0,
             "autotune_observations": [
                 {"config": {"slots": 8}, "offered_rps": 2.0,
                  "tok_s": 215.0},
                 {"config": {"slots": 16}, "offered_rps": 8.0,
                  "tok_s": 441.0},
             ]},
            {"config": {"slots": 32}, "offered_rps": 30.0,
             "tok_s": 1229.0},
            {"config": {"slots": 32, "bogus_knob": 1},
             "tok_s": 1.0},               # malformed: skipped
        ],
    }
    obs = extract_observations(doc)
    assert sorted(o.config.slots for o in obs) == [8, 16, 32]


def test_observations_from_step_log(tmp_path):
    recs = []
    # two 10s windows: 1 admission + 100 decode tokens, then 2 + 300
    for t, kind, tokens in [(0.0, "prefill", 1), (1.0, "decode", 60),
                            (2.0, "decode_scan", 40),
                            (11.0, "prefill", 1), (11.5, "prefill", 1),
                            (12.0, "mixed", 300)]:
        recs.append({"ts": 1000.0 + t, "kind": kind, "tokens": tokens,
                     "rows": 1})
    p = tmp_path / "steps.jsonl"
    p.write_text("".join(json.dumps(r) + "\n" for r in recs))
    from cake_tpu.autotune import observations_from_step_log
    obs = observations_from_step_log(str(p), EngineConfig(slots=16),
                                     window_s=10.0)
    assert len(obs) == 2
    assert obs[0].tok_s == pytest.approx(10.0)    # 100 tokens / 10s
    assert obs[1].tok_s == pytest.approx(30.0)
    assert obs[1].offered_rps == pytest.approx(0.2)
    assert all(o.config.slots == 16 for o in obs)
    # mixed-mode captures (the paged default) have NO standalone
    # prefill records — admissions ride mixed steps as chunk rows, and
    # the admission proxy must read them or every window shows 0 load
    q = tmp_path / "mixed.jsonl"
    q.write_text(json.dumps(
        {"ts": 1000.0, "kind": "mixed", "tokens": 50, "rows": 4,
         "rows_decode": 2, "rows_prefill": 2, "rows_idle": 0}) + "\n")
    mob = observations_from_step_log(str(q), EngineConfig(slots=16),
                                     window_s=10.0)
    assert mob[0].offered_rps == pytest.approx(0.2)
    assert mob[0].tok_s == pytest.approx(5.0)


def test_autotune_fit_cli(tmp_path, capsys):
    spec = importlib.util.spec_from_file_location(
        "autotune_fit", TOOLS / "autotune_fit.py")
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    bench = tmp_path / "bench.json"
    bench.write_text(json.dumps({
        "autotune_observations": [
            {"config": {"slots": 8}, "offered_rps": 1.0, "tok_s": 200},
            {"config": {"slots": 32}, "offered_rps": 20.0,
             "tok_s": 1200},
        ]}))
    out = tmp_path / "policy.json"
    assert tool.main(["--bench", str(bench), "--out", str(out)]) == 0
    policy = PolicyTable.load(str(out))
    assert policy.lookup(100.0).slots == 32
    # step-log ingestion requires a paired config
    assert tool.main(["--step-log", "x.jsonl", "--out",
                      str(out)]) == 2
    # nothing usable -> fit failure, not a traceback
    empty = tmp_path / "empty.json"
    empty.write_text("{}")
    assert tool.main(["--bench", str(empty), "--out", str(out)]) == 1
    capsys.readouterr()


# -- controller -------------------------------------------------------------


LO = EngineConfig(slots=8, kv_pages=64)
HI = EngineConfig(slots=32, kv_pages=64)


def _policy():
    return PolicyTable(regimes=[
        {"max_offered_rps": 5.0, "config": LO},
        {"max_offered_rps": None, "config": HI},
    ]).validate()


def _controller(clock, **kw):
    kw.setdefault("interval_s", 1.0)
    kw.setdefault("window", 2)
    kw.setdefault("hold", 2)
    kw.setdefault("cooldown_s", 10.0)
    kw.setdefault("rollback_window", 2)
    kw.setdefault("rollback_frac", 0.7)
    return AutotuneController(_policy(), LO,
                              config=ControllerConfig(**kw),
                              now_fn=lambda: clock[0])


def _sig(t, rps, tps=100.0):
    return AutotuneSignals(t=t, offered_rps=rps, service_tps=tps)


def test_hysteresis_holds_through_a_one_window_spike():
    clock = [0.0]
    c = _controller(clock, window=1)
    # steady low load: no proposal
    for t in range(3):
        assert c.decide(_sig(float(t), 1.0)) is None
    # ONE noisy high window must not switch (hold=2)
    assert c.decide(_sig(3.0, 50.0)) is None
    # back to low: the streak resets — still nothing
    assert c.decide(_sig(4.0, 1.0)) is None
    assert c.decide(_sig(5.0, 1.0)) is None
    # sustained high load: the hold is satisfied on the 2nd
    # CONSECUTIVE window naming the same target
    assert c.decide(_sig(6.0, 50.0)) is None       # streak 1
    got = c.decide(_sig(7.0, 50.0))                # streak 2 == hold
    assert got is not None
    target, reason = got
    assert config_key(target) == config_key(HI) and reason == "auto"


def test_cooldown_respected_after_a_switch():
    clock = [0.0]
    c = _controller(clock, hold=1, rollback_frac=0.0,
                    rollback_window=1)
    got = c.decide(_sig(0.0, 50.0))
    assert got is not None
    clock[0] = 0.5
    c.on_switched(HI, LO, pre_rate=100.0, reason="auto")
    # guard verdict (accepted: frac=0 never rolls back), then cooldown
    assert c.decide(_sig(1.0, 1.0)) is None
    # load says "go back to LO" but the cooldown forbids flapping
    for t in (2.0, 5.0, 9.0):
        assert c.decide(_sig(t, 1.0)) is None
    # past the cooldown: the downswitch is allowed again
    assert c.decide(_sig(11.0, 1.0)) is not None


def test_rollback_fires_exactly_once_and_pins():
    clock = [0.0]
    c = _controller(clock, hold=1, cooldown_s=0.0)
    # drive the up-switch (pre-switch service rate 100 tok/s)
    got = c.decide(_sig(0.0, 50.0, tps=100.0))
    assert got is not None
    clock[0] = 0.1
    c.on_switched(HI, LO, pre_rate=100.0, reason="auto")
    # post-switch service rate collapses: the guard must revert after
    # rollback_window samples — and not before
    assert c.decide(_sig(1.0, 50.0, tps=10.0)) is None
    got = c.decide(_sig(2.0, 50.0, tps=10.0))
    assert got is not None
    target, reason = got
    assert reason == "rollback"
    assert config_key(target) == config_key(LO)
    clock[0] = 2.1
    c.on_switched(LO, HI, pre_rate=10.0, reason="rollback")
    # HI is pinned: sustained high load proposes NOTHING ever again,
    # and the guard (disarmed by the rollback) cannot fire twice
    for t in range(3, 12):
        assert c.decide(_sig(float(t), 50.0, tps=10.0)) is None
    assert any(e["action"] == "rollback" for e in c.decision_log())
    assert c.state()["pinned"] == 1


def test_rollback_guard_accepts_a_good_switch():
    clock = [0.0]
    c = _controller(clock, hold=1, cooldown_s=0.0)
    assert c.decide(_sig(0.0, 50.0, tps=100.0)) is not None
    c.on_switched(HI, LO, pre_rate=100.0, reason="auto")
    # service rate IMPROVED: the guard rules "accepted", no revert
    assert c.decide(_sig(1.0, 50.0, tps=300.0)) is None
    assert c.decide(_sig(2.0, 50.0, tps=300.0)) is None
    assert c.decide(_sig(3.0, 50.0, tps=300.0)) is None
    assert any(e["action"] == "accepted" for e in c.decision_log())
    assert not any(e["action"] == "rollback"
                   for e in c.decision_log())


def test_manual_switch_does_not_arm_the_guard():
    clock = [0.0]
    c = _controller(clock, hold=1, cooldown_s=0.0)
    c.on_switched(HI, LO, pre_rate=100.0, reason="manual")
    # a collapsed rate after an OPERATOR's switch is the operator's
    # call — the guard must not fight it
    for t in range(1, 5):
        got = c.decide(_sig(float(t), 50.0, tps=1.0))
        assert got is None or got[1] != "rollback"


def test_pool_pressure_escalates_int8_to_int4():
    """A saturated int8 page pool (window-mean occupancy >= the 0.95
    trigger) overrides the fitted table and proposes the SAME point at
    int4 — doubling page capacity in place — through the normal
    hysteresis; healthy occupancy proposes nothing, and a one-window
    spike does not move the mean past the trigger."""
    I8 = EngineConfig(slots=8, kv_pages=64, kv_dtype="int8")
    policy = PolicyTable(regimes=[
        {"max_offered_rps": None, "config": I8}]).validate()
    clock = [0.0]
    c = AutotuneController(
        policy, I8,
        config=ControllerConfig(interval_s=1.0, window=2, hold=2,
                                cooldown_s=0.0, rollback_window=2,
                                rollback_frac=0.0),
        now_fn=lambda: clock[0])

    def sig(t, frac):
        return AutotuneSignals(t=t, offered_rps=1.0, service_tps=100.0,
                               pages_in_use_frac=frac)

    # healthy pool: the table names the current config, nothing moves
    for t in range(3):
        assert c.decide(sig(float(t), 0.5)) is None
    # one saturated window: the window-2 mean stays below the trigger
    assert c.decide(sig(3.0, 1.0)) is None
    assert c.decide(sig(4.0, 0.2)) is None
    # sustained saturation: escalation target survives the hold streak
    assert c.decide(sig(5.0, 0.99)) is None
    assert c.decide(sig(6.0, 0.99)) is None        # mean crossed: streak 1
    got = c.decide(sig(7.0, 0.99))                 # streak 2 == hold
    assert got is not None
    target, reason = got
    assert reason == "auto"
    assert target.kv_dtype == "int4"
    assert target.slots == 8 and target.kv_pages == 64
    # the proposed narrowing is LEGAL for the engine to apply...
    assert switch_guard(I8, target) is None
    # ...and terminal: at int4 the pressure override no longer applies
    # (no narrower pool exists; the table's int8 point is a WIDENING
    # the engine-side switch_guard refuses and pins)
    c.on_switched(target, I8, pre_rate=100.0, reason="auto")
    assert c.decide(sig(8.0, 0.99)) is None        # guard verdict window
    assert c.decide(sig(9.0, 0.99)) is None


def test_config_info_gauge_tracks_the_live_config():
    from cake_tpu.autotune import CONFIG_INFO, set_config_info
    set_config_info(LO)
    live = {k: v for (k,), v in CONFIG_INFO.samples().items()
            if v == 1.0}
    assert "slots=8" in live
    set_config_info(HI)
    now = CONFIG_INFO.samples()
    assert now[("slots=32",)] == 1.0
    assert now[("slots=8",)] == 0.0     # superseded pair dropped to 0
