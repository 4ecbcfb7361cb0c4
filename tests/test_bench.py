"""Bench plumbing smoke tests (CPU-runnable tiers).

The real tiers need a TPU, and with none the tier chain refuses to run;
these name the `*_tiny` tiers one by one under JAX_PLATFORMS=cpu to
validate the subprocess orchestration, tier-mode entry, direct-int8 init,
and the JSON contract ({"metric", "value", "unit", "vs_baseline"}).
"""

import json
import os
import subprocess
import sys

import pytest

BENCH = os.path.join(os.path.dirname(__file__), os.pardir, "bench.py")


def _base_env(**extra):
    return {**os.environ, "JAX_PLATFORMS": "cpu", **extra}


def _run_tier(name: str) -> dict:
    proc = subprocess.run(
        [sys.executable, BENCH], env=_base_env(CAKE_BENCH_TIER=name),
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = next(ln for ln in proc.stdout.splitlines() if ln.startswith("{"))
    return json.loads(line)


@pytest.mark.parametrize("tier", [
    "tiny",
    pytest.param("tiny_int8", marks=pytest.mark.slow),
    pytest.param("tiny_int4", marks=pytest.mark.slow),
])
def test_smoke_tier_json_contract(tier):
    result = _run_tier(tier)
    for key in ("metric", "value", "unit", "vs_baseline"):
        assert key in result
    assert result["value"] > 0
    assert result["unit"] == "tokens/s"
    assert tier in result["metric"]
    # a CPU has no peak in the table (obs/steps.py): no roofline, no
    # utilization — the keys are absent, never defaulted
    assert result["device_kind"] == "cpu"
    assert result["vs_baseline"] is None
    assert not {"mfu", "hbm_util", "roofline_frac"} & set(result)


@pytest.mark.slow  # heaviest cases -> slow lane (tier-1 wall budget)
def test_sd_smoke_tier_reports_step_latency():
    result = _run_tier("sd_tiny")
    assert result["value"] > 0
    assert result["unit"] == "ms/step"
    assert result["sd_step_ms"] > 0
    assert result["sd_image_s"] > 0


def test_engine_smoke_tier_reports_ttft():
    result = _run_tier("engine_tiny")
    assert result["value"] > 0
    assert result["ttft_p50_ms"] > 0
    assert result["engine_decode_tok_s"] > 0
    assert result["engine_streams"] == 2
    # no peak for a CPU: the flight recorder reports no utilization
    assert "mfu" not in result and "hbm_util" not in result


@pytest.mark.slow  # heaviest cases -> slow lane (tier-1 wall budget)
def test_engine_spec_smoke_tier_reports_acceptance():
    """Speculation merged into the engine tier: the tier runs the engine
    in per-slot draft/verify mode and reports acceptance. The smoke
    draft IS the target (same init seed path? no — same 'tiny' config,
    same seed 1 vs 0), so acceptance is just bounded-sane here."""
    result = _run_tier("engine_spec_tiny")
    assert result["value"] > 0
    assert result["ttft_p50_ms"] > 0
    assert 0.0 <= result["spec_acceptance"] <= 1.0
    assert result["spec_gamma"] == 3


def test_probe_reports_device():
    proc = subprocess.run(
        [sys.executable, BENCH], env=_base_env(CAKE_BENCH_PROBE="1"),
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = next(ln for ln in proc.stdout.splitlines() if ln.startswith("{"))
    assert json.loads(line)["platform"] == "cpu"


def test_no_tpu_chain_exits_nonzero_with_no_result():
    """The measurement path FAILS with no chip: on a CPU backend the
    tier chain (every --<mode> entry goes through the same
    _require_tpu) exits non-zero after the probe and prints no result
    line — a CPU number never appears under a device metric's name."""
    proc = subprocess.run(
        [sys.executable, BENCH], env=_base_env(),
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 1, proc.stderr[-2000:]
    assert proc.stdout.strip() == ""
    assert "no TPU backend" in proc.stderr


@pytest.mark.slow  # bench subprocess + engine compile -> slow lane
@pytest.mark.parametrize("impl", ["fold", "pallas"])
def test_paged_attn_microbench_tier(impl):
    # the paged-decode microbench's tiny tier, by name (the
    # `--paged-attn` entry itself needs a TPU)
    result = _run_tier(f"paged_tiny_{impl}")
    assert result["paged_attn"] == impl
    assert result["value"] > 0
    assert result["unit"] == "tokens/s"
    assert result["kv_pages"] > 0


@pytest.mark.slow  # two engine phases + registration compile -> slow lane
def test_paged_prefix_smoke_tier_reports_sharing():
    """The paged prefix-sharing tier must emit pages_shared > 0 plus
    both phases' TTFTs — a tier where sharing silently stopped engaging
    (0 hits) fails here instead of benching the unshared path twice."""
    result = _run_tier("paged_prefix_tiny")
    assert result["value"] > 0
    assert result["unit"] == "ms"
    assert result["pages_shared"] > 0
    assert result["prefix_hits"] > 0
    assert result["ttft_p50_shared_ms"] > 0
    assert result["ttft_p50_unshared_ms"] > 0
    assert result["prefill_suffix_tok_s"] > 0


@pytest.mark.slow  # two engine phases under load -> slow lane
def test_mixed_smoke_tier_reports_both_row_kinds():
    """The --mixed tier's acceptance contract: the mixed-batching ON
    phase recorded at least one `mixed` step carrying BOTH row kinds
    (decode rows AND prefill-chunk rows in one launch — the
    no-decode-pause observable), and both phases report tok/s, step
    MFU, and arrival TTFT percentiles. A run where admissions never
    actually interleaved with decode benches the phase loop twice and
    fails here."""
    result = _run_tier("mixed_tiny")
    assert result["unit"] == "ms" and result["value"] > 0
    assert result["mixed_steps_both_kinds"] > 0
    assert result["mixed_tok_s_on"] > 0
    assert result["mixed_tok_s_off"] > 0
    # step MFU needs a peak; a CPU has none
    assert result["mixed_step_mfu_on"] is None
    assert result["mixed_step_mfu_off"] is None
    for tag in ("on", "off"):
        assert result[f"mixed_ttft_p50_{tag}_ms"] > 0
        assert result[f"mixed_ttft_p99_{tag}_ms"] > 0


@pytest.mark.slow  # two engine phases under load -> slow lane
def test_slo_smoke_tier_reports_preemption_win():
    """The --slo tier's acceptance contract: preemption actually
    engaged (preemptions_total > 0) and interactive-class p99 TTFT
    with preemption sits STRICTLY below the preemption-off phase under
    the same offered load — the number the sched/ subsystem exists
    for. A run where preemption silently stopped firing benches FIFO
    twice and fails here."""
    proc = subprocess.run(
        [sys.executable, BENCH], env=_base_env(CAKE_BENCH_TIER="slo_tiny"),
        capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = next(ln for ln in proc.stdout.splitlines()
                if ln.startswith("{"))
    result = json.loads(line)
    assert result["unit"] == "ms" and result["value"] > 0
    assert result["preemptions_total"] > 0
    assert result["preemptions_total_off"] == 0
    assert (result["interactive_ttft_p99_on_ms"]
            < result["interactive_ttft_p99_off_ms"])
    # every class reported both phases' percentiles
    for cls in ("interactive", "standard", "batch"):
        for tag in ("on", "off"):
            assert result[f"{cls}_ttft_p50_{tag}_ms"] > 0
            assert result[f"{cls}_ttft_p99_{tag}_ms"] > 0
    # goodput accounting (obs/slo.py): tokens from SLO-met requests
    # only, so goodput <= raw by construction; attainment in [0, 1]
    for tag in ("on", "off"):
        assert result[f"tok_s_{tag}"] > 0
        assert 0.0 <= result[f"goodput_tok_s_{tag}"] \
            <= result[f"tok_s_{tag}"]
        att = result[f"attainment_{tag}"]
        assert att and all(0.0 <= v <= 1.0 for v in att.values())


def test_paged_attn_microbench_rejects_bad_impl():
    proc = subprocess.run(
        [sys.executable, BENCH, "--paged-attn", "nope"], env=_base_env(),
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 2
    line = next(ln for ln in proc.stdout.splitlines() if ln.startswith("{"))
    assert "fold or pallas" in json.loads(line)["error"]


@pytest.mark.slow  # heaviest cases -> slow lane (tier-1 wall budget)
def test_spec_smoke_tier_reports_acceptance():
    result = _run_tier("spec_tiny")
    assert result["value"] > 0
    assert result["spec_baseline_tok_s"] > 0
    assert 0.0 <= result["spec_accept_rate"] <= 1.0
    assert result["spec_gamma"] == 4


def test_spec_paged_smoke_tier_identical_and_conserved():
    """FAST-LANE (ISSUE 20): the --spec-paged smoke pins the paged
    speculative mechanics — greedy spec-paged serving token-identical
    to plain greedy paged decode, self-draft acceptance > 0, more than
    one emitted token per round, and a fully conserved page pool after
    the wave (zero leaked draft/suffix pages)."""
    result = _run_tier("spec_paged_tiny")
    assert result["unit"] == "tokens/round"
    assert result["value"] > 1
    assert result["spec_acceptance"] > 0
    assert result["spec_rounds"] > 0
    assert result["spec_gamma"] == 3
    assert result["identical_to_plain"] is True
    assert result["pool_conserved"] is True


@pytest.mark.slow  # three engine phases under load -> slow lane
def test_kv_tier_smoke_reports_capacity_win():
    """The --kv-tier acceptance contract: at the SAME pool byte
    budget, each KV narrowing step admits >= 1.8x the resident decode
    streams of the tier above it (int8 vs f32, int4 vs int8), and each
    phase's host tier actually engaged — the cold shared prefix
    SPILLED under admission pressure and RESTORED for the
    prefix-matching tail request. A run where a quantized pool
    silently fell back to wider sizing (equal pages) or the tier never
    moved a page benches nothing and fails here."""
    result = _run_tier("kvtier_tiny")
    assert result["unit"] == "x" and result["value"] >= 1.8
    assert result["kv_streams_int8"] > result["kv_streams_f32"]
    assert result["kv_streams_int8"] >= 1.8 * result["kv_streams_f32"]
    # int4 repeats the win over int8, and transitively dominates f32
    assert result["kv_streams_int4"] >= 1.8 * result["kv_streams_int8"]
    assert result["kv_streams_int4"] > result["kv_streams_f32"]
    assert result["kv_streams_ratio_int4"] > result["value"]
    # the byte budget really bought more pages, not more bytes
    assert result["kv_pages_int8"] > result["kv_pages_f32"]
    assert result["kv_pages_int4"] > result["kv_pages_int8"]
    for tag in ("int4", "int8", "f32"):
        assert (result[f"kv_pool_bytes_{tag}"]
                <= result["kv_pool_budget_bytes"])
        assert result[f"kv_tok_s_{tag}"] > 0
        assert result[f"kv_spills_{tag}"] > 0
        assert result[f"kv_restores_{tag}"] > 0


@pytest.mark.slow  # five engine builds over loopback -> slow lane
def test_disagg_smoke_tier_ships_pages_and_stays_identical():
    """The --disagg acceptance contract: pages actually crossed the
    wire in both split phases (a run where every request silently
    degraded to local prefill benches nothing), the f32 split streams
    came back token-identical to colocated (the handoff contract), and
    an int8 shipment moved well under 0.3x the f32 bytes for the same
    prefix (int8 pages are 1/4 the value bytes + two small f32 scale
    sidecars — the serving-economics reason to quantize the transfer
    unit)."""
    result = _run_tier("disagg_tiny")
    assert result["unit"] == "x" and 0 < result["value"] < 0.3
    assert result["disagg_token_identical_f32"] is True
    for tag in ("f32", "int8"):
        assert result[f"disagg_pages_shipped_{tag}"] > 0
        assert result[f"disagg_shipments_{tag}"] > 0
        assert result[f"disagg_adopted_{tag}"] > 0
        assert result[f"disagg_degraded_{tag}"] == 0
        assert result[f"disagg_tok_s_{tag}"] > 0
        assert result[f"disagg_ttft_p99_ms_{tag}"] > 0
    assert (result["disagg_ship_bytes_int8"]
            < 0.3 * result["disagg_ship_bytes_f32"])
    assert result["disagg_tok_s_colocated_f32"] > 0


@pytest.mark.slow  # two engine phases + a live hot switch -> slow lane
def test_autotune_smoke_tier_switches_without_losing_streams():
    """The --autotune tier's acceptance contract: the mid-run offered-
    load shift triggered >= 1 AUTONOMOUS switch (the policy controller
    moved the engine from slots_lo to slots_hi), no stream was lost
    across it, and at f32 KV the autotuned run's greedy streams came
    back token-identical to the pinned run. A run where the controller
    silently stopped proposing (or the switch dropped a stream)
    benches the pinned config twice and fails here."""
    result = _run_tier("autotune_tiny")
    assert result["unit"] == "switches" and result["value"] >= 1
    assert result["autotune_switches"] >= 1
    assert result["autotune_streams_lost"] == 0
    assert result["autotune_final_slots"] == 4   # lo (2) -> hi (4)
    # f32 KV: the hot switch is token-identical, not approximately-resumed
    assert result["autotune_tokens_match"] is True
    # per-phase numbers for both runs, and fitter-ingestible records
    for tag in ("pinned", "auto"):
        for ph in ("low", "high"):
            assert result[f"{ph}_tok_s_{tag}"] > 0
            assert result[f"{ph}_ttft_p99_{tag}_ms"] > 0
            # goodput <= raw, attainment in [0, 1] (obs/slo.py)
            assert 0.0 <= result[f"{ph}_goodput_tok_s_{tag}"] \
                <= result[f"{ph}_tok_s_{tag}"]
            att = result[f"{ph}_attainment_{tag}"]
            assert att and all(0.0 <= v <= 1.0 for v in att.values())
    assert all("config" in o and o["tok_s"] > 0
               for o in result["autotune_observations"])


@pytest.mark.slow  # subprocess tier -> slow lane (tier-1 wall budget)
def test_fleet_smoke_tier_ships_batches_with_finite_lag():
    """The --fleet tier's acceptance contract: the federation plane
    works end to end over real localhost sockets — export batches > 0
    all ingested, collector ingest lag finite (p99 >= p50 >= 0), the
    control exchange carries a measurable per-op wire cost, and the
    drained follower reports applied-seq lag 0."""
    result = _run_tier("fleet_tiny")
    assert result["unit"] == "frames" and result["value"] > 0
    assert result["fleet_export_batches"] > 0
    assert result["fleet_ingest_frames"] == result[
        "fleet_export_batches"]
    assert result["fleet_events_shipped"] > 0
    import math
    for key in ("fleet_ingest_lag_p50_ms", "fleet_ingest_lag_p99_ms"):
        assert math.isfinite(result[key]) and result[key] >= 0
    assert result["fleet_ingest_lag_p99_ms"] \
        >= result["fleet_ingest_lag_p50_ms"]
    assert result["fleet_control_bytes_per_op"] > 0
    assert result["fleet_publish_us_per_op"] > 0
    assert result["fleet_lag_ops"] == 0
    assert result["fleet_host_live"] is True


@pytest.mark.slow  # oracle + killed child + replay engine -> slow lane
def test_restart_smoke_tier_loses_nothing_and_matches_tokens():
    """The --restart tier's acceptance contract: the journaled child
    died by the PLANNED abort (a staged kill -9, not an organic
    crash), the replay resubmitted every interrupted stream, ZERO
    requests were lost, at f32 KV the recovered greedy streams came
    back token-identical to the uninterrupted oracle, and the tier
    measured a real RTO."""
    result = _run_tier("restart_tiny")
    assert result["unit"] == "s" and result["value"] > 0
    assert result["restart_journal_records"] > 0
    assert result["restart_replayed"] > 0
    assert result["restart_lost"] == 0
    assert result["restart_tokens_match"] is True
    assert result["restart_journal_findings"] == 0
    assert result["restart_replay_s"] is not None
    assert 0 < result["restart_replay_s"] <= result["value"]


@pytest.mark.slow  # two engine phases under injected chaos -> slow lane
def test_chaos_smoke_tier_recovers_without_losing_requests():
    """The --chaos tier's acceptance contract: the injected transient
    crashes cost ZERO requests — in-flight streams recover via the
    fold-tokens-into-prompt resubmit (recovered > 0), the ONLY failed
    request is the quarantined poison one (failed == quarantined), the
    clean phase failed nothing, and at f32 KV the chaos phase's greedy
    streams came back token-identical to the clean phase. A run where
    recovery silently stopped engaging (or started failing bystanders)
    benches the legacy fail-everything path and fails here."""
    result = _run_tier("chaos_tiny")
    assert result["unit"] == "requests" and result["value"] > 0
    assert result["chaos_injections"] > 0
    assert result["chaos_recoveries"] > 0
    assert result["chaos_recovered"] > 0
    # the poison request is the ONLY casualty
    assert result["chaos_quarantined"] == 1
    assert result["chaos_failed"] == result["chaos_quarantined"]
    assert result["chaos_clean_failed"] == 0
    # f32 KV: recovery is token-identical, not approximately-resumed
    assert result["chaos_tokens_match"] is True
    assert result["chaos_recovery_p50_ms"] > 0
    assert result["chaos_recovery_p99_ms"] >= result["chaos_recovery_p50_ms"]


@pytest.mark.slow  # two router phases x 2 engines each -> slow lane
def test_router_smoke_tier_affinity_beats_round_robin():
    """The --router tier's acceptance contract: under the SAME
    shared-prefix load over 2 replicas behind the real front door, the
    prefix-affinity policy's fleet hit rate strictly beats the
    round-robin strawman's (affinity registers each tenant's prefix
    once fleet-wide; round-robin re-registers it per replica) and its
    aggregate goodput is no worse. Zero failovers on a healthy fleet.
    A run where affinity silently stopped engaging (text-fallback
    drift, ring regression) degenerates to round-robin and fails
    here."""
    result = _run_tier("router_tiny")
    assert result["unit"] == "tokens/s" and result["value"] > 0
    assert result["router_replicas"] == 2
    assert (result["router_hit_rate_affinity"]
            > result["router_hit_rate_round_robin"])
    # the DETERMINISTIC work delta behind the goodput win: round-robin
    # force-registers every tenant's prefix on every replica it visits
    assert (result["router_new_regs_affinity"]
            < result["router_new_regs_round_robin"])
    # goodput ≥ modulo wall-clock scheduling noise on a shared CPU box
    # (the work delta above is strict; a co-loaded box must not flake
    # a deterministic win)
    assert (result["router_goodput_tok_s_affinity"]
            >= 0.9 * result["router_goodput_tok_s_round_robin"])
    assert result["router_failovers"] == 0
    assert result["router_ttft_p50_ms_affinity"] > 0
    assert result["router_ttft_p99_ms_round_robin"] > 0
    # every request completed, split across BOTH replicas under
    # round-robin (the strawman really did alternate)
    assert sum(result["router_per_replica_round_robin"]) \
        == result["router_requests"]
    assert all(n > 0 for n in result["router_per_replica_round_robin"])
    # the discovery/placement smoke (announce-only fleet): the
    # hot-joined replica — in NO --replicas list — served real routed
    # traffic, the flagged hot-switch admitted ZERO new work onto the
    # switching box and restored it afterwards, and the explicit
    # departure notice admitted ZERO new work before the forget
    assert result["router_disc_joiner_completed"] > 0
    assert result["router_disc_join_to_first_serve_ms"] > 0
    assert 0 < result["router_disc_placement_shift"] < 1
    assert result["router_disc_switch_admissions_routed_around"] == 0
    assert result["router_disc_switch_restored"] is True
    assert result["router_disc_post_departure_admissions"] == 0
    assert result["router_disc_forgotten_after_depart"] is True
