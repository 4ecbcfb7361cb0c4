"""CLI argument surface — capability parity with the reference's clap structs.

Reference: `Args` (cake-core/src/lib.rs:21-88), `SDArgs` (lib.rs:90-127),
`ImageGenerationArgs` (lib.rs:145-200), `ModelType` (lib.rs:14-19).

Defaults match the reference where sensible; the dtype default is **bfloat16**
instead of f16 (cake/mod.rs:54-60) because bf16 is the native TPU matmul type.
`ImageGenerationArgs` doubles as the REST image-request body, like the
reference's parallel clap/serde attributes (lib.rs:145-200, api/image.rs:15-18).
"""

from __future__ import annotations

import argparse
from dataclasses import dataclass, field, fields, asdict
from enum import Enum
from typing import Optional

# the quantized paged-pool storage names ("int8" = 1 byte/value,
# "int4" = two nibble-packed values/byte; cake_tpu/kv/quantized_pool)
QUANTIZED_KV_DTYPES = ("int8", "int4")


class ModelType(str, Enum):
    TEXT = "text"
    IMAGE = "image"


def parse_replicas(spec: str) -> list:
    """Validate + split a --replicas list: comma-separated host:port
    entries, no duplicates. One source of truth for Args.validate and
    the router builder (cli._serve_router)."""
    out = []
    for entry in spec.split(","):
        entry = entry.strip()
        if not entry:
            continue
        host, sep, port = entry.rpartition(":")
        if not sep or not host:
            raise ValueError(
                f"--replicas entry {entry!r} must be host:port")
        try:
            p = int(port)
        except ValueError:
            raise ValueError(
                f"--replicas entry {entry!r}: port {port!r} is not an "
                "integer")
        if not 0 < p < 65536:
            raise ValueError(
                f"--replicas entry {entry!r}: port {p} out of range")
        out.append(entry)
    if not out:
        raise ValueError(f"--replicas {spec!r} names no replicas")
    if len(set(out)) != len(out):
        raise ValueError(f"--replicas {spec!r} has duplicate entries")
    return out


class SDVersion(str, Enum):
    V1_5 = "v1-5"
    V2_1 = "v2-1"
    XL = "xl"
    TURBO = "turbo"


@dataclass
class Args:
    """Process-wide configuration (reference lib.rs:21-88)."""

    model: str = ""                     # path to model directory
    model_type: ModelType = ModelType.TEXT
    mode: str = "master"                # master | worker (compat; TPU runs SPMD)
    name: str = ""                      # node name within the topology
    address: str = "127.0.0.1:10128"    # serving bind address
    api: Optional[str] = None           # REST bind address; None = one-shot CLI
    topology: Optional[str] = None      # topology.yml path
    prompt: str = "Why is the sky blue?"
    system_prompt: str = "You are a helpful AI assistant."
    seed: int = 299792458               # reference default (lib.rs)
    sample_len: int = 100
    temperature: float = 1.0
    top_p: Optional[float] = None
    top_k: Optional[int] = None
    # None = "not set": resolves to the reference default 1.1
    # (llama.rs:311-320); an EXPLICIT value is honored everywhere
    repeat_penalty: Optional[float] = None
    repeat_last_n: int = 128
    dtype: str = "bf16"                 # f16 | bf16 | f32 (TPU default bf16)
    # KV-cache storage dtype; fp8 halves KV HBM traffic/footprint (values
    # upcast into the attention matmul on read). "int8"/"int4" select
    # the QUANTIZED paged pool (cake_tpu/kv): int8 or nibble-packed
    # int4 KV pages + per-page per-kv-head f32 scales, ~4x / ~8x the
    # resident decode streams per pool byte vs f32 — both require
    # --kv-pages (the page is the quantization unit; int4 additionally
    # needs an even --kv-page-size). None = same as dtype.
    kv_dtype: Optional[str] = None      # + f8_e4m3 | f8_e5m2 | int8 | int4
    max_seq_len: int = 4096             # reference hard constant (config.rs:6); tunable here
    batch_size: int = 1
    max_slots: int = 8                  # continuous-batching decode slots (API serving)
    # parallelism knobs (TPU additions; reference has PP only)
    tp: int = 1                         # tensor-parallel degree
    dp: int = 1                         # data-parallel degree
    sp: int = 1                         # sequence/context-parallel degree
    microbatches: int = 1               # GPipe microbatches per pipeline step
                                        # (1 = reference depth-1 behavior)
    # prefill prompts in fixed windows of N tokens (one compiled program
    # for every prompt length; cache-aware flash attention per chunk);
    # None = whole-prompt prefill with bucketed shapes. On the paged
    # (--kv-pages) engine it is the mixed step's window width (None =
    # the widest the mixed kernel admits): a prompt's windows scatter
    # into the slot's pages at any offset (models/llama/paged
    # .mixed_step_paged)
    prefill_chunk: Optional[int] = None
    # engine: when no request is queued, decode N tokens per host
    # round-trip as one on-device scan (amortizes dispatch latency);
    # 1 = step-by-step
    decode_scan: int = 1
    # Pallas flash attention for LLM prefill; None = auto (on when the
    # backend is a real TPU, off on CPU where interpret mode is slow)
    flash_attention: Optional[bool] = None
    # profile generation to this directory (jax.profiler; view in
    # TensorBoard or ui.perfetto.dev) — LLM-path analog of --sd-tracing
    tracing: Optional[str] = None
    # engine checkpoint file: restore in-flight requests on startup, save
    # on shutdown (serve/checkpoint.py; the reference has no runtime
    # checkpointing, SURVEY.md §5)
    checkpoint: Optional[str] = None
    # weight quantization (ops/quant.py): "int8" halves decode HBM traffic
    # (weight-only per-channel), "int4" quarters it (group-wise, dense
    # models only); "none" keeps args.dtype weights
    quant: str = "none"
    # speculative decoding (cake_tpu/spec): path to a small draft model
    # sharing the target's tokenizer, whose KV rides a second paged
    # pool behind the engine's one page allocator — speculation is a
    # row KIND of the paged engine (many streams speculate at once, a
    # row whose sampling has no accept/resample identity decodes
    # plain), and each round's one target pass verifies spec_gamma
    # drafted tokens a row. Requires --kv-pages + f32/bf16 KV.
    spec_draft: Optional[str] = None
    spec_gamma: int = 4
    # serving watchdog: fail (recoverably) when the engine makes no
    # progress for this many seconds with active requests; must exceed
    # the worst-case first-request compile time (parallel/health.py)
    stall_timeout: float = 600.0
    # multi-host serving: fail when a follower's heartbeat lapses this
    # many seconds (parallel/health.HeartbeatMonitor stale window) —
    # pre-fail snapshot + 503s instead of a wedged collective
    heartbeat_timeout: float = 15.0
    # --auto-prefix: the API engine KV-caches each distinct system
    # prompt's rendered head once (serve/engine.register_prefix), so
    # conversations sharing it prefill only their own turns. On the
    # paged (--kv-pages) engine the head is rounded down to a page
    # boundary and its pages are mapped READ-ONLY into every matching
    # slot's table row (page-granular prefix sharing: one copy in the
    # pool, refcounted, however many slots share it)
    auto_prefix: bool = False
    # --kv-pages N: paged KV for the serving engine — KV lives in a pool
    # of N pages of --kv-page-size tokens; slot admission is gated by
    # free pages, so resident KV is bounded by the pool instead of
    # max_slots x max_seq_len (models/llama/paged.py). Prompts and
    # decode rows share ONE mixed step (token-level continuous
    # batching: a new request's windows join the very next step).
    # Composes with --auto-prefix (shared prefix pages) and
    # --prefill-chunk (the mixed step's window width)
    kv_pages: Optional[int] = None
    kv_page_size: int = 128
    # --paged-attn: attention impl for the paged (--kv-pages) engine —
    # "pallas" = the ragged paged-attention TPU kernel
    # (ops/ragged_paged_attention.py), "fold" = the XLA online-softmax
    # fold over all pages (the reference semantics; use for debugging
    # or non-TPU backends); "auto" = pallas on TPU, fold elsewhere
    paged_attn: str = "auto"
    # --require-model-type NAME: refuse to start unless the model
    # directory's config.json resolves to this family (a config.json
    # `model_type`: a key of models/llama/config.MODEL_TYPES).
    # An assertion for scripted deployments, not a switch: nothing else
    # reads it
    require_model_type: Optional[str] = None
    # --kv-host-pages N: host-RAM spill tier for the paged pool
    # (cake_tpu/kv/host_tier.py) — preemption victims' pages and cold
    # shared-prefix pages spill to pinned host memory (LRU, capacity N
    # pages) and stream back on demand, so a resumed victim decodes
    # from where it stopped instead of re-prefilling and a cold prefix
    # re-maps instead of recomputing. Applies to --kv-pages serving
    # only (the page is the spill unit)
    kv_host_pages: Optional[int] = None
    # --trace-events PATH: append every request-lifecycle span as one
    # JSON line (obs/tracing.py) — the replayable audit log behind the
    # in-memory ring served at GET /api/v1/requests
    trace_events: Optional[str] = None
    # --trace-ring N: finished request traces retained in memory for
    # GET /api/v1/requests
    trace_ring: int = 256
    # --step-log PATH: append one JSON line per engine step (the
    # obs/steps.py flight recorder: kind, occupancy, tokens, dispatch
    # wall, MFU/HBM utilization, page-pool state) — the step-level
    # audit log behind GET /api/v1/steps
    step_log: Optional[str] = None
    # --step-ring N: step flight-recorder records retained in memory
    # for GET /api/v1/steps
    step_ring: int = 512
    # --event-log PATH: append every cross-subsystem serving event
    # (preempted, kv_spill/kv_restore, prefix_hit, recovered/poisoned,
    # reconfigured, shed, fault_injected, recompile — obs/events.py)
    # as one JSON line; the lossless sink behind the bounded ring at
    # GET /api/v1/events
    event_log: Optional[str] = None
    # --event-ring N: events retained in memory for GET /api/v1/events;
    # 0 disables the event bus entirely (every publish site is then one
    # attribute test, the --fault-plan discipline)
    event_ring: int = 1024
    # --slo-targets SPEC: per-class latency SLOs for attainment +
    # goodput accounting (obs/slo.py) —
    # "interactive=ttft:0.1,e2e:2;standard=ttft:1,e2e:30;..." names a
    # class's TTFT / e2e targets in seconds; unnamed classes keep the
    # defaults. Drives cake_slo_attainment{class,window},
    # cake_slo_*_total burn-rate counters and
    # cake_goodput_tokens_total{class}, and the autotuner's
    # quality-aware policy lookup
    slo_targets: Optional[str] = None
    # --profile-dir DIR: where POST /api/v1/profile writes its
    # jax.profiler capture; None = a fresh temp dir per capture
    profile_dir: Optional[str] = None
    # --priority-classes: SLO-aware scheduling (cake_tpu/sched/) for
    # the serving engine — requests carry a class (interactive |
    # standard | batch, via the request-body "priority" field or the
    # x-cake-priority header) and plan() admits by class with
    # anti-starvation aging instead of FIFO arrival order
    priority_classes: bool = False
    # --preemption / --no-preemption: recompute-style preemption
    # (requires --priority-classes): when a higher class is slot- or
    # page-starved, the youngest lowest-class decoding slot is
    # preempted — its generated tokens fold into its prompt (the
    # checkpoint-resume fold), its kv pages release through the
    # refcounted allocator, and it requeues to re-prefill later, with a
    # per-request preemption budget guaranteeing progress. None = auto
    # (on whenever --priority-classes is on and the engine flavor
    # supports the fold)
    preemption: Optional[bool] = None
    # --shed: per-class load shedding — admission probability derived
    # from the measured service rate and queue depth; rejected requests
    # surface as HTTP 429 with an honest computed Retry-After
    # (cake_tpu/sched/shed.py)
    shed: bool = False
    # --fault-plan SPEC: deterministic fault injection (cake_tpu/faults)
    # — "seed=N;site:trigger:error[:opts];..." names where/when/what
    # the serving stack should fail (e.g.
    # "seed=7;engine.decode:nth=12:transient"), so every chaos
    # experiment is reproducible from its command line. Sites cover
    # engine step dispatch, the control channel, the host KV tier and
    # the page allocator; unset = the plane is a no-op.
    fault_plan: Optional[str] = None
    # --recovery / --no-recovery: crash recovery for the serving
    # engine — on a step failure, reset and RESUBMIT in-flight
    # requests via the checkpoint fold-tokens-into-prompt path
    # instead of failing them all; repeatedly-implicated requests are
    # quarantined as poison, and a reset storm trips a breaker
    # (snapshot + clean stop). None = auto: on wherever the fold works
    # (off for windowed serving)
    recovery: Optional[bool] = None
    # --autotune {off,manual,auto}: live engine-config hot-switching
    # (cake_tpu/autotune). "manual" arms POST /api/v1/autotune (an
    # operator switches slots/decode-scan/kv-pages/kv-dtype/
    # paged-attn under load: in-flight streams fold their
    # generated tokens into their prompts — the checkpoint-resume fold
    # — and requeue with seniority/class preserved, token-identical at
    # f32 KV); "auto" additionally runs the policy controller: an
    # offered-load regime -> config table (--autotune-policy, fitted
    # offline by tools/autotune_fit.py) consulted over sliding-window
    # signals with hysteresis, cooldown and a one-shot rollback guard
    autotune: str = "off"
    # --autotune-policy PATH: the piecewise policy table for --autotune
    # auto (JSON: {"version": 1, "regimes": [{"max_offered_rps": ...,
    # "config": {...}}, ...]}; cake_tpu/autotune/search.py)
    autotune_policy: Optional[str] = None
    # --journal PATH: write-ahead request journal (serve/journal.py) —
    # one record per admission, one per emitted-token batch, retire
    # tombstones. On startup the journal (plus the --checkpoint base
    # when both are set) replays every non-retired request through the
    # fold-tokens-into-prompt path, so a hard process death (SIGKILL,
    # OOM-kill, power) between snapshots loses no stream; greedy
    # continuations are token-identical at f32 KV. Composes with
    # idempotency keys + SSE Last-Event-ID resume so clients re-attach
    # across the restart.
    journal: Optional[str] = None
    # --journal-fsync {never,batch,always}: journal durability —
    # "never" flushes per line (process death loses nothing, machine
    # death may lose recent records), "batch" (default) fsyncs once
    # per engine iteration, "always" fsyncs every append
    journal_fsync: str = "batch"
    # --telemetry-export / --no-telemetry-export: fleet telemetry
    # federation (obs/federation.py) — every non-coordinator process
    # ships its metrics / event-bus events / step summaries / applied
    # control-op seq to a coordinator-side collector, powering
    # GET /api/v1/fleet, ?host= event filters, host-labeled federated
    # /metrics families and cross-host request timelines. None = auto
    # (on for multi-host serving, where followers would otherwise be
    # observability black holes); True on a single host is a one-shot
    # warning (there are no followers to federate)
    telemetry_export: Optional[bool] = None
    # --telemetry-interval S: exporter frame cadence in seconds (each
    # frame batches everything new since the last one; the event
    # cursor advances only on a successful send, so a collector blip
    # delays events rather than dropping them)
    telemetry_interval: float = 2.0
    # --router: run THIS process as the front-door router
    # (cake_tpu/router) over N independent engine replicas instead of
    # loading a model — prefix-affinity consistent-hash routing, lite
    # health polling with staleness ejection, drain-aware failover,
    # verbatim Retry-After propagation. Binds --api (or --address).
    # With --model pointing at a directory holding tokenizer.json the
    # affinity keys are page-aligned token fingerprints (the
    # register_prefix rounding rule); without one they degrade to
    # system-prompt text fingerprints.
    router: bool = False
    # --replicas host:port,host:port,...: the engine replicas the
    # router fronts (each an independent `--api` serving process).
    # With --router-announce this becomes an OPTIONAL static seed —
    # announced replicas join the same fleet.
    replicas: Optional[str] = None
    # --router-announce host:port — dual-role flag for fleet discovery
    # (cake_tpu/router/discovery.py):
    #   * on the --router role: BIND the token-gated announce listener
    #     there (port 0 = ephemeral); replicas self-register, pushed
    #     frames supersede polling while fresh, departures
    #     drain-then-forget, pushed headroom/attainment feed placement
    #   * on a replica (--api) role: ANNOUNCE to the router's listener
    #     at that address (lite-health-superset frames + an explicit
    #     departure notice at shutdown)
    # The shared token comes from $CAKE_ANNOUNCE_TOKEN on both sides.
    router_announce: Optional[str] = None
    # --announce-interval S: replica announce-frame cadence; also the
    # router side's warm-up Retry-After bound and (x3) its
    # fallback-to-poll staleness window
    announce_interval: float = 2.0
    # --router-watermark N: bounded-load spill threshold — the
    # affinity target takes the request only under this queue+active
    # load; over it, the request spills to the next ring node
    router_watermark: int = 8
    # --router-poll S: lite-health poll cadence per replica
    # (GET /api/v1/health?lite=1)
    router_poll: float = 0.25
    # --router-policy {affinity,round_robin}: round_robin is the
    # bench strawman (no prefix affinity; per-request rotation)
    router_policy: str = "affinity"
    # --sentinel: arm the online performance-regression sentinel
    # (obs/sentinel.py) — rolling-window anomaly detectors over the
    # LIVE signal stream (per-kind step-time p95 vs a self-calibrated
    # baseline, jit-recompile rate, kv spill rate, shed rate,
    # per-class SLO attainment; on the --router role: per-replica
    # TTFT skew, affinity collapse, router shed storms), emitting
    # typed `anomaly` events, cake_anomaly_total{kind} /
    # cake_anomaly_active{kind} metrics and GET /api/v1/anomalies.
    # Fed entirely from existing seams — zero hot-path work.
    sentinel: bool = False
    # --sentinel-interval S: detector tick cadence in seconds (each
    # tick reads one rolling window per detector)
    sentinel_interval: float = 2.0
    # --sentinel-act: CLOSE the loop on the engine replica
    # (obs/actions.py): recompile-storm / step-time anomalies become
    # first-class autotune signals — hold new policy switches while
    # active, pin the post-switch rollback verdict from anomaly
    # evidence — every action typed on the bus, rate-bounded, counted
    # in cake_anomaly_actions_total and listed by GET
    # /api/v1/anomalies. Off = PR 15 report-only, byte-identical.
    sentinel_act: bool = False
    # --router-anomaly-weighting: the router-role closed loop — TTFT
    # skew / shed storm / affinity collapse de-weight the offending
    # replica's placement (never ejecting it), re-weighting on
    # recovery with a per-replica cooldown
    router_anomaly_weighting: bool = False
    # --postmortem-dir DIR: black-box forensics — breaker stops,
    # poison quarantines, failed recoveries and SIGTERM each dump one
    # JSON bundle (step records, event ring, traces, anomaly + action
    # history, metrics snapshot, journal tail) here;
    # tools/postmortem.py renders a bundle into a wall-clock narrative
    postmortem_dir: Optional[str] = None
    # --disagg {prefill,decode}: disaggregated prefill/decode serving
    # (cake_tpu/kv/transfer.py) — this engine takes ONE phase of the
    # pair. "decode" is the front door: it forwards each admission's
    # prompt to the prefill peer, installs the shipped KV pages via
    # the refcounted allocator and serves SSE from the first decoded
    # token; "prefill" admits forwarded prompts, runs chunked prefill
    # into pool pages and ships the pages + a handoff record. Requires
    # --kv-pages (pages are the transfer unit) and the shared channel
    # token in $CAKE_DISAGG_TOKEN on both engines. Any channel failure
    # degrades the decode host to whole-prompt local prefill — never a
    # wedged stream.
    disagg: Optional[str] = None
    # --disagg-peer host:port: the transfer channel address — the
    # PREFILL engine binds it (port 0 = ephemeral), the DECODE engine
    # connects to it (retrying with backoff, so start order is free)
    disagg_peer: Optional[str] = None
    # --disagg-timeout S: decode-host wait per forwarded prefill
    # before degrading that request to local prefill
    disagg_timeout: float = 30.0

    def validate(self) -> "Args":
        if self.dtype not in ("f16", "bf16", "f32"):
            raise ValueError(f"unsupported dtype '{self.dtype}'")
        if self.quant not in ("none", "int8", "int4"):
            raise ValueError(f"unsupported quant '{self.quant}'")
        if self.paged_attn not in ("auto", "fold", "pallas"):
            raise ValueError(
                f"unsupported paged_attn '{self.paged_attn}' "
                "(choose auto, fold or pallas)")
        if self.kv_dtype in QUANTIZED_KV_DTYPES:
            # quantized KV is page-granular (per-page scales live in
            # the paged pool); without --kv-pages there is nothing to
            # quantize — loud error, not a silent no-op
            if not self.kv_pages:
                raise ValueError(
                    f"--kv-dtype {self.kv_dtype} requires --kv-pages: "
                    "quantized KV pages live in the paged pool "
                    "(cake_tpu/kv)")
            if self.kv_dtype == "int4" and self.kv_page_size % 2:
                # two int4 values nibble-pack into one byte along the
                # page's token axis, so a page must split evenly
                raise ValueError(
                    f"--kv-dtype int4 requires an even --kv-page-size "
                    f"(got {self.kv_page_size}): pages nibble-pack "
                    "token pairs (cake_tpu/kv/quantized_pool)")
        elif self.kv_dtype is not None:
            # single source of truth for storage dtypes
            from cake_tpu.utils.devices import resolve_kv_dtype
            resolve_kv_dtype(self.kv_dtype)
        if self.spec_draft is not None:
            # speculative decoding (cake_tpu/spec): loud startup
            # errors mirroring the engine's constructor checks, so a
            # bad flag combination fails before the model loads
            if not self.kv_pages:
                raise ValueError(
                    "--spec-draft requires --kv-pages: a speculating "
                    "row's draft and target KV share the paged pool's "
                    "page allocator")
            if self.kv_dtype in ("int8", "int4"):
                raise ValueError(
                    f"--spec-draft requires f32/bf16 KV pages, got "
                    f"--kv-dtype {self.kv_dtype}: the draft pool has "
                    "no quantized flavor yet (ROADMAP item 3)")
            if self.spec_gamma < 1:
                raise ValueError(
                    f"--spec-gamma {self.spec_gamma} must be >= 1")
            if self.disagg is not None:
                raise ValueError(
                    "--spec-draft is not supported with --disagg yet: "
                    "a shipped prefill carries no draft-pool KV (the "
                    "decode host would re-prefill every draft)")
        if self.kv_host_pages is not None and self.kv_host_pages < 1:
            raise ValueError(
                f"--kv-host-pages {self.kv_host_pages} must be >= 1")
        if self.autotune not in ("off", "manual", "auto"):
            raise ValueError(
                f"unsupported autotune '{self.autotune}' "
                "(choose off, manual or auto)")
        if self.autotune == "auto":
            if not self.autotune_policy:
                raise ValueError(
                    "--autotune auto requires --autotune-policy "
                    "(fit one with tools/autotune_fit.py)")
            # parse NOW so a malformed/missing policy is a loud startup
            # error, not a crash after the model loaded (the
            # --fault-plan precedent)
            from cake_tpu.autotune import PolicyTable
            PolicyTable.load(self.autotune_policy)
        if self.fault_plan:
            # parse NOW so a malformed plan is a loud startup error,
            # not a crash after the model loaded (a chaos run that
            # silently injects nothing is worse than no chaos run)
            from cake_tpu.faults import FaultPlan
            FaultPlan.parse(self.fault_plan)
        if self.journal_fsync not in ("never", "batch", "always"):
            raise ValueError(
                f"unsupported journal_fsync '{self.journal_fsync}' "
                "(choose never, batch or always)")
        if self.slo_targets:
            # same discipline as --fault-plan: a malformed SLO spec is
            # a loud startup error, not a serving run silently
            # accounting against the defaults
            from cake_tpu.obs.slo import parse_slo_targets
            parse_slo_targets(self.slo_targets)
        if self.event_ring < 0:
            raise ValueError(
                f"--event-ring {self.event_ring} must be >= 0 "
                "(0 disables the event bus)")
        if not self.telemetry_interval > 0:
            raise ValueError(
                f"--telemetry-interval {self.telemetry_interval} must "
                "be > 0 seconds")
        if self.router_policy not in ("affinity", "round_robin"):
            raise ValueError(
                f"unsupported router_policy '{self.router_policy}' "
                "(choose affinity or round_robin)")
        if self.router_watermark < 1:
            raise ValueError(
                f"--router-watermark {self.router_watermark} must be "
                ">= 1")
        if not self.router_poll > 0:
            raise ValueError(
                f"--router-poll {self.router_poll} must be > 0 "
                "seconds")
        if not self.sentinel_interval > 0:
            raise ValueError(
                f"--sentinel-interval {self.sentinel_interval} must "
                "be > 0 seconds")
        if self.sentinel_act and not self.sentinel:
            raise ValueError(
                "--sentinel-act requires --sentinel (nothing to act "
                "on without the anomaly sentinel)")
        if self.router_anomaly_weighting and not self.sentinel:
            raise ValueError(
                "--router-anomaly-weighting requires --sentinel (the "
                "router-side detectors drive the de-weighting)")
        if not self.announce_interval > 0:
            raise ValueError(
                f"--announce-interval {self.announce_interval} must "
                "be > 0 seconds")
        if self.router_announce is not None:
            # same shape discipline as a --replicas entry: the value
            # must be a bindable/dialable host:port
            host, sep, port = self.router_announce.rpartition(":")
            if not sep or not host or not port.isdigit():
                raise ValueError(
                    f"--router-announce {self.router_announce!r} must "
                    "be host:port (port 0 binds an ephemeral announce "
                    "listener on the router role)")
            if not 0 <= int(port) <= 65535:
                raise ValueError(
                    f"--router-announce port {port} out of range "
                    "(0-65535)")
        if self.router:
            # parse NOW so a malformed replica list is a loud startup
            # error (the --fault-plan discipline). With discovery
            # armed the static seed may be empty; without it an empty
            # fleet could never serve — keep the loud error.
            if not self.replicas and self.router_announce is None:
                raise ValueError(
                    "--router requires --replicas host:port,... (the "
                    "engine replicas the front door routes over) or "
                    "--router-announce host:port (fleet discovery: "
                    "replicas self-register)")
            if self.replicas:
                parse_replicas(self.replicas)
        if self.disagg is not None:
            if self.disagg not in ("prefill", "decode"):
                raise ValueError(
                    f"unsupported disagg '{self.disagg}' (choose "
                    "prefill or decode)")
            if not self.kv_pages:
                raise ValueError(
                    "--disagg requires --kv-pages: KV pool pages are "
                    "the transfer unit (cake_tpu/kv/transfer.py)")
            if not self.disagg_peer:
                raise ValueError(
                    "--disagg requires --disagg-peer host:port (the "
                    "prefill engine binds it; the decode engine "
                    "connects to it)")
            host, sep, port = self.disagg_peer.rpartition(":")
            if not sep or not host or not port.isdigit() \
                    or not 0 <= int(port) <= 65535:
                raise ValueError(
                    f"--disagg-peer {self.disagg_peer!r} must be "
                    "host:port (port 0 binds ephemeral on the prefill "
                    "role)")
            import os as _os
            if not _os.environ.get("CAKE_DISAGG_TOKEN"):
                # loud NOW, not a dead channel after the model loaded
                # (the $CAKE_ANNOUNCE_TOKEN discipline)
                raise ValueError(
                    "--disagg needs the shared channel token in "
                    "$CAKE_DISAGG_TOKEN on both engines")
        if not self.disagg_timeout > 0:
            raise ValueError(
                f"--disagg-timeout {self.disagg_timeout} must be > 0 "
                "seconds")
        if self.mode not in ("master", "worker"):
            raise ValueError(f"unsupported mode '{self.mode}'")
        for knob in ("tp", "dp", "sp", "microbatches", "batch_size",
                     "max_slots", "decode_scan", "spec_gamma",
                     "trace_ring", "step_ring"):
            if getattr(self, knob) < 1:
                raise ValueError(f"--{knob.replace('_', '-')} must be >= 1")
        return self


@dataclass
class SDArgs:
    """Stable-Diffusion model options (reference lib.rs:90-127)."""

    sd_version: SDVersion = SDVersion.V1_5
    sd_tokenizer: Optional[str] = None
    sd_tokenizer_2: Optional[str] = None
    sd_use_f16: bool = True
    sd_width: Optional[int] = None
    sd_height: Optional[int] = None
    sd_sliced_attention_size: Optional[int] = None
    sd_clip: Optional[str] = None
    sd_clip2: Optional[str] = None
    sd_vae: Optional[str] = None
    sd_unet: Optional[str] = None
    sd_flash_attention: bool = False


@dataclass
class ImageGenerationArgs:
    """Per-request image generation parameters (reference lib.rs:145-200).

    Serves as both CLI flags and the JSON body of POST /api/v1/image
    (reference api/image.rs:15-18).
    """

    image_prompt: str = "A very realistic photo of a rusty robot walking on a sandy beach"
    image_uncond_prompt: str = ""
    sd_tracing: bool = False
    sd_img2img: Optional[str] = None
    sd_img2img_strength: float = 0.8
    sd_n_steps: Optional[int] = None
    sd_num_samples: int = 1
    sd_bsize: int = 1
    sd_intermediary_images: bool = False
    sd_guidance_scale: Optional[float] = None
    sd_seed: Optional[int] = None

    @classmethod
    def from_json(cls, body: dict) -> "ImageGenerationArgs":
        known = {f.name for f in fields(cls)}
        return cls(**{k: v for k, v in body.items() if k in known})

    def to_json(self) -> dict:
        return asdict(self)


def _add_dataclass_args(parser: argparse.ArgumentParser, dc_type) -> None:
    for f in fields(dc_type):
        name = "--" + f.name.replace("_", "-")
        default = f.default
        if isinstance(default, Enum):
            parser.add_argument(name, type=str, default=default.value,
                                dest=f.name)
        elif isinstance(default, bool):
            # --flag / --no-flag so True defaults (e.g. sd_use_f16) can be
            # disabled from the CLI
            parser.add_argument(name, action=argparse.BooleanOptionalAction,
                                default=default, dest=f.name)
        elif default is None and f.type == "Optional[bool]":
            parser.add_argument(name, action=argparse.BooleanOptionalAction,
                                default=None, dest=f.name)
        elif default is None:
            parser.add_argument(name, default=None, dest=f.name)
        else:
            parser.add_argument(name, type=type(default), default=default,
                                dest=f.name)


def build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(
        prog="cake-tpu",
        description="TPU-native distributed LLM + diffusion inference",
    )
    _add_dataclass_args(parser, Args)
    _add_dataclass_args(parser, SDArgs)
    _add_dataclass_args(parser, ImageGenerationArgs)
    return parser


def parse_args(argv=None):
    """Parse argv into (Args, SDArgs, ImageGenerationArgs)."""
    ns = build_parser().parse_args(argv)
    d = vars(ns)

    def pick(dc_type):
        kwargs = {}
        for f in fields(dc_type):
            v = d[f.name]
            if isinstance(f.default, Enum) and not isinstance(v, Enum):
                v = type(f.default)(v)
            if f.type in ("int", "Optional[int]") and isinstance(v, str):
                v = int(v)
            if f.type in ("float", "Optional[float]") and isinstance(v, str):
                v = float(v)
            kwargs[f.name] = v
        return dc_type(**kwargs)

    return pick(Args).validate(), pick(SDArgs), pick(ImageGenerationArgs)
