"""Continuous-batching inference engine.

The reference serves one request at a time — the REST handler write-locks
the whole Master for the duration of a generation (api/text.rs:67,
SURVEY.md §3.3). This engine replaces that with slot-based continuous
batching on top of the native scheduler (cake_tpu/native/scheduler.py):

  * a fixed pool of B decode slots shares ONE batched KV cache
    [L, B, T, KV, hd] — static shapes, so the decode step is a single
    cached XLA program regardless of which requests occupy which slots;
  * new requests are admitted *between decode steps*: `prefill_slot`
    fills exactly one slot's cache lines (dynamic_slice / update along the
    batch axis) while neighboring slots keep decoding next iteration;
  * every slot carries its own position, PRNG key, repeat-penalty ring and
    sampling options, so the batched step is "ragged": per-row RoPE rows,
    per-row causal masks, per-row temperature/top_p
    (model.forward_ragged, ops/sampling.sample_tokens_ragged);
  * tokens stream to per-request callbacks from the engine thread; EOS /
    max-token retirement frees the slot for the next queued request.

A row's output depends only on its own prompt, options and PRNG key — not
on which other requests happen to share the batch (verified by
tests/test_engine.py against the sequential generator).
"""

from __future__ import annotations

import logging
import os
import threading
import time
from collections import OrderedDict, deque
from dataclasses import dataclass, field
from functools import partial
from typing import Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from cake_tpu.analysis import engine_thread_only
from cake_tpu.models.chat import History, Message
from cake_tpu.obs import metrics as obs_metrics
from cake_tpu.obs import steps as obs_steps
from cake_tpu.obs.events import EventBus
from cake_tpu.obs.slo import SLOAccountant, parse_slo_targets
from cake_tpu.obs.tracing import RequestTracer
from cake_tpu.startup import STARTUP
from cake_tpu.models.llama.cache import KVCache
from cake_tpu.models.llama.config import LlamaConfig
from cake_tpu.models.llama.generator import (
    StreamDetokenizer, bucket_length, encode_text,
)
from cake_tpu.models.family import Windows
from cake_tpu.models.llama.model import (
    RopeTables, decode_step_ragged, prefill_slot, prefill_slot_prefixed,
)
from cake_tpu.models.llama.paged import mixed_bucket_for
from cake_tpu.models.step_programs import (
    ROW_ACTIVE, ROW_FROM_CARRY, ROW_SAMPLE, _masked_sample, make_decode_scan,
)
from cake_tpu.ops.sampling import SamplingConfig
from cake_tpu.sched import (
    SchedConfig, ShedController, ShedError, make_scheduler,
)
from cake_tpu.sched.classes import CLASS_RANK, validate_priority

log = logging.getLogger(__name__)

# a failed post-error rebuild bricks the engine thread; the counter makes
# that state visible on /api/v1/metrics instead of only in the logs
_RESET_FAILURES = obs_metrics.counter(
    "cake_engine_reset_failures_total",
    "Post-error engine resets that themselves failed (engine stopped)")

# paged-engine device-step wall latency (dispatch+fetch, sampling
# included), split by path — the observable the fold->pallas kernel
# switch moves; scan/burst decodes observe their per-step average so
# fold and pallas histograms compare like for like at any decode_scan
_PAGED_ATTN_STEP = obs_metrics.histogram(
    "cake_paged_attn_step_seconds",
    "Paged-engine step wall latency by path (decode|mixed)",
    labelnames=("path",))

# page-granular prefix sharing (the paged engine's prompt-cache path):
# the gauge tracks how many pool pages are currently backing more than
# their first mapping (capacity the pool did NOT have to spend), the
# counters how often and how many prompt tokens the sharing saved
_PREFIX_PAGES_SHARED = obs_metrics.gauge(
    "cake_prefix_pages_shared",
    "Shared prefix pages currently mapped into admitted slots' table "
    "rows (pool pages saved vs unshared admission)")
_PREFIX_PAGED_HITS = obs_metrics.counter(
    "cake_prefix_paged_hits_total",
    "Paged prefills served from pool-resident shared prefix pages")
_PREFIX_TOKENS_SAVED = obs_metrics.counter(
    "cake_prefix_tokens_saved_total",
    "Prompt tokens whose prefill was skipped via a cached prefix")

# SLO-aware scheduling (cake_tpu/sched): preemption/shed outcomes and
# per-class queue state — the observables behind the 429 contract and
# the bench --slo tier's preemption-on-vs-off comparison
_PREEMPTIONS = obs_metrics.counter(
    "cake_preemptions_total",
    "Decoding slots preempted for a starved higher priority class, by "
    "trigger (slots = slot-starved, pages = kv-page-starved)",
    labelnames=("reason",))
_SHED_REQUESTS = obs_metrics.counter(
    "cake_shed_requests_total",
    "Requests rejected by per-class load shedding (HTTP 429 with a "
    "computed Retry-After)",
    labelnames=("class",))
_QUEUE_DEPTH = obs_metrics.gauge(
    "cake_queue_depth",
    "Queued requests by priority class (SLO scheduler; refreshed at "
    "submit, each engine iteration, and metrics scrape)",
    labelnames=("class",))
_SCHED_TTFT = obs_metrics.histogram(
    "cake_sched_ttft_seconds",
    "Submit-to-first-token latency by priority class (includes queue "
    "wait and any preemption-induced requeues)",
    labelnames=("class",))

# crash recovery (cake_tpu/faults + _attempt_recovery): the observables
# behind the "one transient fault must not wipe a batch" contract —
# recovery outcomes, requests carried across a reset, and requests
# quarantined as poison so their batch could recover
_RECOVERIES = obs_metrics.counter(
    "cake_engine_recoveries_total",
    "Engine step-failure recovery attempts by outcome (recovered = "
    "reset + in-flight requests resubmitted; storm_breaker = too many "
    "resets in the window, snapshot + clean stop; reset_failed = the "
    "rebuild itself failed, engine stopped)",
    labelnames=("outcome",))
_RECOVERED_REQUESTS = obs_metrics.counter(
    "cake_requests_recovered_total",
    "In-flight requests carried across an engine reset via the "
    "fold-tokens-into-prompt resubmit (no client-visible failure)")
_POISON_REQUESTS = obs_metrics.counter(
    "cake_poison_requests_total",
    "Requests quarantined with a typed non-retryable error, by reason "
    "(implicated = present in implication_budget consecutive failed "
    "steps; resubmit_failed = recovery could not requeue it)",
    labelnames=("reason",))
_RECOVERY_SECONDS = obs_metrics.histogram(
    "cake_engine_recovery_seconds",
    "Wall seconds from deciding to recover to every surviving request "
    "requeued (backoff wait + cache rebuild + resubmission)")


@dataclass
class _Request:
    rid: int
    prompt_ids: List[int]
    max_new_tokens: int
    temperature: float
    top_p: float
    repeat_penalty: float
    # (delta, is_final) — or (delta, is_final, n_done) when the callback
    # declares wants_count (see stream_wants_count below)
    stream: Optional[Callable[..., None]]
    # stream callback declared `wants_count = True`: it is called with a
    # third argument, the number of finalized (token, logprob, top) entries
    # up to and including this delta — snapshotted on the engine thread so
    # streamed logprob entries pair exactly with the delta carrying their
    # text (api/server.py streaming logprobs)
    stream_wants_count: bool = False
    # previously-generated tokens whose penalty state must be reconstructed
    # (checkpoint resume): seeds the slot's repeat-penalty ring
    prime_tokens: List[int] = field(default_factory=list)
    # request asked for top-N alternatives (OpenAI top_logprobs): the
    # extra lax.top_k + host transfer is only paid while such a request
    # is in the batch
    want_top: bool = False
    # SLO scheduling (cake_tpu/sched): admission class and how many
    # times this request's slot has been reclaimed for a higher class
    priority: str = "standard"
    preemptions: int = 0
    # crash-implication tracking (_attempt_recovery): consecutive
    # failed steps this request was dispatched in; reset to 0 by any
    # step that emits for it, quarantined as poison at the budget
    crash_count: int = 0
    # durable serving (serve/journal.py): the client's idempotency key
    # (x-cake-idempotency-key — a retried submit with the same key
    # attaches instead of double-admitting), and the tokens generated
    # in PREVIOUS process generations that a cold-restart replay folded
    # into prompt_ids. The request's ABSOLUTE stream position — SSE
    # event ids, journal emit counts — is len(replayed_tokens) +
    # len(out_tokens).
    idempotency_key: Optional[str] = None
    replayed_tokens: List[int] = field(default_factory=list)
    out_tokens: List[int] = field(default_factory=list)
    out_logprobs: List[float] = field(default_factory=list)
    # per emitted token: [(alt_token_id, alt_logprob), ...] top-N list
    out_top: List[list] = field(default_factory=list)
    done: threading.Event = field(default_factory=threading.Event)
    error: Optional[Exception] = None
    slot: int = -1
    # spilled-victim resume (cake_tpu/kv host tier): set by
    # _alloc_slot_pages when the slot's KV was restored from host RAM
    # — the admission path then skips the recompute prefill entirely
    _kv_restored: bool = False
    # disaggregated serving (cake_tpu/kv/transfer.py): on the PREFILL
    # host, the callback handed the captured page shipment at
    # retirement; on the DECODE host, True while the admission is
    # parked awaiting the peer's shipment (disagg_complete enters it
    # into the scheduler)
    ship_sink: Optional[Callable] = None
    _disagg_pending: bool = False
    submit_t: float = 0.0
    first_token_t: float = 0.0
    finish_t: float = 0.0
    # the streamed text's place in out_tokens (_incremental_text): the
    # detokeniser, made at the first delta, and how many of out_tokens
    # it has been handed. On the request, so a preempted and requeued
    # row goes on where its text stopped
    _detok: Optional[StreamDetokenizer] = None
    _detok_seen: int = 0


class RequestHandle:
    """Caller-side view of a submitted request."""

    def __init__(self, req: _Request, tokenizer, eos_ids):
        self._req = req
        self._tokenizer = tokenizer
        self._eos_ids = eos_ids

    def wait(self, timeout: Optional[float] = None) -> bool:
        return self._req.done.wait(timeout)

    def finished(self) -> bool:
        """True once the request retired (tokens final or error set) —
        non-blocking; the disagg prefill plane's writer uses this to
        spot admissions that died before capturing a shipment."""
        return self._req.done.is_set()

    @property
    def token_ids(self) -> List[int]:
        ids = self._req.out_tokens
        return [t for t in ids if t not in self._eos_ids]

    @property
    def token_logprobs(self) -> List[tuple]:
        """(token_id, logprob) pairs aligned with token_ids (EOS dropped;
        the OpenAI `logprobs` content)."""
        return [(t, lp) for t, lp in zip(self._req.out_tokens,
                                         self._req.out_logprobs)
                if t not in self._eos_ids]

    @property
    def token_top_logprobs(self) -> List[list]:
        """Per emitted token, the top-N most probable alternatives as
        [(token_id, logprob), ...] (the OpenAI `top_logprobs` content),
        aligned with token_ids (EOS dropped)."""
        return [top for t, top in zip(self._req.out_tokens,
                                      self._req.out_top)
                if t not in self._eos_ids]

    def text(self) -> str:
        if self._req.error is not None:
            raise self._req.error
        return self._tokenizer.decode(self.token_ids)

    @property
    def ttft(self) -> float:
        """Seconds from submit to first token (includes queueing)."""
        r = self._req
        return (r.first_token_t - r.submit_t) if r.first_token_t else 0.0

    @property
    def tokens_per_s(self) -> float:
        r = self._req
        n = len(r.out_tokens)
        dt = (r.finish_t or time.perf_counter()) - (r.first_token_t or 0)
        return (n - 1) / dt if n > 1 and dt > 0 else 0.0


@dataclass
class EngineStats:
    """Aggregate throughput counters (reference worker.rs:254-283 analog)."""

    steps: int = 0
    tokens_generated: int = 0
    requests_completed: int = 0
    decode_time_s: float = 0.0
    prefill_time_s: float = 0.0
    prefix_hits: int = 0     # prefills served from a registered prefix
    errors: int = 0
    last_error: str = ""
    # SLO scheduling: slots reclaimed for a higher class / requests
    # rejected by load shedding (cake_tpu/sched)
    preemptions: int = 0
    shed: int = 0
    # KV host tier (cake_tpu/kv): spill/restore EVENTS (the
    # cake_kv_spill_total counters count pages); resident spills are
    # the subset that parked an ACTIVELY-DECODING stream to admit a
    # new one (pool oversubscription, cake_kv_resident_spills_total)
    kv_spills: int = 0
    kv_restores: int = 0
    kv_resident_spills: int = 0
    # disaggregated serving (cake_tpu/kv/transfer.py): shipments
    # captured on the prefill host / shipped prefills adopted on the
    # decode host (the wire counters are cake_kv_ship_total et al.)
    kv_ships: int = 0
    kv_adopts: int = 0
    # crash recovery (cake_tpu/faults): successful reset+resubmit
    # cycles, requests carried across them, and requests quarantined
    # as poison so the rest of their batch could recover
    recoveries: int = 0
    requests_recovered: int = 0
    poisoned: int = 0
    # live reconfiguration (cake_tpu/autotune): completed hot switches
    # and guard-driven reverts (engine.reconfigure)
    config_switches: int = 0
    config_rollbacks: int = 0
    # speculative rounds (cake_tpu/spec): drafts offered / kept across
    # all rows
    spec_proposed: int = 0
    spec_accepted: int = 0

    @property
    def spec_acceptance(self) -> float:
        return (self.spec_accepted / self.spec_proposed
                if self.spec_proposed else 0.0)

    @property
    def decode_tokens_per_s(self) -> float:
        return (self.tokens_generated / self.decode_time_s
                if self.decode_time_s > 0 else 0.0)


class InferenceEngine:
    """Slot-based continuous batching over one shared batched KV cache."""

    # -- cakelint vocabulary (tools/cakelint.py, cake_tpu/analysis/) ----
    # Machine-checked threading discipline; the prose invariants these
    # encode used to live only in comments here and in two source-scan
    # tests. ENGINE_THREAD_ATTRS is single-writer engine-thread state:
    # the mapped lock (if any) is the ONE lock whose holder may touch
    # the attr from a handler thread; None means only
    # _run_on_engine_thread reaches it. HANDLER_THREAD_METHODS are the
    # entry points that run on HTTP handler / scrape / signal / health
    # threads and are statically checked against that table.
    ENGINE_THREAD_ATTRS = {
        # paged pool + page-table row state (the pager swaps wholesale
        # during a live reconfigure — admission reads its bounds under
        # the same lock the switch holds)
        "_pager": "_switch_lock",
        # slot -> request mapping and the per-slot device mirrors:
        # written only between device steps by the engine loop
        "_slot_req": None,
        "_mixed_pending": None,
        "_implicated": None,
        "_last_jit": None,
        "_page_starved": None,
        "_pending_page_preempt": None,
        # decode-resident spill state (_spill_resident_stream): the
        # admission-order stamp for LRU victim choice, the iteration's
        # decode-resident candidate set, and the parked flag that
        # forces the decode dispatch to re-validate its (stale) plan
        "_admit_seq": None,
        "_cur_decode": None,
        "_resident_parked": None,
        # handler<->engine mailboxes: strictly lock-guarded
        "_cancel_q": "_rid_lock",
        "_cmd_q": "_rid_lock",
        # disaggregated serving: shipments staged by the decode plane's
        # channel thread (disagg_complete) for the engine thread's
        # adoption in _do_prefill/_mixed_admit
        "_adopt_store": "_rid_lock",
    }
    HANDLER_THREAD_METHODS = (
        "submit", "chat", "cancel", "stop", "begin_drain",
        "drain_state", "_drain_eta_s", "register_prefix",
        "unregister_prefix", "_auto_register_system",
        "_attach_idempotent", "seed_finished_idempotent",
        "reconfigure", "request_timeline", "recovery_state",
        "autotune_state", "current_config", "_set_queue_gauges",
        "shutdown_save", "_snapshot_before_fail", "_fail_all",
        "disagg_complete",
    )
    # optional subsystems (None = disabled plane): every dotted use
    # must sit under an `is not None` guard so a disabled plane costs
    # exactly one attribute read per site (the --fault-plan injector
    # discipline, generalized)
    OPTIONAL_PLANES = ("_faults", "events", "_journal", "_shed",
                       "_control", "_host_tier", "_autotuner",
                       "telemetry", "sentinel", "_actions",
                       "_postmortem", "_disagg", "_specp")
    # the only legal nesting order; _rid_lock sits on the submit/emit
    # hot path, so nothing may block under it
    LOCK_ORDER = ("_switch_lock", "_rid_lock", "_ckpt_lock")
    NO_BLOCKING_UNDER = ("_rid_lock",)

    def __init__(
        self,
        config: LlamaConfig,
        params,
        tokenizer,
        *,
        max_slots: int = 8,
        max_seq_len: int = 4096,
        max_queue: int = 1024,
        sampling: Optional[SamplingConfig] = None,
        seed: int = 299792458,
        cache_dtype=jnp.bfloat16,
        step_fns=None,
        cache: Optional[KVCache] = None,
        decode_scan_steps: int = 1,
        auto_prefix_system: bool = False,
        max_auto_prefixes: int = 8,
        prefill_chunk: Optional[int] = None,
        top_logprobs_cap: int = 20,
        ring: Optional[bool] = None,
        spec_gamma: int = 4,
        spec_draft_params=None,
        spec_draft_config=None,
        kv_pages: Optional[int] = None,
        kv_page_size: int = 128,
        paged_attn: Optional[str] = None,
        kv_dtype: Optional[str] = None,
        kv_host_pages: Optional[int] = None,
        prompt_limit: Optional[int] = None,
        decode_budget: Optional[int] = None,
        trace_events: Optional[str] = None,
        trace_ring: int = 256,
        step_log: Optional[str] = None,
        step_ring: int = 512,
        event_log: Optional[str] = None,
        event_ring: int = 1024,
        slo_targets=None,
        priority_classes: bool = False,
        preemption: Optional[bool] = None,
        shed: bool = False,
        sched_config: Optional[SchedConfig] = None,
        fault_plan: Optional[str] = None,
        recovery: Optional[bool] = None,
        recovery_config=None,
        journal: Optional[str] = None,
        journal_fsync: str = "batch",
        autotune: Optional[str] = None,
        autotune_policy=None,
        autotune_config=None,
        sentinel: bool = False,
        sentinel_interval: float = 2.0,
        sentinel_act: bool = False,
        postmortem_dir: Optional[str] = None,
        disagg: Optional[str] = None,
        disagg_peer: Optional[str] = None,
        disagg_token: Optional[str] = None,
        disagg_timeout_s: float = 30.0,
    ):
        self.config = config
        self.params = params
        self.tokenizer = tokenizer
        self.max_slots = max_slots
        self.max_seq_len = max_seq_len
        # windowed layouts (the sp engine: prompt region + decode tail)
        # bound prompts and per-request generation separately from
        # max_seq_len; None = the classic single-window rules
        self.prompt_limit = prompt_limit
        self.decode_budget = decode_budget
        self.defaults = sampling or SamplingConfig()
        # alternatives computed per sample step for OpenAI `top_logprobs`
        # (requests slice their n <= cap host-side; 20 is the API maximum;
        # one lax.top_k over [B, V] — noise next to the forward pass)
        self.n_top = top_logprobs_cap
        self.rope = RopeTables.create(config, max_seq_len)
        # step_fns: (prefill_slot_fn, decode_ragged_fn) replacements with
        # the same signatures as model.prefill_slot/decode_step_ragged —
        # e.g. parallel.pipeline.make_engine_step_fns for topology-sharded
        # serving. cache: optional pre-placed KV cache (must match the step
        # fns' sharding contract).
        # step_fns: 2-4 fns replacing the built-in jitted steps —
        # (prefill_slot_fn, decode_ragged_fn[, decode_scan_fn
        # [, prefill_chunk_fn]]), e.g. parallel.pipeline
        # .make_engine_step_fns for topology-sharded serving. With the
        # scan/chunk fns present, multi-step decode and chunked prefill
        # work over the pipeline exactly as on the built-in path.
        # ring: None = auto (builtin path decides from config); True =
        # the caller's custom step fns operate on a ring cache (pipelined
        # sliding-window serving — make_engine passes ring step fns AND a
        # W-length sharded cache together)
        self.ring = False
        if step_fns is None:
            from cake_tpu.models.llama.model import prefill_slot_chunk
            self._prefill_slot = prefill_slot
            self._decode_step = decode_step_ragged
            self._decode_scan_impl = _decode_scan
            self._prefill_chunk_step = prefill_slot_chunk
            if (config.sliding_window is not None
                    and config.sliding_window < max_seq_len):
                # ring-buffer KV cache: a sliding-window model never
                # attends past `window`, so the cache holds only W =
                # window slots (position p -> slot p % W) — KV memory
                # drops to window/max_seq of dense. All prompts prefill
                # through the ring chunk fn (windows <= W keep scatter
                # indices unique); decode writes wrap modularly.
                from cake_tpu.models.llama.model import (
                    decode_step_ragged_ring, prefill_slot_chunk_ring,
                )
                self.ring = True
                self._decode_step = decode_step_ragged_ring
                self._prefill_chunk_step = prefill_slot_chunk_ring
                self._decode_scan_impl = _decode_scan_ring
        else:
            fns = tuple(step_fns)
            self._prefill_slot, self._decode_step = fns[0], fns[1]
            self._decode_scan_impl = fns[2] if len(fns) > 2 else None
            self._prefill_chunk_step = fns[3] if len(fns) > 3 else None
            if ring:
                if self._prefill_chunk_step is None:
                    raise ValueError(
                        "ring step fns require a chunked-prefill variant "
                        "(every ring prompt prefills in windows <= W)")
                self.ring = True
        # decode_scan_steps > 1: when no request is waiting, run K decode
        # steps as ONE on-device lax.scan per host round-trip — host
        # dispatch latency amortizes across K tokens.
        if decode_scan_steps < 1:
            raise ValueError("decode_scan_steps must be >= 1")
        if decode_scan_steps > 1 and self._decode_scan_impl is None:
            log.warning(
                "decode_scan_steps=%d ignored: these custom step fns "
                "provide no scan variant", decode_scan_steps)
            decode_scan_steps = 1
        self._decode_scan = decode_scan_steps
        # prefix caching capability: builtin dense path, or a pipelined
        # path with a chunked-prefill variant (the suffix windows at
        # pos0 = P through it). Ring caches own their layout (install
        # writes dense positions) and multi-host serving would need the
        # registration replayed (attach_control re-checks) — both refuse.
        self._prefix_capable = (
            not self.ring
            and (self._prefill_slot is prefill_slot
                 or self._prefill_chunk_step is not None))
        # prefill_chunk: admit prompts longer than C in fixed C-token
        # windows (one compiled program for every prompt length; bounded
        # activation memory). Same divisibility contract as the
        # generator's knob — a clamped final window would overwrite
        # earlier cache entries.
        if prefill_chunk is not None and self._prefill_chunk_step is None:
            # check BEFORE validation: an engine whose step fns lack a
            # chunk variant ignores the knob with a warning, not a crash
            log.warning("prefill_chunk ignored: these custom step fns "
                        "provide no chunked-prefill variant")
            prefill_chunk = None
        if self.ring:
            # every prefill must be a ring window <= W
            W = config.sliding_window
            prefill_chunk = min(prefill_chunk or min(512, W), W)
        if prefill_chunk is not None and (
                prefill_chunk < 1 or max_seq_len % prefill_chunk != 0):
            raise ValueError(
                f"prefill_chunk {prefill_chunk} must be >= 1 and divide "
                f"max_seq_len {max_seq_len}"
                + (" (ring/sliding-window serving requires a chunk that "
                   "divides max_seq_len; pass --prefill-chunk)"
                   if self.ring else ""))
        # speculative decoding (cake_tpu/spec): a KIND OF ROW of the
        # paged engine — a draft model's KV lives in a second paged
        # pool addressed by the SAME page allocator, streams opt in
        # lazily per-row (incompatible sampling simply decodes plain),
        # and acceptance truncates the speculative suffix pages back
        # to the pool every round.
        self.spec_gamma = spec_gamma
        self._spec_paged = spec_draft_params is not None
        self._specp = None
        if self._spec_paged:
            from cake_tpu.spec import SpecPlane
            if kv_pages is None:
                raise ValueError(
                    "--spec-draft requires --kv-pages: a speculating "
                    "row's draft and target KV share the paged pool's "
                    "page allocator")
            if kv_dtype in ("int8", "int4"):
                raise ValueError(
                    f"--spec-draft requires f32/bf16 KV pages, got "
                    f"--kv-dtype {kv_dtype}: the draft pool has no "
                    "quantized flavor yet (ROADMAP item 3)")
            if spec_draft_config.vocab_size != config.vocab_size:
                raise ValueError(
                    "spec draft and target must share a vocabulary")
            if spec_gamma < 1:
                raise ValueError(
                    f"spec_gamma must be >= 1, got {spec_gamma}")
            from cake_tpu.autotune.spec import SpecGammaTuner
            self._specp = SpecPlane(
                spec_draft_params, spec_draft_config, spec_gamma,
                rope=RopeTables.create(spec_draft_config, max_seq_len),
                tuner=SpecGammaTuner(gamma=spec_gamma))
        # paged KV (round-5, the 32-slot HBM-thrash fix): KV lives in a
        # shared pool of kv_pages fixed-size pages; slots map position
        # ranges through a table and the page ALLOCATOR gates admission,
        # so resident KV is bounded by the pool, not slots x max_seq_len
        # (models/llama/paged.py).
        self.paged = kv_pages is not None
        self.paged_attn: Optional[str] = None
        # --kv-dtype: storage dtype for the PAGED pool. "int8"/"int4"
        # select the quantized page pools (cake_tpu/kv: int8 pages or
        # nibble-packed int4 pages + per-page per-kv-head f32 scales —
        # ~4x / ~8x the resident streams per pool byte vs f32); other
        # names resolve to a plain pool dtype. Quantized KV without
        # --kv-pages is a loud config error, not a silent no-op.
        self.kv_quant = kv_dtype in ("int8", "int4")
        # config identity the live-reconfiguration seam (reconfigure /
        # cake_tpu/autotune) needs verbatim: the configured storage
        # name, the base cache dtype, the host-tier capacity and the
        # custom-step marker — a rebuilt pool must resolve exactly as
        # the startup one did
        self._kv_dtype_name = kv_dtype
        self._base_cache_dtype = cache_dtype
        self._kv_host_pages = kv_host_pages
        self._custom_steps = step_fns is not None
        # cross-subsystem event bus (obs/events.py), created BEFORE the
        # paged setup so the host tier can attach to it: preemption,
        # KV spill/restore, prefix hits, recovery, switches, shedding,
        # fault injections and recompiles all publish request-linked
        # events here (GET /api/v1/events; --event-log JSONL sink).
        # --event-ring 0 disables the plane: self.events is then None
        # and every publish site costs one attribute test (the
        # --fault-plan injector discipline, pinned by a source scan)
        self.events = (EventBus(capacity=event_ring, log_path=event_log)
                       if event_ring > 0 else None)
        # SLO attainment + goodput accounting (obs/slo.py): per-class
        # targets from --slo-targets (defaults otherwise), fed from
        # the tracer's finish seam so TTFT/e2e verdicts use the
        # ORIGINAL admission clock across resubmits
        self.slo = SLOAccountant(
            slo_targets if isinstance(slo_targets, dict)
            else parse_slo_targets(slo_targets))
        if self.kv_quant and not self.paged:
            raise ValueError(
                f"--kv-dtype {kv_dtype} requires --kv-pages: quantized "
                "KV pages live in the paged pool")
        self._host_tier = None
        # pid -> monotonic last-hit time (the cold-prefix LRU order)
        self._prefix_last_hit: dict = {}
        self.prefill_chunk = prefill_chunk
        # token-level continuous batching IS the paged engine: an
        # admission's prefill windows join the very next mixed step
        # alongside decode rows (_do_mixed). The dense, ring and
        # pipelined engines keep their prefill/decode phases.
        # slot -> in-flight prefill progress (req, remaining window
        # offsets); teardown paths clear entries via
        # _release_slot_pages so cancel/preempt/error cannot leave a
        # ghost chunk row in the next mixed step
        self._mixed_pending: dict = {}
        # fixed mixed-chunk width: prompts walk the mixed step C tokens
        # per iteration — ONE compiled program for every prompt length
        # (a per-bucket width would recompile the hottest program).
        # Set by the paged setup below (_resolve_paged_attn), which
        # narrows the default to what the mixed kernel can hold.
        self._mixed_chunk: Optional[int] = None
        # step kind -> the paged attention that actually runs for it
        # ({"decode", "mixed"[, "spec"]} -> fold|pallas); empty = dense
        self.attn_impl: dict = {}
        # what the engine reads of the model's family (models/family.py):
        # its step programs, its cache, its counters, and what its rows
        # cannot move yet, which is refused here by the option's name,
        # never ignored
        self._family = family = config.family
        refusal = family.refusal({
            "--kv-pages": not self.paged,
            "topology": step_fns is not None,
            "--spec-draft": self._spec_paged,
            "--kv-dtype": self.kv_quant,
            "--kv-host-pages": kv_host_pages is not None,
            "--disagg": disagg is not None,
            "--auto-prefix": auto_prefix_system})
        if refusal:
            raise ValueError(refusal)
        if self.paged:
            if step_fns is not None or self.ring:
                raise ValueError(
                    "--kv-pages requires the built-in dense single-"
                    "device path (no topology/ring)")
            if cache is not None:
                raise ValueError(
                    "--kv-pages builds its own page pool; a pre-placed "
                    "cache= cannot apply")
            self._setup_paged_exec(kv_pages, kv_page_size, paged_attn,
                                   kv_host_pages)
        elif kv_host_pages is not None:
            log.warning("--kv-host-pages ignored: the host KV tier "
                        "spills paged pool pages (set --kv-pages)")
        cache_len = (config.sliding_window if self.ring else max_seq_len)
        if not self.paged:
            self.cache = cache if cache is not None else KVCache.create(
                config, max_slots, cache_len, dtype=cache_dtype)
        # remember placement so the post-error rebuild (see _run) restores
        # an identically-sharded cache even after donation freed the buffers
        self._capture_cache_identity()
        # SLO-aware scheduling (cake_tpu/sched): priority-class queues
        # with anti-starvation aging replace FIFO admission; preemption
        # recompute-folds a lower-class slot back into the queue when a
        # higher class is slot- or page-starved; shedding turns
        # overload into honest 429s. The FIFO native scheduler stays
        # the priority-free fallback.
        self._sched_cfg = sched_config or SchedConfig()
        self._slo = bool(priority_classes)
        can_preempt = self.decode_budget is None
        if preemption is None:
            self._preemption = self._slo and can_preempt
        else:
            self._preemption = bool(preemption)
        if self._preemption and not self._slo:
            log.warning("--preemption requires --priority-classes; "
                        "preemption disabled")
            self._preemption = False
        if self._preemption and not can_preempt:
            log.warning(
                "preemption disabled: windowed (ctx+tail) layouts "
                "cannot fold generated tokens back into the prompt "
                "window")
            self._preemption = False
        # crash recovery (the fail-everything replacement): on a step
        # failure, snapshot-classify-reset-RESUBMIT the in-flight
        # requests through the checkpoint fold-tokens-into-prompt path
        # instead of failing them all. Auto-on wherever the fold works
        # (the same flavors preemption can resume); windowed
        # (ctx+tail) engines keep the legacy fail-all path.
        from cake_tpu.serve.errors import RecoveryConfig
        self._recovery_cfg = recovery_config or RecoveryConfig()
        if recovery is None:
            self._recover = can_preempt
        else:
            self._recover = bool(recovery)
            if self._recover and not can_preempt:
                log.warning(
                    "crash recovery disabled: windowed (ctx+tail) "
                    "layouts cannot fold generated tokens back into "
                    "the prompt window")
                self._recover = False
        # reset-storm breaker state: monotonic times of recent resets
        # (recovered OR legacy), consecutive-reset counter for backoff,
        # and a bounded recovery-latency log for bench --chaos
        self._reset_times: List[float] = []
        self._consec_resets = 0
        self.recovery_seconds: List[float] = []
        self._breaker_tripped = False
        # deterministic fault injection (cake_tpu/faults): None without
        # a --fault-plan — every site guard is then one attribute test
        from cake_tpu.faults import build_injector
        self._faults = build_injector(fault_plan)
        if self._faults is not None:
            # firings ride the event bus too (None stays None: the
            # injector's publish site guards `is not None` like ours)
            self._faults.events = self.events
            log.warning("fault plan armed: %s",
                        self._faults.plan.describe())
        # rids dispatched in the CURRENT device step — the blast radius
        # the recovery path implicates on failure (overwritten by every
        # dispatch; a failure before any dispatch implicates nobody)
        self._implicated: Sequence = ()
        # durable serving (serve/journal.py): --journal arms a
        # write-ahead request journal — admissions, emitted-token
        # batches and retire tombstones, replayed at cold restart so a
        # kill -9 loses no stream. None without the flag: every call
        # site below is one attribute test (the --fault-plan injector
        # discipline, pinned by a source-scan test).
        self._journal = None
        if journal:
            from cake_tpu.serve.journal import RequestJournal
            self._journal = RequestJournal(journal, fsync=journal_fsync)
            self._journal.faults = self._faults
            self._journal.owner = self
            log.info("request journal armed: %s (fsync=%s)", journal,
                     journal_fsync)
        # idempotent-submit registry: key -> live rid, and a bounded
        # ring of FINISHED keyed requests so a retry that lands after
        # retirement still attaches to the completed stream instead of
        # re-running it. Both guarded by _rid_lock.
        self._idem_live: dict = {}
        self._idem_done: "OrderedDict" = OrderedDict()
        self._idem_done_cap = 128
        # drain mode (POST /api/v1/drain, SIGTERM): admissions refuse
        # with a typed 429 while in-flight work finishes or snapshots
        self._draining = False
        self._shed = ShedController(self._sched_cfg) if shed else None
        # rank of a page-starved higher-class admission awaiting a
        # victim; consumed at the TOP of the next engine iteration (a
        # mid-wave preemption would leave already-planned decode rows
        # writing through a released page-table row)
        self._pending_page_preempt: Optional[int] = None
        # decode-resident spill (kv oversubscription): admission-order
        # stamp for LRU victim choice, this iteration's decode-resident
        # slots (plan()'s decode rows — NOT same-wave admissions, whose
        # prefill may be mid-flight), and the parked-this-iteration
        # flag that makes the decode dispatch re-validate its plan
        self._admit_seq = 0
        self._cur_decode: dict = {}
        self._resident_parked = False
        # retained for live reconfiguration: a hot switch that changes
        # max_slots rebuilds/resizes the scheduler at the same queue
        # capacity (reconfigure)
        self._max_queue = max_queue
        self.scheduler = make_scheduler(
            max_slots, max_queue, priority_classes=self._slo,
            config=self._sched_cfg)
        self.stats = EngineStats()
        # request-lifecycle traces (obs/tracing.py): spans recorded at
        # the submit/prefill/emit/retire seams below, so every serving
        # mode (dense, paged, spec, pipelined, sp / stage x sp / dp x
        # sp step fns) is traced identically. trace_events: optional
        # JSONL event log path (--trace-events).
        self.tracer = RequestTracer(capacity=trace_ring,
                                    events_path=trace_events,
                                    slo=self.slo)
        # step-level flight recorder + jit compile/cost accounting
        # (obs/steps.py): one record per engine step at the dispatch
        # seams below, served at GET /api/v1/steps and optionally
        # appended to --step-log. The accountant key prefix namespaces
        # this engine's config so two engines with different configs
        # (or cache dtypes) can never alias each other's compiled
        # signatures in the process-global seen-set.
        flavor = (f"paged-{self.paged_attn}" if self.paged else
                  "ring" if self.ring else
                  "custom" if step_fns is not None else "dense")
        self.flight = obs_steps.StepTelemetry(
            impl=flavor, capacity=step_ring, log_path=step_log,
            key_prefix=(config, max_slots, max_seq_len,
                        str(self._cache_dtype), flavor),
            events=self.events, counters=family.counters)
        # latest dispatch's _JitStep (engine-thread-only mailbox between
        # the device-call seam and the step record that follows it)
        self._last_jit = None
        # a sparse model's step programs return their expert counters;
        # they wait here, on the device, for the next sampled-token
        # fetch, and then on the host for the next step record
        self._moe_pending: list = []
        self._moe_fetched: list = []
        # small host arrays the chained decode dispatches last sent the
        # device, by name (_held)
        self._dev_held: dict = {}
        # distributed-trace annotation: events published with a rid
        # pick up the request's x-cake-trace id from the tracer, so
        # the front-door router's federated timeline can select this
        # replica's events by trace (one dict lookup per INCIDENT —
        # events are never per-token)
        if self.events is not None:
            self.events.trace_of = self.tracer.trace_for
        # online regression sentinel (--sentinel, obs/sentinel.py):
        # rolling-window detectors over the flight recorder / event
        # bus / SLO accountant, ticked from a daemon thread between
        # start() and stop() — zero hot-path instrumentation. None
        # without the flag (one attribute test per site, the
        # --fault-plan discipline).
        self.sentinel = None
        if sentinel:
            from cake_tpu.obs.sentinel import attach_engine_sentinel
            self.sentinel = attach_engine_sentinel(
                self, interval_s=sentinel_interval)

        B = max_slots
        self._pos = np.zeros(B, np.int64)            # next write position
        self._last_tok = np.zeros(B, np.int64)
        self._steps = np.zeros(B, np.int64)          # generated count per slot
        self._temp = np.full(B, self.defaults.temperature or 0.0, np.float32)
        self._top_p = np.ones(B, np.float32)
        self._penalty = np.full(B, self.defaults.repeat_penalty, np.float32)
        self._ring = jnp.full((B, self.defaults.repeat_last_n), -1, jnp.int32)
        self._key_seed = seed                        # for _reset_after_error
        self._reset_count = 0
        root = jax.random.PRNGKey(seed)
        self._keys = jax.random.split(root, B)       # [B] keys
        self._slot_req: List[Optional[_Request]] = [None] * B

        # registered prompt prefixes: id -> (token ids, k, v) with k/v
        # [L, 1, P, KV, hd] in cache dtype (register_prefix)
        self._prefixes: dict = {}
        self._next_prefix_id = 1
        # auto_prefix_system: chat() registers each distinct system
        # prompt's rendered head once (FIFO-capped so a public API cannot
        # grow the registry without bound). Keyed by the rendered head
        # STRING so the membership test costs no tokenization; the value
        # is None while a registration is in flight (reservation — chat()
        # runs on concurrent HTTP handler threads).
        self._auto_prefix = auto_prefix_system
        self._max_auto = max_auto_prefixes
        # head str -> prefix id | None (in-flight) | -1 (unqualifying
        # head, negative-cached) — only non-negative ids key _prefixes
        self._auto_pids: dict = {}

        # multi-host serving: the coordinator publishes each device-step
        # op through _control (serve/control.py) so follower processes
        # replay the identical SPMD dispatch; _multihost additionally
        # localizes logits so sampling is process-local + deterministic
        self._control = None
        self._multihost = False
        # follower side: seq of the last successfully APPLIED control
        # op — the exporter (obs/federation.py) ships it in telemetry
        # frames so the coordinator's fleet view can compute lag
        self.applied_op_seq = 0
        # coordinator side: an attached obs/federation
        # TelemetryCollector — request_timeline merges its remote
        # events so one explain call spans hosts
        self.telemetry = None

        self._next_rid = 1
        self._rid_lock = threading.Lock()
        # engine-thread command queue (multi-host prefix ops: their
        # device work is a cross-process collective, so it must dispatch
        # in the engine thread's program order — see _run_on_engine_thread)
        self._cmd_q: list = []
        # serializes pre-fail snapshot writes (health-monitor thread)
        # against the shutdown keep-or-save decision (signal/serve
        # thread) — without it a SIGTERM landing mid-failure could read
        # _prefail_written before the pre-fail write and clobber it
        self._ckpt_lock = threading.Lock()
        self._requests = {}
        # rids whose callers gave up (client disconnect): drained by the
        # ENGINE thread at the top of its loop, so request/slot teardown
        # has a single writer
        self._cancel_q: List[int] = []
        self._wake = threading.Event()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

        # live reconfiguration (cake_tpu/autotune): --autotune
        # {off,manual,auto}. `manual` arms POST /api/v1/autotune;
        # `auto` additionally runs the policy controller from the
        # engine thread (_autotune_tick). The hot-switch seam
        # (reconfigure) exists regardless of the mode — checkpoint
        # restore and tests drive it directly.
        self.config_epoch = 0
        self._switch_lock = threading.Lock()
        self._switch_inflight = False
        self._switch_log: deque = deque(maxlen=64)
        mode = autotune or "off"
        if mode not in ("off", "manual", "auto"):
            raise ValueError(
                f"--autotune must be off, manual or auto, got {mode!r}")
        if mode != "off" and not self._reconfig_supported():
            log.warning("--autotune disabled: %s",
                        self._reconfig_refusal())
            mode = "off"
        self.autotune_mode = mode
        if mode != "off":
            # publish the STARTUP config through the info gauge: the
            # "live effective config" contract must hold before (and
            # without) any switch, not only after the first one
            from cake_tpu.autotune import set_config_info
            set_config_info(self.current_config())
        self._autotuner = None
        self._autotune_last = 0.0
        # (t, submitted, completed, tokens, shed) deltas for the
        # signal gather (_gather_autotune_signals)
        self._autotune_prev: Optional[tuple] = None
        if mode == "auto":
            from cake_tpu.autotune import (
                AutotuneController, ControllerConfig, PolicyTable,
            )
            if autotune_policy is None:
                raise ValueError(
                    "--autotune auto requires --autotune-policy (fit "
                    "one with tools/autotune_fit.py)")
            if isinstance(autotune_policy, str):
                policy = PolicyTable.load(autotune_policy)
            elif isinstance(autotune_policy, dict):
                policy = PolicyTable.from_dict(autotune_policy).validate()
            else:
                policy = autotune_policy
            policy.validate(max_seq_len=self.max_seq_len)
            self._autotuner = AutotuneController(
                policy, self.current_config(),
                config=autotune_config or ControllerConfig())
            log.info("autotune: auto mode, %d policy regime(s), "
                     "interval %.1fs",
                     len(policy.regimes),
                     self._autotuner.config.interval_s)

        # closed-loop action plane (--sentinel-act, obs/actions.py):
        # sentinel anomalies become first-class autotune signals with a
        # typed, rate-bounded, metric-counted audit trail. None without
        # the flag — report-only stays byte-identical to PR 15.
        self._actions = None
        if sentinel_act:
            if self.sentinel is None:
                raise ValueError(
                    "--sentinel-act requires --sentinel (nothing to "
                    "act on without the anomaly sentinel)")
            from cake_tpu.obs.actions import (
                ActionPlane, EngineAnomalyActuator,
            )
            self._actions = ActionPlane(events=self.events)
            EngineAnomalyActuator(self, self._actions).attach(
                self.sentinel)
        # black-box postmortem sink (--postmortem-dir): breaker stops,
        # poison quarantines, failed recoveries and SIGTERM dump one
        # forensic bundle each (tools/postmortem.py renders them)
        self._postmortem = None
        if postmortem_dir:
            from cake_tpu.obs.actions import PostmortemSink
            self._postmortem = PostmortemSink(postmortem_dir)

        # disaggregated prefill/decode (--disagg, kv/transfer.py): one
        # engine runs prefill-only and ships pool pages; the other is
        # the front door, adopting shipped prefills into its own pool.
        # _adopt_store stages reassembled shipments (channel thread ->
        # engine thread) keyed by rid; it exists even without the plane
        # so the adoption peeks stay branch-free.
        self._adopt_store = {}
        self._disagg = None
        if disagg is not None:
            if not self.paged:
                raise ValueError(
                    "--disagg requires the paged KV pool (--kv-pages): "
                    "pages are the transfer unit")
            if not disagg_peer:
                raise ValueError(
                    "--disagg requires --disagg-peer host:port (the "
                    "prefill engine binds it; the decode engine "
                    "connects to it)")
            from cake_tpu.kv.transfer import build_disagg_plane
            token = disagg_token or os.environ.get(
                "CAKE_DISAGG_TOKEN", "")
            self._disagg = build_disagg_plane(
                self, disagg, disagg_peer, token, events=self.events,
                timeout_s=disagg_timeout_s)
            log.info("disagg: %s role, peer %s", disagg, disagg_peer)

    # -- lifecycle ------------------------------------------------------------

    def start(self) -> "InferenceEngine":
        if self._thread is None:
            from cake_tpu.utils.profiling import log_memory
            if self.paged:
                self._warm_mixed_buckets()
            log_memory("engine start")
            self._thread = threading.Thread(target=self._run, daemon=True,
                                            name="cake-engine")
            self._thread.start()
            if self.sentinel is not None:
                self.sentinel.start()
            if self._disagg is not None:
                self._disagg.start()
        return self

    def stop(self, timeout: float = 10.0) -> None:
        if self.sentinel is not None:
            self.sentinel.close()
        if self._disagg is not None:
            # first: a decode plane degrades its in-flight shipments to
            # local prefill while the engine thread can still run them
            self._disagg.stop()
        self._stop.set()
        self._wake.set()
        if self._thread is not None:
            self._thread.join(timeout)
            self._thread = None
        # catch cancellations enqueued after the engine thread's final
        # drain but before join returned (the cancel() dead-thread check
        # handles calls arriving later than this)
        # cakelint: skip[affinity] engine thread joined above: inline teardown is single-threaded; the runtime assert checks liveness
        self._drain_cancellations()
        self.tracer.close()
        self.flight.close()
        if self.events is not None:
            self.events.close()
        if self._journal is not None:
            # flush buffered emit batches + fsync: a clean stop's
            # journal is durable (the snapshot handshake may then
            # truncate it — shutdown_save)
            self._journal.close()
        if self._control is not None:
            # published only after the engine thread has exited, so no
            # step op can be ordered after the stop on the wire
            try:
                self._control.publish({"op": "stop"})
            except Exception:  # noqa: BLE001
                log.warning("control: stop publish failed (followers "
                            "will exit on channel close)")

    # -- multi-host -----------------------------------------------------------

    def attach_control(self, control) -> None:
        """Coordinator side of multi-host serving: publish every device
        step through `control` (a serve.control.ControlServer) before
        dispatching it, so every follower process enters the same SPMD
        program. Reference behavior analog: the master streaming work to
        workers (worker.rs:289-303). Must be called before start()."""
        from cake_tpu.models.llama.model import prefill_slot as _builtin
        if self._prefill_slot is _builtin or self.paged:
            raise ValueError(
                "multi-host control requires pipelined step fns (a mesh "
                "spanning processes); the single-device engine (incl. "
                "--kv-pages) has no cross-process computation to "
                "coordinate")
        if self._prefixes:
            raise ValueError(
                "multi-host control cannot be attached after prefix "
                "registrations (registrations are not replayed)")
        self._control = control
        # a --fault-plan with control.publish rules fires inside the
        # channel itself, so the failure shape (publish raises) is the
        # one a dead follower produces
        if self._faults is not None:
            control.faults = self._faults
        self._multihost = True

    def run_follower_loop(self, client,
                          reset_wait_s: float = 120.0,
                          op_timeout_s: Optional[float] = None,
                          liveness=None) -> None:
        """Non-coordinator side: replay the coordinator's op stream.
        Blocks until the coordinator publishes a stop or closes the
        channel. The engine thread is never started here — this process
        only mirrors device steps so the SPMD collectives line up.

        After a failed op this process is out of sync (its donated cache
        may be gone). The symmetric case — the collective failed on every
        process — is recovered by the coordinator's reset op. If no reset
        arrives within reset_wait_s, the failure was follower-local
        (asymmetric); the only safe move is to disconnect, which makes
        the coordinator's next publish raise and fail its requests
        instead of hanging its next collective forever.

        op_timeout_s/liveness: the follower liveness deadline. A
        coordinator that dies BETWEEN ops (kill -9, kernel panic —
        no FIN ever arrives) used to hang this process in recv()
        forever. With op_timeout_s set, each quiet interval re-checks
        `liveness()` (cli wires it to the heartbeat channel: the
        monitor lives in the coordinator process, so a sendall that
        still succeeds proves the peer is up); a quiet interval with
        liveness gone exits with a clear error instead of hanging. An
        idle-but-alive coordinator just keeps the loop waiting."""
        import socket as _socket

        self._multihost = True
        log.info("engine follower: replaying coordinator ops")
        failed = False
        while True:
            try:
                op = client.recv(
                    timeout=reset_wait_s if failed else op_timeout_s)
            except (_socket.timeout, TimeoutError):
                if not failed:
                    if liveness is not None and liveness():
                        continue    # quiet but provably alive: keep on
                    log.error(
                        "engine follower: no op for %.0fs and the "
                        "coordinator shows no liveness; exiting "
                        "instead of hanging the process", op_timeout_s)
                    return
                log.error("engine follower: op failed and no reset came "
                          "within %.0fs; disconnecting", reset_wait_s)
                return
            if op is None or op.get("op") == "stop":
                if op is not None and isinstance(op.get("seq"), int):
                    # count the stop as applied: a drained follower
                    # must report zero lag, not one phantom op
                    self.applied_op_seq = op["seq"]
                log.info("engine follower: coordinator %s",
                         "stopped" if op else "closed the channel")
                return
            if failed and op.get("op") != "reset":
                # a normal op after our failure means the coordinator's
                # twin dispatch SUCCEEDED — our mirrors may have drifted,
                # and executing more ops would silently diverge; bail
                log.error("engine follower: op %r after a local failure "
                          "(no reset) — out of sync; disconnecting",
                          op.get("op"))
                return
            try:
                kind = op["op"]
                if kind == "prefill":
                    self._prefill_device(
                        op["ids"], op["slot"], op["temp"], op["top_p"],
                        op["penalty"], op.get("prime", ()),
                        n_top=op.get("n_top", 0))
                elif kind == "decode":
                    self._decode_device(op["rows"],
                                        n_top=op.get("n_top", 0))
                elif kind == "decode_scan":
                    budget = np.asarray(
                        op.get("budget", [op["n"]] * self.max_slots),
                        np.int32)
                    toks, *_ = self._decode_scan_device(
                        op["rows"], op["n"], op["n_top"], budget=budget)
                    self._finalize_scan_mirrors(op["rows"], op["n"], toks,
                                                budget)
                elif kind == "register_prefix":
                    ids = list(op["ids"])
                    P = len(ids)
                    k, v = self._prefix_kv_device(
                        ids, P, bucket_length(P, self.max_seq_len))
                    with self._rid_lock:
                        self._prefixes[op["pid"]] = (ids, k, v)
                elif kind == "unregister_prefix":
                    with self._rid_lock:
                        self._prefixes.pop(op["pid"], None)
                elif kind == "prefill_prefixed":
                    self._prefixed_prefill_device(
                        op["pid"], op["ids"], op["slot"], op["temp"],
                        op["top_p"], op["penalty"], op.get("prime", ()),
                        n_top=op.get("n_top", 0))
                elif kind == "reset":
                    self._reset_after_error()
                else:
                    log.error("engine follower: unknown op %r", kind)
                failed = False
                if isinstance(op.get("seq"), int):
                    # applied (not merely received): telemetry frames
                    # report this, and lag vs the published seq is the
                    # fleet view's behind-ness signal
                    self.applied_op_seq = op["seq"]
            except Exception:  # noqa: BLE001
                log.exception("follower op failed (awaiting reset)")
                failed = True

    def _publish(self, op: dict) -> None:
        if self._control is not None:
            self._control.publish(op)

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()

    # -- submission -----------------------------------------------------------

    def submit(
        self,
        prompt_ids: Sequence[int],
        *,
        max_new_tokens: int = 100,
        temperature: Optional[float] = None,
        top_p: Optional[float] = None,
        repeat_penalty: Optional[float] = None,
        stream: Optional[Callable[..., None]] = None,
        prime_penalty_tokens: Optional[Sequence[int]] = None,
        want_top_logprobs: bool = False,
        priority: Optional[str] = None,
        idempotency_key: Optional[str] = None,
        replay_tokens: Optional[Sequence[int]] = None,
        trace_id: Optional[str] = None,
        ship_sink: Optional[Callable] = None,
    ) -> RequestHandle:
        """Queue one generation. stream(text_delta, is_final) is called from
        the engine thread as tokens finalize; a callback with attribute
        `wants_count = True` instead gets (text_delta, is_final, n_done)
        where n_done counts the finalized logprob entries up to and
        including this delta. The handle's wait()/text() gives the
        blocking interface."""
        if self._stop.is_set():
            # post-stop submits (e.g. an HTTP handler racing shutdown)
            # must not mutate state under a checkpoint snapshot; typed
            # + retryable so the API can 503 (a stopped engine is a
            # restart away from serving this same request)
            from cake_tpu.serve.errors import EngineResetError
            raise EngineResetError("engine stopped")
        if idempotency_key is not None:
            # BEFORE validation: the key names an EXISTING stream, so a
            # retry attaches regardless of what its (possibly
            # re-rendered, possibly oversized) payload looks like — the
            # original admission already validated the real work. The
            # re-check under the switch lock below closes the race of
            # two concurrent first-submits with one key.
            prev = self._attach_idempotent(idempotency_key, stream)
            if prev is not None:
                return prev
        # validate the class EVERY time (unknown values must 400 at the
        # API); the class only orders admission when the SLO scheduler
        # is on, but it always labels the TTFT histogram
        cls = validate_priority(priority)
        ids = list(prompt_ids)
        if not ids:
            raise ValueError("empty prompt")
        if len(ids) >= self.max_seq_len:
            raise ValueError(
                f"prompt length {len(ids)} exceeds max_seq_len "
                f"{self.max_seq_len}")
        if self.prompt_limit is not None and len(ids) > self.prompt_limit:
            raise ValueError(
                f"prompt length {len(ids)} exceeds this serving mode's "
                f"prompt window {self.prompt_limit}")
        max_new = min(max_new_tokens, self.max_seq_len - len(ids))
        if self.decode_budget is not None:
            # windowed layouts cap generation by the tail capacity, not
            # by max_seq - prompt
            max_new = min(max_new, self.decode_budget)
        with self._rid_lock:
            rid = self._next_rid
            self._next_rid += 1
        d = self.defaults
        eff_temp = temperature if temperature is not None else d.temperature
        eff_top_p = top_p if top_p is not None else d.top_p
        replayed = list(replay_tokens or ())
        if replayed and ids[-len(replayed):] != replayed:
            # the replay coordinate must be a literal suffix of the
            # folded prompt (checkpoint/journal resume constructs it
            # that way); anything else would corrupt SSE event ids
            raise ValueError(
                "replay_tokens must be the folded suffix of prompt_ids")
        req = _Request(
            rid=rid, prompt_ids=ids, max_new_tokens=max_new,
            temperature=eff_temp if eff_temp is not None else 0.0,
            top_p=eff_top_p if eff_top_p is not None else 1.0,
            repeat_penalty=(d.repeat_penalty if repeat_penalty is None
                            else repeat_penalty),
            stream=stream,
            stream_wants_count=bool(getattr(stream, "wants_count", False)),
            submit_t=time.perf_counter(),
            prime_tokens=list(prime_penalty_tokens or ()),
            want_top=want_top_logprobs,
            priority=cls,
            idempotency_key=idempotency_key,
            replayed_tokens=replayed,
            ship_sink=ship_sink,
        )
        # admission critical section: a LIVE config switch
        # (_reconfigure_sync) replaces the pool/pager/scheduler on the
        # engine thread while THIS runs on a handler thread — the lock
        # makes each admission land fully before or fully after a
        # switch (never half-registered across the scheduler swap, and
        # the pool bound below always reads one consistent pool)
        with self._switch_lock:
            if idempotency_key is not None:
                # the race-closing RE-check: two concurrent first
                # submits with one key serialize here — the loser
                # attaches to the winner's admission instead of
                # double-admitting
                prev = self._attach_idempotent(idempotency_key, stream)
                if prev is not None:
                    return prev
            if self._draining and replay_tokens is None:
                # admissions are closed while the drain finishes or
                # snapshots in-flight work; replay resubmits (the
                # recovery path) must still land — they ARE the
                # in-flight work. Typed so the API maps it to 429 +
                # the computed seconds until the drain completes.
                from cake_tpu.serve.errors import DrainingError
                raise DrainingError(self._drain_eta_s())
            if self.paged and (self._pager.pages_for(len(ids) + max_new)
                               > self.cache.n_pages):
                # can NEVER be admitted (need exceeds the whole pool) —
                # fail fast instead of requeueing forever. A shared
                # prefix does not change this bound: the prefix is
                # page-aligned, so prefix pages + suffix pages == the
                # contiguous page count exactly (sharing saves FREE
                # pages at admission, not table-row size)
                raise ValueError(
                    f"request needs "
                    f"{self._pager.pages_for(len(ids) + max_new)} kv "
                    f"pages; the pool has {self.cache.n_pages} total "
                    "(raise --kv-pages or lower max_tokens)")
            if self._shed is not None:
                # AFTER every validation above: an invalid request must
                # get its deterministic 400, never a 429 inviting a
                # retry of something that can never succeed (and must
                # not pollute the shed counters)
                depth = (self.scheduler.depth_ahead(cls)
                         if hasattr(self.scheduler, "depth_ahead")
                         else self.scheduler.queue_depth)
                dec = self._shed.decide(cls, depth)
                if not dec.admit:
                    self.stats.shed += 1
                    _SHED_REQUESTS.labels(cls).inc()
                    if self.events is not None:
                        self.events.publish(
                            "shed", rid=rid, priority=cls,
                            retry_after_s=round(dec.retry_after_s, 3),
                            est_wait_s=(round(dec.est_wait_s, 3)
                                        if dec.est_wait_s is not None
                                        else None))
                    raise ShedError(cls, dec.retry_after_s,
                                    est_wait_s=dec.est_wait_s)
            if self._journal is not None:
                # WRITE-AHEAD for real: the admit record must land
                # before the request becomes visible to the engine
                # thread (registered below) — otherwise an emit batch
                # could flush ahead of its admit and replay would drop
                # the orphaned tokens. A scheduler refusal below
                # compensates with a tombstone.
                self._journal.note_admit(req, self.config_epoch)
            # register BEFORE scheduler.submit: the engine thread may
            # plan the rid immediately, and _do_prefill treats an
            # unknown rid as cancelled
            self._requests[rid] = req
            # trace BEFORE scheduler.submit (prefill_start on an
            # unknown rid would silently drop the span). config_epoch
            # attributes the trace to the engine config that admitted
            # it (a hot switch bumps the epoch, so traces spanning one
            # are distinguishable — cake_tpu/autotune). trace_id is
            # the originating x-cake-trace (front-door router /
            # client): the key the federated timeline correlates this
            # replica-local record under.
            self.tracer.admit(rid, len(ids), max_new, priority=cls,
                              config_epoch=self.config_epoch,
                              trace=trace_id)
            if (self._disagg is not None
                    and self._disagg.role == "decode"
                    and replay_tokens is None and not want_top_logprobs
                    and self._disagg.request_prefill(req)):
                # disaggregated front door: the admission is held OUT
                # of the scheduler while the prefill peer computes its
                # pages — disagg_complete enters it (with the shipment
                # to adopt, or without one after any channel failure).
                # Replays and top-logprob requests stay local: a replay
                # suffix already holds generated tokens, and the
                # shipped first token carries no top-N alternatives.
                # request_prefill == False means the channel is down —
                # fall through to the local path, same as colocated.
                req._disagg_pending = True
            else:
                ok = (self.scheduler.submit(rid, len(ids), max_new,
                                            priority=cls)
                      if self._slo else
                      self.scheduler.submit(rid, len(ids), max_new))
                if not ok:
                    self._requests.pop(rid, None)
                    self.tracer.drop(rid)
                    if self._journal is not None:
                        # the admit was journaled write-ahead; the
                        # refused admission must not replay after a
                        # restart
                        self._journal.note_retire(rid, "cancelled")
                    retry = 1.0
                    if self._shed is not None:
                        retry = self._shed.estimate_retry_after(
                            cls, self.scheduler.queue_depth)
                    raise QueueFullError(retry_after=retry)
            if idempotency_key is not None:
                with self._rid_lock:
                    self._idem_live[idempotency_key] = rid
        self._set_queue_gauges()
        self._wake.set()
        return RequestHandle(req, self.tokenizer, self.config.eos_token_ids)

    # -- disaggregated serving (cake_tpu/kv/transfer.py) -------------------

    def disagg_complete(self, rid: int, shipment) -> None:
        """Decode-plane channel thread: the peer's answer for a
        deferred admission arrived — a reassembled Shipment to adopt,
        or None (peer down / timeout / refused / corrupt), which means
        whole-prompt prefill locally. Either way the request NOW
        enters the scheduler; adoption itself happens on the engine
        thread when _do_prefill/_mixed_admit reach the rid."""
        with self._switch_lock:
            req = self._requests.get(rid)
            if req is None or not req._disagg_pending:
                return   # cancelled / failed while the shipment flew
            req._disagg_pending = False
            if shipment is not None:
                with self._rid_lock:
                    self._adopt_store[rid] = shipment
            ids, max_new = req.prompt_ids, req.max_new_tokens
            ok = (self.scheduler.submit(rid, len(ids), max_new,
                                        priority=req.priority)
                  if self._slo else
                  self.scheduler.submit(rid, len(ids), max_new))
            if not ok:
                # mirror submit's refusal compensation — the deferred
                # admission was already registered/journaled, so the
                # late refusal must finish the handle with the same
                # retryable error a synchronous refusal raises
                self._requests.pop(rid, None)
                with self._rid_lock:
                    self._adopt_store.pop(rid, None)
                self.tracer.drop(rid)
                if self._journal is not None:
                    self._journal.note_retire(rid, "cancelled")
                req.error = QueueFullError(retry_after=1.0)
                req.done.set()
                return
        self._set_queue_gauges()
        self._wake.set()

    # -- durable serving: idempotency, drain, journal seams --------------

    def _attach_idempotent(self, key: str,
                           stream=None) -> Optional[RequestHandle]:
        """A submit whose idempotency key matches a live or finished
        request attaches to THAT stream (safe client retry — across
        reconnects AND restarts, since the journal replay re-registers
        keys). The new stream callback replaces the dead client's;
        tokens the swap races are covered by the reconnect replay
        (api/server.py dedupes by absolute event id). None = no match
        (admit normally)."""
        with self._rid_lock:
            rid = self._idem_live.get(key)
            req = self._requests.get(rid) if rid is not None else None
            if req is None:
                req = self._idem_done.get(key)
            if req is None:
                return None
        if not req.done.is_set() and stream is not None:
            req.stream = stream
            req.stream_wants_count = bool(
                getattr(stream, "wants_count", False))
        h = RequestHandle(req, self.tokenizer, self.config.eos_token_ids)
        h.attached = True
        return h

    def seed_finished_idempotent(self, rec: dict) -> None:
        """Journal replay (serve/journal.recover): a request that
        COMPLETED before the crash but whose client may still retry —
        synthesize its finished state into the idempotency registry so
        the retry attaches to the transcript instead of re-running it.
        Errored/cancelled records are not seeded (a fresh retry is the
        right outcome for those)."""
        key = rec.get("idempotency_key")
        if not key or rec.get("error") \
                or rec.get("status") == "cancelled":
            return
        out = list(rec.get("out_tokens") or ())
        req = _Request(
            rid=int(rec.get("rid") or 0),
            prompt_ids=list(rec.get("prompt_ids") or ()),
            max_new_tokens=int(rec.get("max_new")
                               or rec.get("remaining") or 0),
            temperature=rec.get("temperature", 0.0),
            top_p=rec.get("top_p", 1.0),
            repeat_penalty=rec.get("repeat_penalty", 1.0),
            stream=None,
            priority=rec.get("priority", "standard"),
            idempotency_key=key,
            replayed_tokens=list(rec.get("replayed") or ()),
        )
        req.out_tokens = out
        # the journal stores no logprobs; a replayed transcript serves
        # text/ids only (documented limitation)
        req.out_logprobs = [0.0] * len(out)
        req.out_top = [[] for _ in out]
        req.done.set()
        with self._rid_lock:
            self._idem_done[key] = req
            while len(self._idem_done) > self._idem_done_cap:
                self._idem_done.popitem(last=False)

    def _journal_retire(self, req: _Request, status: str,
                        error: Optional[str] = None) -> None:
        """THE terminal side-channel shared by every retire seam
        (_emit finish, recovered-finish, force-finish, drop, fail-all,
        cancel, requeue-exhausted): write the journal tombstone and
        transition the idempotency registry — a completed keyed
        request stays attachable in the bounded done-ring, a
        failed/cancelled one frees its key so a retry re-runs."""
        if self._journal is not None:
            self._journal.note_retire(req.rid, status, error=error)
        key = req.idempotency_key
        if key is None:
            return
        with self._rid_lock:
            if self._idem_live.get(key) == req.rid:
                del self._idem_live[key]
            if status == "retired":
                self._idem_done[key] = req
                while len(self._idem_done) > self._idem_done_cap:
                    self._idem_done.popitem(last=False)

    def begin_drain(self) -> dict:
        """Close admissions (new submits raise the typed DrainingError
        the API maps to 429 + computed Retry-After) while in-flight
        work keeps decoding. POST /api/v1/drain and the SIGTERM paths
        call this before finishing/snapshotting and exiting clean."""
        if not self._draining:
            log.info("drain: admissions closed (%d in flight)",
                     len(self._requests))
        self._draining = True
        self._wake.set()
        return self.drain_state()

    def _drain_eta_s(self) -> float:
        """Computed seconds until the drain completes: remaining
        budgeted tokens over the measured decode rate (capped; a 1s
        floor matches the API's Retry-After ceil)."""
        remaining = sum(max(0, r.max_new_tokens - len(r.out_tokens))
                        for r in list(self._requests.values())
                        if not r.done.is_set())
        if remaining == 0:
            return 1.0
        rate = self.stats.decode_tokens_per_s
        if rate > 0:
            return min(600.0, max(1.0, remaining / rate))
        return min(600.0, max(1.0, remaining / 8.0))

    def drain_state(self) -> dict:
        """/api/v1/health `draining` block + the drain response."""
        pending = sum(1 for r in list(self._requests.values())
                      if not r.done.is_set())
        out = {"draining": self._draining,
               "pending_requests": pending,
               "queue_depth": self.queue_depth}
        if self._draining:
            out["eta_s"] = round(self._drain_eta_s(), 3)
        return out

    def register_prefix(self, prefix_ids: Sequence[int]) -> int:
        """Precompute and cache the KV of a shared prompt head (e.g. the
        rendered system prompt). Later submits whose prompt starts with
        these ids prefill only the suffix — prefill FLOPs and TTFT drop
        proportionally. Returns a prefix id (for unregister_prefix).

        HBM cost per prefix: L*P*KV*hd*2 entries in cache dtype (an
        8B-model 1k-token prefix is ~130 MiB at bf16; stage-sharded on a
        pipelined engine). Unavailable on ring (sliding-window) caches
        (see _prefix_capable). Multi-host: the coordinator publishes a
        register_prefix op and every follower computes the same prefix KV
        (the registration prefill is itself a cross-process collective,
        so it runs on the engine thread — wire position == dispatch
        position); followers reject direct registrations.
        """
        if self._multihost and self._control is None:
            raise ValueError(
                "followers mirror the coordinator's prefix registry; "
                "register prefixes on the coordinator process")
        if not self._prefix_capable:
            # name the ACTUAL refusal per engine flavor — the paged
            # engine serves prefixes now (page-granular sharing), so a
            # one-size message would blame the wrong subsystem
            if not self._family.moves("register_prefix"):
                reason = self._family.refuses["register_prefix"]
            elif self.ring:
                reason = ("ring sliding-window caches own their layout "
                          "(a prefix install writes dense positions the "
                          "ring would misplace)")
            else:
                reason = ("these custom step fns provide no "
                          "chunked-prefill variant to window the suffix "
                          "at the prefix boundary")
            raise ValueError(f"prefix caching is unavailable here: "
                             f"{reason}")
        ids = list(prefix_ids)
        if not ids:
            raise ValueError("empty prefix")
        if len(ids) >= self.max_seq_len - 1:
            raise ValueError(
                f"prefix length {len(ids)} leaves no room for a suffix "
                f"(max_seq_len {self.max_seq_len})")
        if self.paged:
            with self._switch_lock:
                # a live reconfigure swaps the pager wholesale; the
                # switch lock pins one consistent page size for this
                # validation (same discipline as submit's pool bound)
                P = self._pager.page_size
            if len(ids) < P:
                raise ValueError(
                    f"paged prefix sharing is page-granular: the prefix "
                    f"({len(ids)} tokens) is shorter than one kv page "
                    f"({P} tokens), so there is nothing to share "
                    "(lower --kv-page-size or skip registration)")
            # pool pages + the table are single-writer state: route
            # through the engine thread when it is running (auto-prefix
            # registrations arrive on HTTP handler threads)
            if self._thread is not None and self._thread.is_alive():
                return self._run_on_engine_thread(
                    lambda: self._register_prefix_paged(ids))
            # cakelint: skip[affinity] pre-start direct drive: no engine thread exists to race; the runtime assert enforces this
            return self._register_prefix_paged(ids)
        if self._control is not None:
            return self._run_on_engine_thread(
                lambda: self._register_prefix_sync(ids))
        return self._register_prefix_sync(ids)

    def _register_prefix_sync(self, ids: List[int]) -> int:
        """Allocate a pid, publish (multi-host), compute the prefix KV on
        device, store. Coordinator-side; followers mirror via the
        register_prefix op handler."""
        P = len(ids)
        bucket = bucket_length(P, self.max_seq_len)
        with self._rid_lock:
            pid = self._next_prefix_id
            self._next_prefix_id += 1
        self._publish({"op": "register_prefix", "ids": ids, "pid": pid})
        k, v = self._prefix_kv_device(ids, P, bucket)
        with self._rid_lock:
            self._prefixes[pid] = (ids, k, v)
        log.info("registered prefix %d: %d tokens", pid, P)
        return pid

    @engine_thread_only
    def _register_prefix_paged(self, ids: List[int]) -> int:
        """Paged registration: round the prefix DOWN to a page boundary
        (remainder ids join every request's suffix — no copy-on-write of
        a partial last page), prefill it ONCE into dedicated pool pages,
        and record the page list. Matching admissions map those pages
        read-only into their table rows (_alloc_slot_pages) — a 1k-token
        system prompt costs ceil(1k/page) pool pages TOTAL instead of
        per slot. Runs on the engine thread when the engine is live (the
        pool + table are single-writer state)."""
        P = self._pager.page_size
        aligned = (len(ids) // P) * P
        p_ids = ids[:aligned]
        n_pp = aligned // P
        pages = self._pager.alloc(aligned)
        if pages is None:
            raise ValueError(
                f"kv page pool cannot hold the prefix: needs {n_pp} "
                f"pages, {self._pager.free_pages} free (raise "
                "--kv-pages, or register before taking load)")
        with self._rid_lock:
            pid = self._next_prefix_id
            self._next_prefix_id += 1
        self._prefix_last_hit[pid] = time.monotonic()
        row = np.full(self.cache.max_pages, -1, np.int64)
        row[:n_pp] = pages
        try:
            fargs = (self.params, jnp.asarray([p_ids], jnp.int32),
                     jnp.asarray(row, jnp.int32), self.cache, self.rope,
                     self.config)
            js = self._obs_jit("prefill_prefix_pages", (aligned,),
                               self._prefix_pages_step, fargs)
            self.cache = self._prefix_pages_step(*fargs)
        except Exception:
            self._pager.release(pages)
            raise
        with self._rid_lock:
            self._prefixes[pid] = (p_ids, pages, None)
        log.info("registered paged prefix %d: %d tokens in %d shared "
                 "pages (%d trailing tokens fall to each suffix)",
                 pid, aligned, n_pp, len(ids) - aligned)
        return pid

    def _prefix_kv_device(self, ids: List[int], P: int, bucket: int):
        """Device computation of a prefix's KV (identical on every
        process: a multi-host follower replays this as one collective)."""
        padded = ids + [0] * (bucket - P)
        if self._prefill_slot is prefill_slot:
            tmp = KVCache.create(self.config, 1, bucket,
                                 dtype=self._cache_dtype)
            from cake_tpu.models.llama.model import prefill
            _, tmp = prefill(self.params,
                             jnp.asarray([padded], jnp.int32),
                             jnp.asarray([P], jnp.int32),
                             tmp, self.rope, self.config)
        else:
            # pipelined path: prefill slot 0 of a one-slot TEMP cache
            # with the serving cache's sharding — the prefix k/v stay
            # stage-sharded, matching the install target
            tmp = self._sharded_like_cache(1, bucket)
            _, tmp = self._prefill_slot(
                self.params, jnp.asarray([padded], jnp.int32),
                jnp.asarray([P], jnp.int32), jnp.int32(0), tmp,
                self.rope, self.config)
        k = jax.lax.slice_in_dim(tmp.k, 0, P, axis=2)
        v = jax.lax.slice_in_dim(tmp.v, 0, P, axis=2)
        return k, v

    def _run_on_engine_thread(self, fn, timeout: float = 300.0):
        """Execute fn on the engine thread between iterations and return
        its result. Multi-host prefix ops MUST run there: they dispatch
        cross-process collectives, and only the engine thread's program
        order matches the control channel's op order (a handler-thread
        dispatch could interleave with a step op differently on the
        coordinator than on a follower, wedging the mesh)."""
        if self._thread is None or not self._thread.is_alive():
            raise RuntimeError(
                "engine not running: multi-host prefix operations "
                "execute on the engine thread (start() first)")
        box: dict = {}
        ev = threading.Event()
        with self._rid_lock:
            self._cmd_q.append((fn, box, ev))
        self._wake.set()
        if not ev.wait(timeout):
            raise TimeoutError("engine thread did not run the command "
                               f"within {timeout:.0f}s")
        if "error" in box:
            raise box["error"]
        return box["result"]

    @engine_thread_only
    def _drain_commands(self) -> None:
        with self._rid_lock:
            cmds, self._cmd_q = self._cmd_q, []
        for fn, box, ev in cmds:
            try:
                box["result"] = fn()
            except Exception as e:  # noqa: BLE001
                box["error"] = e
            finally:
                ev.set()

    def _fail_pending_commands(self) -> None:
        """Engine exit: release command waiters instead of letting them
        time out against a dead thread."""
        with self._rid_lock:
            cmds, self._cmd_q = self._cmd_q, []
        for _fn, box, ev in cmds:
            box["error"] = RuntimeError("engine stopped")
            ev.set()

    def _sharded_like_cache(self, slots: int, length: int) -> KVCache:
        """Zeroed [L, slots, length] cache with the serving cache's
        sharding (stage/tp axes preserved, batch/seq unsharded dims
        free to differ)."""
        make = jax.jit(
            lambda: KVCache.create(self.config, slots, length,
                                   dtype=self._cache_dtype),
            out_shardings=self._cache_shardings)
        return make()

    def unregister_prefix(self, prefix_id: int) -> None:
        if (self._control is not None and self._thread is not None
                and self._thread.is_alive()):
            # engine-thread ordering guarantees no later prefill_prefixed
            # op on the wire references the dropped pid (matching happens
            # on the same thread, after this pop)
            def job():
                self._publish({"op": "unregister_prefix",
                               "pid": prefix_id})
                with self._rid_lock:
                    self._prefixes.pop(prefix_id, None)
            self._run_on_engine_thread(job)
            return
        if self.paged and (self._thread is not None
                           and self._thread.is_alive()):
            # the registry's page references drop on the engine thread:
            # slots mid-decode on those pages hold their own refs, so
            # the pages outlive the registration until the last request
            # using them retires
            self._run_on_engine_thread(
                lambda: self._unregister_paged_sync(prefix_id))
            return
        if self.paged:
            # cakelint: skip[affinity] reached only with the engine thread not running (checked above); runtime assert backstops
            self._unregister_paged_sync(prefix_id)
            return
        with self._rid_lock:
            self._prefixes.pop(prefix_id, None)

    @engine_thread_only
    def _unregister_paged_sync(self, prefix_id: int) -> None:
        with self._rid_lock:
            entry = self._prefixes.pop(prefix_id, None)
        self._prefix_last_hit.pop(prefix_id, None)
        if entry is not None:
            if entry[1] is not None:
                self._pager.release(entry[1])
            elif self._host_tier is not None:
                # spilled registration: the pages live in the host
                # tier, not the pool — drop the host copy instead
                self._host_tier.drop(("prefix", prefix_id))

    def _match_prefix(self, ids: List[int]):
        """Longest registered prefix that is a proper head of `ids`:
        (pid, p_ids, k, v) or None."""
        best = None
        with self._rid_lock:
            entries = list(self._prefixes.items())
        for pid, (p_ids, k, v) in entries:
            P = len(p_ids)
            if P < len(ids) and ids[:P] == p_ids:
                if best is None or P > len(best[1]):
                    best = (pid, p_ids, k, v)
        return best

    def chat(self, messages: Sequence[Message], **kw) -> RequestHandle:
        """Render a chat history through the Llama-3 template and submit.

        With auto_prefix_system on, the system message's rendered head is
        KV-cached once per distinct system prompt, so every conversation
        sharing it prefills only its own turns."""
        hist = History(self.config.chat_template)
        for m in messages:
            hist.add_message(m)
        if (self._auto_prefix and messages
                and messages[0].role.value == "system"
                and self._prefix_capable
                and (not self._multihost or self._control is not None)
                and hist.template == "llama3"):
            # the head builder below renders the llama3 system block;
            # other templates (mistral merges system into the first user
            # turn) have no standalone shared head
            self._auto_register_system(messages[0])
        return self.submit(encode_text(self.tokenizer, hist.render()), **kw)

    def _auto_register_system(self, system_msg: Message) -> None:
        from cake_tpu.models.chat import BEGIN_OF_TEXT
        head = BEGIN_OF_TEXT + History.encode_message(system_msg)
        evict = None
        with self._rid_lock:
            if head in self._auto_pids:
                pid = self._auto_pids[head]
                if pid is None or pid < 0 or pid in self._prefixes:
                    return   # in-flight, negative-cached, or live
                # stale head->pid: the registry was cleared underneath
                # a completed registration (paged _reset_after_error
                # racing a handler-thread registration) — drop the
                # entry and re-register, or this head would silently
                # serve whole-prompt prefills forever
                del self._auto_pids[head]
            if len(self._auto_pids) >= self._max_auto:
                # evict the oldest COMPLETED registration; in-flight
                # reservations (None) are skipped
                for k, pid in list(self._auto_pids.items()):
                    if pid is not None:
                        del self._auto_pids[k]
                        evict = pid
                        break
                else:
                    return    # registry full of in-flight reservations
            self._auto_pids[head] = None   # reserve before the prefill
        if evict is not None and evict >= 0:
            # through unregister_prefix, OUTSIDE the lock: under
            # multi-host it publishes the eviction to followers (a direct
            # pop would leak the prefix KV in every follower's mirrored
            # registry) and routes through the engine thread, which may
            # itself need _rid_lock
            try:
                self.unregister_prefix(evict)
            except Exception:  # noqa: BLE001
                log.exception("auto-prefix eviction failed")
        try:
            ids = encode_text(self.tokenizer, head)
            min_len = 8
            if self.paged:
                # page-granular sharing: a head shorter than one page
                # has nothing to share (register_prefix would refuse)
                with self._switch_lock:
                    min_len = max(min_len, self._pager.page_size)
            if len(ids) < min_len or len(ids) >= self.max_seq_len - 1:
                # unqualifying head: keep a negative sentinel so the
                # membership check short-circuits every later request
                # with the same system prompt
                with self._rid_lock:
                    self._auto_pids[head] = -1
                return
            pid = self.register_prefix(ids)
        except Exception:
            # cache warming must never fail the request — drop the
            # reservation and let the normal whole-prompt prefill serve it
            log.exception("auto prefix registration failed; serving "
                          "without prefix cache")
            with self._rid_lock:
                self._auto_pids.pop(head, None)
        else:
            with self._rid_lock:
                self._auto_pids[head] = pid

    def cancel(self, handle: RequestHandle) -> None:
        """Abandon a request (e.g. the streaming client disconnected):
        its slot frees for the next queued request instead of decoding to
        max_new_tokens for nobody. Safe from any thread; the engine
        thread performs the actual teardown — unless it has already
        exited (shutdown window), in which case teardown runs inline so
        the request can neither hang wait() nor be checkpointed as live."""
        with self._rid_lock:
            self._cancel_q.append(handle._req.rid)
        self._wake.set()
        if self._stop.is_set() and (self._thread is None
                                    or not self._thread.is_alive()):
            # cakelint: skip[affinity] shutdown window: the engine thread has exited (checked above); runtime assert backstops
            self._drain_cancellations()

    def _host_attention(self) -> Optional[str]:
        """What on the host side needs the run loop back, as a chain
        break (obs/steps.BREAKS), the first that holds: stop, an
        admission waiting, a cancellation, a command. None: nothing."""
        if self._stop.is_set():
            return "stop"
        if self.scheduler.queue_depth > 0:
            return "queue"
        if self._cancel_pending():
            return "cancel"
        if self._commands_pending():
            return "command"
        return None

    def _host_attention_pending(self) -> bool:
        """Something on the host side needs the run loop back."""
        return self._host_attention() is not None

    def _drive_burst(self, dispatch, complete, chain_break,
                     first_unconditional: bool = False) -> None:
        """THE double-buffered dispatch/fetch driver, shared by the
        decode burst and the mixed burst: dispatch k+1 (chained
        from k's on-device state, zero host round-trips between
        dispatches) BEFORE completing k, so k's device-to-host fetch
        overlaps k+1's device compute.

        dispatch(state) -> (devs, state'): device dispatch, no fetch.
        complete(devs): fetch + emit one dispatch's results.
        chain_break(n_inflight) -> the cause (obs/steps.BREAKS) for
        which the burst may not dispatch ahead, or None: its own
        budget/window gating (asked after the shared host-attention
        gate); n_inflight = dispatched-but-unfetched count, for
        projecting the device frontier past the stale host mirrors.
        The first cause that stops a chain goes to the flight recorder
        once its last step has been fetched (StepTelemetry.chain_broke:
        the next record that is not chained carries it).
        A completed dispatch's device outputs, and at the end the
        stretch's last carry, die under the `release` span: the runtime
        gives the interpreter lock away while it frees them, and the
        thread then waits for it behind every handler thread the emit
        woke (1-3 ms a step at 32 streams: my chip run, PR 50; the
        carry a dispatch consumed dies at once, 0.07 ms).
        first_unconditional: guarantee one dispatch per call even when
        the gates say stop — a caller whose planning loop has no other
        progress path would otherwise spin forever (the spec burst with
        full slots and a waiting queue)."""
        inflight: list = []
        state = None
        first = first_unconditional
        broke = None
        while True:
            cause = None
            if not first:
                with self.flight.span("gate"):
                    cause = (self._host_attention()
                             or chain_break(len(inflight)))
            first = False
            if cause is None:
                devs, state = dispatch(state)
                inflight.append(devs)
                # (`inflight` holds them now: a name left here would keep
                # the stretch's last outputs alive past their span)
                del devs
            elif inflight and broke is None:
                broke = cause
            if not inflight:
                with self.flight.span("release"):
                    state = None
                break
            if cause is not None or len(inflight) >= 2:
                done = inflight.pop(0)
                complete(done)
                with self.flight.span("release"):
                    del done
                if broke is not None and not inflight:
                    self.flight.chain_broke(broke)
                    broke = None

    def _cancel_pending(self) -> bool:
        with self._rid_lock:
            return bool(self._cancel_q)

    def _commands_pending(self) -> bool:
        with self._rid_lock:
            return bool(self._cmd_q)

    @engine_thread_only
    def _drain_cancellations(self) -> None:
        with self._rid_lock:
            rids, self._cancel_q = self._cancel_q, []
        for rid in rids:
            req = self._requests.pop(rid, None)
            if req is None:
                continue
            self.scheduler.cancel(rid)
            with self._rid_lock:
                # a shipment staged for a cancelled admission must not
                # outlive it in the adoption store
                self._adopt_store.pop(rid, None)
            if self._host_tier is not None:
                # a victim cancelled while parked leaves its spilled
                # pages orphaned in the LRU — drop them now
                self._host_tier.drop(("victim", rid))
            if req.slot >= 0 and self._slot_req[req.slot] is req:
                self._slot_req[req.slot] = None
                self._release_slot_pages(req.slot)
            req.finish_t = time.perf_counter()
            self._journal_retire(req, "cancelled")
            self.tracer.finish(rid, "cancelled",
                               output_tokens=len(req.out_tokens))
            req.done.set()

    @property
    def queue_depth(self) -> int:
        return self.scheduler.queue_depth

    @property
    def active(self) -> int:
        return self.scheduler.active

    # -- engine loop ----------------------------------------------------------

    def _run(self) -> None:
        try:
            self._run_loop()
        finally:
            # cancellations enqueued in the stop window must still tear
            # down (an undrained handle would block wait() forever and be
            # replayed as live by a checkpoint snapshot); command waiters
            # get an error instead of a timeout
            self._drain_cancellations()
            self._fail_pending_commands()

    def _run_loop(self) -> None:
        # step-phase spans (obs/steps.StepTelemetry.span; vocabulary in
        # PERF.md §3): host seconds by phase in each step record, and
        # cake/<phase> annotations in a profiler capture
        span = self.flight.span
        while not self._stop.is_set():
            with span("admin"):
                self._drain_cancellations()
                self._drain_commands()
                if self._autotuner is not None:
                    # between iterations only — a switch folds every
                    # slot, so it must never land mid-wave (the
                    # preemption invariant); the tick itself is a no-op
                    # off-interval
                    self._autotune_tick()
                if self._slo and self._preemption:
                    # between iterations only: no device work is in
                    # flight, so a reclaimed slot cannot be mid-decode
                    # through a just-released page-table row
                    self._maybe_preempt()
            with span("schedule"):
                with self.flight.part("plan"):
                    prefill_plan, decode_plan = self.scheduler.plan()
                # decode-resident slots THIS iteration: the candidate
                # set for _spill_resident_stream — plan()'s decode rows
                # only, never same-wave admissions (their prefill may
                # be in flight when an admission later in the wave
                # spills)
                self._resident_parked = False
                self._cur_decode = {s: r for r, s in decode_plan}
            if self._slo:
                with span("admin"):
                    self._set_queue_gauges()
            if not prefill_plan and not decode_plan:
                with span("wait"):
                    self._wake.wait(timeout=0.05)
                self._wake.clear()
                continue
            if getattr(self, "_page_starved", False):
                # a page-starved prefill was requeued last iteration; if
                # nothing can retire pages this round (no decode work),
                # back off instead of spin-planning the same admission
                self._page_starved = False
                if not decode_plan:
                    with span("wait"):
                        self._wake.wait(timeout=0.02)
                    self._wake.clear()
            try:
                if self._faults is not None:
                    # chaos plane, top-of-iteration site (step= triggers
                    # key off the engine step counter)
                    self._faults.check("engine.step",
                                       step=self.stats.steps)
                if self.paged:
                    # admissions, prefill windows and decode rows all
                    # go through the one mixed iteration
                    self._do_mixed(prefill_plan, decode_plan)
                else:
                    if prefill_plan and not self._multihost:
                        self._do_prefill_batch(prefill_plan)
                    else:
                        for rid, slot in prefill_plan:
                            self._do_prefill(rid, slot)
                    if decode_plan:
                        self._decode_rows(decode_plan,
                                          chain=not prefill_plan)
                if getattr(self, "_fail_recs", None) is not None:
                    # a successful iteration (real device work incl.
                    # collectives) proves the mesh recovered: the
                    # earlier failure was genuinely transient, so its
                    # capture must not resurrect already-errored
                    # requests in a later fatal's snapshot
                    self._fail_recs = None
                if self._consec_resets:
                    # a successful iteration ends the reset episode:
                    # the next failure backs off from scratch
                    self._consec_resets = 0
                # the iteration's dispatches all landed: a failure in
                # the NEXT iteration before any dispatch (engine.step
                # site, planning/admission code) must implicate nobody
                # — not this iteration's requests
                self._implicated = ()
                if self._journal is not None:
                    # one emit record per request touched this
                    # iteration (+ the batch-mode fsync barrier), then
                    # the size-triggered compaction check — both here,
                    # between iterations, where the registry is stable
                    with span("admin"):
                        self._journal.flush()
                        self._journal.maybe_compact(self)
            except Exception as e:  # noqa: BLE001
                log.exception("engine iteration failed")
                # capture the request records FIRST (cheap, pure
                # Python — the reset publish below can block for
                # minutes against a network-partitioned follower's
                # full TCP buffer), and only if the failure proves
                # fatal write them as the pre-fail snapshot. Transient
                # reset-and-continue errors write nothing: a stale
                # snapshot would resurrect long-errored requests after
                # a later unclean exit.
                recs = None
                if getattr(self, "snapshot_path", None):
                    from cake_tpu.serve import checkpoint
                    recs = checkpoint.snapshot_requests(self)
                    # stash for the heartbeat monitor: a dead follower
                    # often looks transient HERE (the reset publish can
                    # land in the dead peer's TCP buffer) and only the
                    # heartbeat loss seconds later proves it fatal — by
                    # then the registry is empty, so the monitor's
                    # snapshot falls back to this capture
                    self._fail_recs = (time.monotonic(), recs)
                if not self._continue_after_failure(e, recs):
                    return

    # -- crash recovery (cake_tpu/faults + the fail-everything fix) ------

    def _note_reset(self) -> bool:
        """Record one reset in the storm window; True = the breaker
        trips (too many resets in storm_window_s: the fault is not
        transient, stop cleanly instead of thrashing)."""
        cfg = self._recovery_cfg
        now = time.monotonic()
        self._reset_times.append(now)
        cut = now - cfg.storm_window_s
        while self._reset_times and self._reset_times[0] < cut:
            self._reset_times.pop(0)
        return (self._recover
                and len(self._reset_times) >= cfg.storm_resets)

    def _continue_after_failure(self, e: Exception, recs) -> bool:
        """Post-failure policy: transparent recovery (reset + resubmit
        the in-flight requests), or the legacy fail-everything path
        (recovery off / flavor without the fold), or — on a reset
        storm — breaker-open snapshot + clean stop. Returns False when
        the engine must stop."""
        from cake_tpu.serve.errors import as_engine_error
        storm = self._note_reset()
        if self._recover and not storm and not self._stop.is_set():
            return self._attempt_recovery(e, recs)
        err = as_engine_error(e)
        if storm:
            log.error("reset storm: %d resets within %.0fs — breaker "
                      "open; snapshotting in-flight requests and "
                      "stopping cleanly", len(self._reset_times),
                      self._recovery_cfg.storm_window_s)
            self._breaker_tripped = True
            _RECOVERIES.labels(outcome="storm_breaker").inc()
            self.stats.errors += 1
            self.stats.last_error = f"{type(e).__name__}: {e}"
            return self._stop_with_snapshot(recs, err,
                                            trigger="breaker_stop")
        # legacy fail-everything: release the waiters FIRST (the reset
        # publish can block for minutes against a network-partitioned
        # follower's full TCP buffer), then prove the mesh is still
        # drivable, then rebuild
        self._fail_all(err)
        fatal = False
        try:
            self._publish({"op": "reset"})
        except Exception:  # noqa: BLE001
            # followers unreachable: the SPMD mesh is no longer fully
            # driven — stop serving instead of hanging the next
            # collective
            log.exception("control publish failed; stopping")
            fatal = True
        if fatal:
            return self._stop_with_snapshot(recs,
                                            trigger="control_lost")
        try:
            self._reset_after_error()
        except Exception:  # noqa: BLE001
            # the rebuild itself failed (OOM rebuilding the cache, a
            # dead device): the engine cannot serve again — snapshot
            # what the first failure captured and stop CLEANLY,
            # instead of the raise silently killing the thread with no
            # checkpoint and no metric (the API would 200 /health
            # while every request hangs in the queue forever)
            log.exception("post-error engine reset failed; "
                          "stopping the engine")
            _RESET_FAILURES.inc()
            self.stats.errors += 1
            self.stats.last_error = "reset failed"
            return self._stop_with_snapshot(recs,
                                            trigger="reset_failed")
        self.stats.errors += 1
        self.stats.last_error = f"{type(e).__name__}: {e}"
        return True

    def _stop_with_snapshot(self, recs,
                            err: Optional[Exception] = None,
                            trigger: str = "engine_stop") -> bool:
        """The unrecoverable-failure tail shared by every stop branch:
        fail any still-waiting clients FIRST (omitted when the caller
        already released them), persist the pre-fail capture, dump the
        black-box postmortem bundle (--postmortem-dir; `trigger` names
        the terminal cause), stop the engine thread. Always returns
        False — the _continue_after_failure 'engine must stop'
        contract — so callers can
        `return self._stop_with_snapshot(...)`."""
        if err is not None:
            self._fail_all(err)
        # best-effort stop op: a breaker/reset-failed stop leaves this
        # PROCESS alive (the API keeps serving 503s, heartbeats keep
        # answering), so followers would otherwise wait forever on a
        # healthy channel that carries no more ops — their liveness
        # deadline cannot see an engine-only death. Safe to publish
        # here: this runs on the engine thread just before its loop
        # exits, so no step op can follow it on the wire.
        try:
            self._publish({"op": "stop"})
        except Exception:  # noqa: BLE001
            log.warning("control: stop publish failed (followers will "
                        "exit on channel close)")
        with self._ckpt_lock:
            self._snapshot_before_fail(requests=recs)
        if self._postmortem is not None:
            # terminal: always leaves a bundle, even right after an
            # interval-bounded poison dump
            self._postmortem.dump(
                trigger, engine=self,
                reason=str(err) if err is not None
                else self.stats.last_error, force=True)
        self._stop.set()
        return False

    def _attempt_recovery(self, e: Exception, recs) -> bool:
        """The fail-everything replacement: implicate the failing
        dispatch's requests, publish the reset (multi-host followers
        replay it so the SPMD programs line up), back off if resets
        are consecutive, rebuild device state, then RESUBMIT every
        surviving request through the checkpoint fold-tokens-into-
        prompt path — greedy streams complete token-identical across
        the crash. Returns False when the engine must stop."""
        from cake_tpu.serve.errors import as_engine_error
        t0 = time.perf_counter()
        implicated = [rid for rid, _slot in self._implicated]
        self._implicated = ()
        for rid in implicated:
            req = self._requests.get(rid)
            if req is not None and not req.done.is_set():
                req.crash_count += 1
        try:
            self._publish({"op": "reset"})
        except Exception:  # noqa: BLE001
            log.exception("control publish failed; stopping")
            return self._stop_with_snapshot(recs, as_engine_error(e),
                                            trigger="control_lost")
        # exponential backoff between CONSECUTIVE resets (the first is
        # immediate): a persistent fault must not spin the engine
        # thread through rebuild loops at full speed. Interruptible —
        # a stop() during the wait still tears down promptly.
        cfg = self._recovery_cfg
        self._consec_resets += 1
        if self._consec_resets > 1:
            delay = min(cfg.backoff_cap_s,
                        cfg.backoff_base_s
                        * (2.0 ** (self._consec_resets - 2)))
            log.warning("recovery: consecutive reset #%d, backing off "
                        "%.2fs", self._consec_resets, delay)
            if self._stop.wait(delay):
                self._fail_all(as_engine_error(e))
                return False
        try:
            self._reset_after_error()
        except Exception:  # noqa: BLE001
            log.exception("post-error engine reset failed; "
                          "stopping the engine")
            _RESET_FAILURES.inc()
            _RECOVERIES.labels(outcome="reset_failed").inc()
            self.stats.errors += 1
            self.stats.last_error = "reset failed"
            return self._stop_with_snapshot(recs, as_engine_error(e),
                                            trigger="reset_failed")
        n_rec, n_poison = self._resubmit_after_reset(e)
        self.stats.errors += 1
        self.stats.last_error = f"{type(e).__name__}: {e}"
        self.stats.recoveries += 1
        dt = time.perf_counter() - t0
        _RECOVERY_SECONDS.observe(dt)
        if len(self.recovery_seconds) < 512:
            self.recovery_seconds.append(dt)
        _RECOVERIES.labels(outcome="recovered").inc()
        log.warning("recovered from step failure (%s: %s): %d "
                    "request(s) resubmitted, %d quarantined, %.3fs",
                    type(e).__name__, e, n_rec, n_poison, dt)
        self._wake.set()
        return True

    def _resubmit_after_reset(self, cause: Exception):
        """Rebuild the request-side bookkeeping after a reset:
        quarantine poison requests (implicated in implication_budget
        consecutive failed steps), requeue everyone else with their
        generated tokens folded into the prompt — priority class,
        seniority (SLO requeue) and preempt budget all survive because
        the SAME _Request object is resubmitted. Engine thread only.
        Returns (resubmitted, quarantined) counts."""
        from cake_tpu.serve.errors import (
            PoisonRequestError, as_engine_error,
        )
        cfg = self._recovery_cfg
        cause_s = f"{type(cause).__name__}: {cause}"
        # every slot mapping died with the rebuilt cache (the paged
        # reset already rebuilt pager/table/pending; dense slots are
        # only this list)
        self._slot_req = [None] * self.max_slots
        self._page_blocked_rid = None
        self._pending_page_preempt = None
        self._cur_decode = {}
        if not self.paged:
            self._mixed_pending.clear()
        n_rec = n_poison = 0
        for rid, req in sorted(self._requests.items()):
            if req.done.is_set():
                continue
            req.slot = -1
            req._kv_restored = False
            if req.crash_count >= cfg.implication_budget:
                self._drop_request(
                    req, PoisonRequestError(rid, req.crash_count,
                                            cause_s),
                    poison_reason="implicated")
                n_poison += 1
                continue
            remaining = req.max_new_tokens - len(req.out_tokens)
            if remaining <= 0:
                # was retiring in the failed step — it already holds
                # every token it asked for; finish it normally
                self._finish_recovered(req)
                n_rec += 1
                continue
            n_tok = len(req.prompt_ids) + len(req.out_tokens)
            if self._slo:
                # requeue preserves the original enqueue time
                # (seniority) and the preemption count; False just
                # means the request was still QUEUED — nothing to do
                self.scheduler.requeue(rid, n_tok, remaining)
                ok = True
            else:
                # FIFO scheduler has no requeue: cancel + resubmit in
                # rid order restores the original arrival order
                self.scheduler.cancel(rid)
                ok = self.scheduler.submit(rid, n_tok, remaining)
            if not ok:
                self._drop_request(req, as_engine_error(cause),
                                   poison_reason="resubmit_failed")
                n_poison += 1
                continue
            self.tracer.span(rid, "crash_recovered",
                             generated=len(req.out_tokens),
                             crashes=req.crash_count)
            if self.events is not None:
                self.events.publish("recovered", rid=rid,
                                    generated=len(req.out_tokens),
                                    crashes=req.crash_count)
            _RECOVERED_REQUESTS.inc()
            self.stats.requests_recovered += 1
            n_rec += 1
        return n_rec, n_poison

    def _drop_request(self, req: _Request, err: Exception,
                      poison_reason: Optional[str] = None) -> None:
        """Fail ONE request with a typed error during recovery
        (quarantine / resubmit failure) — the per-request sibling of
        _fail_all's teardown. Engine thread only; slots were already
        cleared by the reset."""
        req.error = err
        self.scheduler.cancel(req.rid)
        if self._host_tier is not None:
            self._host_tier.drop(("victim", req.rid))
        self._requests.pop(req.rid, None)
        self._journal_retire(req, "error", error=str(err))
        if poison_reason is not None:
            self.stats.poisoned += 1
            _POISON_REQUESTS.labels(reason=poison_reason).inc()
            if self.events is not None:
                self.events.publish("poisoned", rid=req.rid,
                                    reason=poison_reason,
                                    crashes=req.crash_count)
            log.error("quarantined rid=%d as poison (%s): %s",
                      req.rid, poison_reason, err)
            if self._postmortem is not None:
                # interval-bounded (not forced): a multi-request
                # quarantine cascade leaves ONE bundle, not one per rid
                self._postmortem.dump(
                    "poison", engine=self,
                    reason=f"rid={req.rid} {poison_reason}: {err}")
        self.tracer.finish(req.rid, "error", error=str(err),
                           output_tokens=len(req.out_tokens))
        req.done.set()

    def _finish_recovered(self, req: _Request) -> None:
        """Retire a request whose budget was already exhausted when
        the step failed: it has every token it asked for — deliver the
        final delta instead of resubmitting a zero-budget prefill."""
        if req.stream is not None:
            self._stream_out(req, self._incremental_text(req, final=True),
                             True)
        req.finish_t = time.perf_counter()
        self.scheduler.cancel(req.rid)
        self._requests.pop(req.rid, None)
        self.stats.requests_completed += 1
        if self._shed is not None:
            # a retirement like any other: the shed controller's
            # measured service rate must count it, or post-recovery
            # Retry-After estimates inflate
            self._shed.observe_retire()
        self._journal_retire(req, "retired")
        self.tracer.finish(req.rid, "retired",
                           output_tokens=len(req.out_tokens))
        req.done.set()

    def recovery_state(self) -> dict:
        """Recovery/breaker introspection for /api/v1/health."""
        cfg = self._recovery_cfg
        out = {
            "enabled": self._recover,
            "recoveries": self.stats.recoveries,
            "requests_recovered": self.stats.requests_recovered,
            "poisoned": self.stats.poisoned,
            "consecutive_resets": self._consec_resets,
            "breaker": {
                "tripped": self._breaker_tripped,
                "resets_in_window": len(self._reset_times),
                "storm_resets": cfg.storm_resets,
                "window_s": cfg.storm_window_s,
            },
        }
        if self._faults is not None:
            out["fault_plan"] = self._faults.describe()
        if self._control is not None and hasattr(self._control,
                                                 "wire_state"):
            # control-plane wire state (published seq, per-follower
            # last-sent + last-acked seqs): a follower disconnect is
            # diagnosable from the health endpoint post-mortem
            out["control"] = self._control.wire_state()
        return out

    # -- per-request explain (obs/timeline.py) ---------------------------

    def request_timeline(self, rid: int) -> Optional[dict]:
        """GET /api/v1/requests/{rid}/timeline: one merged,
        time-ordered view of the request's trace spans, its event-bus
        events and the step records whose batch contained it — the
        single call that attributes a slow TTFT to its actual causes
        (preempted twice, prefix spilled then restored, folded by a
        config switch, ...). None when the rid is unknown (fell out of
        the finished ring, or never admitted) — the API's 404."""
        from cake_tpu.obs.timeline import build_timeline
        trace = self.tracer.get(rid)
        if trace is None:
            return None
        events = (self.events.dump(rid=rid)
                  if self.events is not None else [])
        local_host = None
        if self.telemetry is not None:
            # fleet-scope explain: the collector's remote events carry
            # their origin host and clock-offset-corrected timestamps,
            # so a request that prefilled on host A and decoded on
            # host B still reads as ONE ordered chronology
            local_host = getattr(self.telemetry, "local_host", None)
            try:
                events = events + self.telemetry.events_for(rid=rid)
            except Exception:  # noqa: BLE001 — explain must not fail
                log.debug("remote event merge failed", exc_info=True)
        return build_timeline(trace, events,
                              self.flight.records_for(rid),
                              local_host=local_host)

    # -- live reconfiguration (cake_tpu/autotune) ------------------------

    def _setup_paged_exec(self, kv_pages: int, kv_page_size: int,
                          paged_attn: Optional[str],
                          kv_host_pages: Optional[int]) -> None:
        """Build the paged execution state — step-fn partials, page
        allocator, pool cache, host tier — from the geometry knobs.
        The SINGLE source for __init__ AND the live hot-switch seam
        (_apply_exec_config): a reconfigured pool must resolve exactly
        as a startup one would. Requires self.paged/self.kv_quant/
        self._kv_dtype_name/self._base_cache_dtype/
        self.prefill_chunk already set."""
        from cake_tpu.models.llama.paged import (
            PageAllocator, PagedKVCache, mixed_token_buckets,
            prefill_prefix_pages, prefill_slot_paged,
        )
        if kv_pages < 1 or kv_page_size < 1:
            raise ValueError(
                f"--kv-pages {kv_pages} / --kv-page-size "
                f"{kv_page_size} must be >= 1")
        # paged_attn: {fold,pallas} attention impl for the paged step
        # fns, resolved ONCE here from the shapes the engine will
        # really dispatch (_resolve_paged_attn). The choice rides the
        # jitted steps as a STATIC arg, so both variants keep the same
        # traced signature and the engine's dispatch plumbing is
        # impl-blind. `impl` serves the decode step and the two
        # whole-window prefill programs (whose "pallas" is the flash
        # kernel over the fresh window, behind its own gate).
        pool_dtype = self._base_cache_dtype
        if self._kv_dtype_name is not None and not self.kv_quant:
            from cake_tpu.utils.devices import resolve_kv_dtype
            pool_dtype = resolve_kv_dtype(self._kv_dtype_name)
        self._pool_dtype = pool_dtype
        self._resolve_paged_attn(paged_attn, kv_pages, kv_page_size)
        impl = self.attn_impl["decode"]
        # the family's step programs, behind the signatures they share
        # (models/family.py). Token-level continuous batching: ONE
        # jitted step consumes a batch of (row kind, pos, q_len)
        # descriptors — decode rows and prefill-chunk rows in the same
        # launch — and samples (make_mixed_sampled), so that a step can
        # be kept in flight
        family = self._family
        self._decode_step = partial(family.decode_step, attn=impl)
        self._decode_scan_impl = partial(family.decode_programs, attn=impl)
        self._mixed_step_fn = partial(family.mixed_sampled,
                                      attn=self.attn_impl["mixed"])
        # whole-prompt prefill into one slot's pages: the paged spec's
        # draft prefill (_spec_activate); requests' prompts ride the
        # mixed step. Page-granular prefix sharing: registered prefixes
        # (and auto_prefix_system heads) prefill ONCE into pool pages
        # and are mapped read-only into every matching slot's table row
        # (_alloc_slot_pages). Both programs are the GQA pool's: rows
        # that hold more than K/V pages have neither, and no prefix
        # pages.
        if family.moves("register_prefix"):
            self._prefill_slot = partial(prefill_slot_paged, attn=impl)
            self._prefix_pages_step = partial(prefill_prefix_pages,
                                              attn=impl)
        else:
            self._prefix_capable = False
            self._prefill_slot = self._prefix_pages_step = None
        # the packed sizes a mixed step's dispatches run at (the
        # program's static n_tokens): _mixed_burst takes the smallest
        # that holds the tokens, start() runs each once so that none
        # compiles later
        self._mixed_buckets = mixed_token_buckets(
            self.max_slots, self._mixed_chunk,
            prefill_rows=family.prefill_rows)
        self._pager = PageAllocator(kv_pages, kv_page_size)
        self._slot_pages = {}
        # slot -> count of SHARED prefix pages in its table row (gauge
        # bookkeeping; the pages themselves ride _slot_pages for the
        # refcounted release)
        self._slot_prefix_pages = {}
        self._prefix_pages_shared = 0
        self._prefix_last_hit = {}
        self.cache = self._fresh_pool(kv_pages, kv_page_size)
        # a latent family's window kernel: its walk as the host counts
        # it, last position -> (pages, folds) (a function of shapes)
        self._window_walk = (
            family.window_walk(self.config, self.cache, self._mixed_chunk)
            if family.window_walk is not None else None)
        # its page kernel's: a single-token row's position -> (pages,
        # folds)
        self._decode_walk = (
            family.decode_walk(self.config, self.cache)
            if family.decode_walk is not None else None)
        self._mixed_attn_walk = self._mixed_walk_of(family)
        if family.beside is not None:
            what, gauge = family.beside
            beside = self.cache.beside_bytes()
            if gauge is not None:
                obs_steps.BESIDE_POOL_BYTES[gauge].set(beside)
            log.info("%s: %.2f GiB (%d bytes) beside the pool, %d rows",
                     what, beside / 2**30, beside, self.max_slots)
        if family.says is not None:
            log.info("model_type %s: %s", family.name,
                     family.says(self.config))
        if self.cache.memory_bytes() == 0:
            # a family none of whose layers keeps K/V: the pool is a
            # stated case, not a small one (nothing below sizes, spills
            # or reports by a page's bytes)
            log.info("paged KV: a pool of NO layers, 0 bytes: %d pages x "
                     "%d tokens are the allocator's bookkeeping of "
                     "positions alone (%s attention); what a row holds "
                     "lies beside the pool and the slots bound admission",
                     kv_pages, kv_page_size, self.attn_impl)
        else:
            log.info("paged KV: %d pages x %d tokens, %s attention, "
                     "%s storage (%.2f GiB pool; dense %d-slot "
                     "equivalent would be %.2f GiB)",
                     kv_pages, kv_page_size, self.attn_impl,
                     (self._kv_dtype_name + "+scales") if self.kv_quant
                     else str(pool_dtype),
                     self.cache.memory_bytes() / 2**30, self.max_slots,
                     self.cache.memory_bytes() / 2**30
                     * self.max_slots * self.max_seq_len
                     / (kv_pages * kv_page_size))
        # --kv-host-pages: host-RAM spill tier behind the page
        # allocator (cake_tpu/kv/host_tier.py) — preemption victims'
        # suffix pages and cold shared-prefix pages spill to pinned
        # host memory and stream back on demand, instead of being
        # discarded and recomputed.
        prev_tier = getattr(self, "_host_tier", None)
        self._host_tier = None
        if kv_host_pages is not None:
            from cake_tpu.kv import HostTier
            from cake_tpu.kv.quantized_pool import page_bytes
            tier = HostTier(
                kv_host_pages,
                page_bytes=page_bytes(
                    self.config, kv_page_size,
                    self._kv_dtype_name if self.kv_quant
                    else pool_dtype),
                # spill/restore publish on the engine's event bus
                # (present on first setup AND on a reconfigure rebuild)
                events=getattr(self, "events", None),
                dtype_name=(self._kv_dtype_name if self.kv_quant
                            else jnp.dtype(pool_dtype).name))
            if (prev_tier is not None
                    and prev_tier.page_bytes == tier.page_bytes):
                # reconfigure rebuild: _prepare_fold already decided
                # which entries the switch invalidates (and dropped or
                # cleared them) — carry the survivors into the fresh
                # tier so spilled streams resume from their pages
                # instead of re-prefilling
                for key in prev_tier.keys():
                    ent = prev_tier.pop(key)
                    if ent is not None:
                        tier.put(key, ent)
            self._host_tier = tier
            log.info("kv host tier: %d pages (%.1f MiB capacity)",
                     kv_host_pages,
                     kv_host_pages * tier.page_bytes / 2**20)
        # paged speculative decoding (cake_tpu/spec): the draft model's
        # KV pages live in a SECOND pool with the target pool's page
        # geometry, addressed by the SAME allocator — one page-id
        # space, so draft pages debit the one budget the admission
        # gate counts. The round fn rides the same static attn impl.
        if self._specp is not None:
            from cake_tpu.spec.round import spec_round_paged
            self.d_cache = PagedKVCache.create(
                self._specp.draft_config, self.max_slots, kv_pages,
                kv_page_size, self.max_seq_len, dtype=pool_dtype)
            self._spec_round_fn = partial(spec_round_paged,
                                          attn=self.attn_impl["spec"])
            log.info("paged spec: draft pool %d pages x %d tokens "
                     "(%.2f GiB), gamma=%d",
                     kv_pages, kv_page_size,
                     self.d_cache.memory_bytes() / 2**30,
                     self._specp.live_gamma)

    def _fresh_pool(self, kv_pages: int, kv_page_size: int):
        """An empty pool of this engine's storage: a quantized pool
        (cake_tpu/kv), or the family's cache."""
        if self.kv_quant:
            from cake_tpu.kv import Int4PagedKVCache, QuantizedPagedKVCache
            qcls = (Int4PagedKVCache if self._kv_dtype_name == "int4"
                    else QuantizedPagedKVCache)
            return qcls.create(self.config, self.max_slots, kv_pages,
                               kv_page_size, self.max_seq_len)
        from cake_tpu.models.llama.paged import PagedKVCache
        return PagedKVCache.create(
            self.config, self.max_slots, kv_pages, kv_page_size,
            self.max_seq_len, dtype=self._pool_dtype,
            width=self._mixed_chunk)

    def _resolve_paged_attn(self, requested: Optional[str],
                            kv_pages: int, kv_page_size: int) -> None:
        """Resolve the paged attention per step kind from what the
        engine will really dispatch — page size, H, KV, hd, pool dtype
        and pages, slots, and the mixed width C — and set
        self.paged_attn (the name-level fold|pallas the autotuner's
        config key compares; autotune/space.resolve_paged_attn is the
        ONE rule for "auto"), self.attn_impl (what each step kind
        actually runs, which is what the flight recorder and
        /api/v1/health report) and self._mixed_chunk.

        The default mixed width is the widest of 256, 128, ... the
        mixed kernel can hold (its VMEM scales with C), not a
        constant. A step kind whose kernel gate refuses these shapes
        takes the fold under "auto"; under an explicit "pallas" on a
        TPU that is an error, not a quiet reference."""
        from cake_tpu.autotune.space import resolve_paged_attn
        from cake_tpu.ops import ragged_paged_attention as rpa
        impl = resolve_paged_attn(requested)
        if impl not in ("fold", "pallas"):
            raise ValueError(
                f"--paged-attn must be fold or pallas, got {impl!r}")
        family = self._family
        if family.resolve_attn is not None:
            # the family's own rule, beside the kernel calls it
            # describes: one impl for both step kinds
            impl, width = family.resolve_attn(
                self.config, impl, explicit=requested == "pallas",
                prefill_chunk=self.prefill_chunk, slots=self.max_slots,
                n_pages=kv_pages, page_size=kv_page_size,
                max_seq_len=self.max_seq_len,
                q_itemsize=jnp.dtype(self.params["embed"].dtype).itemsize,
                kv_itemsize=jnp.dtype(self._pool_dtype).itemsize)
            self.paged_attn = impl
            self._mixed_chunk = width
            self.attn_impl = {"decode": impl, "mixed": impl}
            log.info("paged attention: requested %s -> %s%s (mixed width "
                     "%d)", requested or "auto", family.impl, impl, width)
            return
        packed4 = self._kv_dtype_name == "int4"
        pool_dtype = self._pool_dtype
        kw = dict(quantized=self.kv_quant, n_pages=kv_pages,
                  packed4=packed4, slots=self.max_slots,
                  max_pages=-(-self.max_seq_len // kv_page_size))
        sizes = dict(
            q_itemsize=jnp.dtype(self.params["embed"].dtype).itemsize,
            kv_itemsize=(1 if self.kv_quant
                         else jnp.dtype(pool_dtype).itemsize))

        def heads(c):
            return (c.num_attention_heads, c.num_key_value_heads,
                    c.head_dim)

        def mixed_ok(width: int) -> bool:
            return rpa.ragged_paged_mixed_supported(
                kv_page_size, *heads(self.config), width, **kw, **sizes)

        width = self.prefill_chunk
        if width is None:
            width = min(256, self.max_seq_len)
            if impl == "pallas":
                width = next((w for w in (256, 128, 64, 32, 16, 8)
                              if w <= width and mixed_ok(w)), width)
        ok = {"decode": rpa.ragged_paged_supported(
                  kv_page_size, *heads(self.config), **kw),
              "mixed": mixed_ok(width)}
        if self._specp is not None:
            # one static impl serves the whole round: the draft's
            # decode steps and the target's verify window (gamma+1
            # wide at most — the tuner only lowers gamma)
            ok["spec"] = (
                rpa.ragged_paged_supported(
                    kv_page_size, *heads(self._specp.draft_config), **kw)
                and mixed_ok(self.spec_gamma + 1))
        refused = [k for k, v in ok.items() if not v]
        if impl == "pallas" and refused and requested == "pallas":
            raise ValueError(
                f"--paged-attn pallas cannot serve the {refused} "
                f"step(s) on this device at page={kv_page_size} "
                f"heads={heads(self.config)} pool="
                f"{self._kv_dtype_name or jnp.dtype(pool_dtype).name} "
                f"pages={kv_pages} slots={self.max_slots} mixed width="
                f"{width} (ops/ragged_paged_attention gates); use "
                "--paged-attn auto or fold, or a narrower "
                "--prefill-chunk")
        self.paged_attn = impl
        self._mixed_chunk = width
        self.attn_impl = {
            k: impl if ok.get(k, True) else "fold"
            for k in ("decode", "mixed")
            + (("spec",) if self._specp is not None else ())}
        log.info("paged attention: requested %s -> %s (mixed width %d)",
                 requested or "auto", self.attn_impl, width)

    def _step_impl(self, kind: str) -> Optional[str]:
        """The attention a step of this kind actually ran, for its
        flight record (None = the recorder's engine-wide flavor)."""
        if not self.paged:
            return None
        return self._family.impl + self.attn_impl.get(
            kind, self.attn_impl["decode"])

    def _capture_cache_identity(self) -> None:
        """Record the cache's placement/dtype so post-error and
        post-switch rebuilds restore identically-sharded zeros even
        after donation freed the live buffers."""
        if isinstance(self.cache, KVCache):
            self._cache_shardings = KVCache(k=self.cache.k.sharding,
                                            v=self.cache.v.sharding)
            self._cache_dtype = self.cache.k.dtype
        else:
            # custom cache pytree (e.g. the sp engine's SPEngineCache):
            # capture (shape, dtype, sharding) NOW — donation frees the
            # buffers, and a post-error rebuild cannot read them then
            self._cache_shardings = jax.tree.map(
                lambda x: (x.shape, x.dtype, x.sharding), self.cache,
                is_leaf=lambda x: hasattr(x, "sharding"))
            # first LEAF, not first field: a quantized paged cache's
            # first field is a QuantPool pytree, not an array
            self._cache_dtype = jax.tree_util.tree_leaves(
                self.cache)[0].dtype

    def _reconfig_supported(self) -> bool:
        return (not self._custom_steps and not self.ring
                and not self._spec_paged
                and not self._multihost
                and self._family.moves("reconfigure"))

    def _reconfig_refusal(self) -> str:
        if not self._family.moves("reconfigure"):
            return self._family.refuses["reconfigure"]
        if self._spec_paged:
            return ("speculative serving has no hot-switch fold "
                    "(the draft pool shares the page allocator a "
                    "switch would swap wholesale)")
        if self.ring:
            return ("ring (sliding-window) caches own their layout; "
                    "a rebuilt ring cannot replay folded positions")
        if self._multihost:
            return ("multi-host serving replays a fixed op stream; "
                    "followers cannot rebuild mid-stream")
        return ("custom step fns own their cache contract; only the "
                "built-in dense/paged engines can hot-switch")

    def current_config(self):
        """The LIVE effective engine config as an autotune point
        (cake_tpu/autotune.EngineConfig) — what /api/v1/health and
        GET /api/v1/autotune report."""
        from cake_tpu.autotune.space import EngineConfig
        kv_dtype = None
        if self.paged:
            if self.kv_quant:
                kv_dtype = self._kv_dtype_name
            elif self._pool_dtype != self._base_cache_dtype:
                # report the storage name only when it actually
                # differs from what an UNSET --kv-dtype resolves to —
                # a policy config omitting kv_dtype must compare equal
                # to an engine whose explicit name resolved to the
                # default (config_key spell-normalization)
                kv_dtype = self._kv_dtype_name
        return EngineConfig(
            slots=self.max_slots,
            decode_scan=self._decode_scan,
            kv_pages=self.cache.n_pages if self.paged else None,
            # cakelint: skip[affinity] taking _switch_lock here would invert the declared order: checkpoint.snapshot calls this under _ckpt_lock (shutdown_save/_snapshot_before_fail); the unlocked read tolerates a torn value mid-switch (informational health/snapshot metadata only)
            kv_page_size=(self._pager.page_size if self.paged else 128),
            kv_dtype=kv_dtype,
            paged_attn=self.paged_attn or "auto",
        )

    def reconfigure(self, config, reason: str = "manual") -> bool:
        """Hot-switch the engine to a new EngineConfig under live load:
        fold every in-flight request's generated tokens into its prompt
        (exactly the PR 8 recovery resubmit minus backoff and crash
        implication), tear down and rebuild the jitted step fns + KV
        pool under the new knobs, and requeue with seniority, class and
        preempt budget preserved. Greedy streams complete
        token-identical at f32 KV across the switch (dense AND paged,
        shared-prefix slots included — tests/test_autotune_engine.py).

        Thread-safe: routed onto the engine thread between iterations
        when the engine is live; a concurrent switch raises
        SwitchInFlightError (the API's 409). Returns True when a
        switch happened, False for a no-op (already at `config`)."""
        from cake_tpu.autotune.space import EngineConfig
        from cake_tpu.serve.errors import SwitchInFlightError
        cfg = (config if isinstance(config, EngineConfig)
               else EngineConfig.from_dict(dict(config)))
        if (self._thread is not None and self._thread.is_alive()
                and threading.current_thread() is not self._thread):
            with self._switch_lock:
                if self._switch_inflight:
                    raise SwitchInFlightError(
                        "a config switch is already in flight")
                self._switch_inflight = True
            try:
                return self._run_on_engine_thread(
                    lambda: self._reconfigure_sync(cfg, reason))
            finally:
                with self._switch_lock:
                    self._switch_inflight = False
        # cakelint: skip[affinity] engine thread not running, or this IS the engine thread (autotune tick); runtime assert distinguishes
        return self._reconfigure_sync(cfg, reason)

    @engine_thread_only
    def _reconfigure_sync(self, new, reason: str) -> bool:
        """Engine-thread body of reconfigure() — between iterations
        only (no device work in flight, exactly the preemption
        invariant)."""
        from cake_tpu.autotune import (
            SWITCH_SECONDS, SWITCHES, set_config_info,
        )
        from cake_tpu.autotune.space import (
            config_key, switch_guard, validate_config,
        )
        # default-aware keys: a policy spelling the engine's default
        # pool dtype explicitly must be a no-op, not a pointless fold
        base = np.dtype(self._base_cache_dtype).name
        cur = self.current_config()
        if (config_key(new, default_kv_dtype=base)
                == config_key(cur, default_kv_dtype=base)):
            return False
        if not self._reconfig_supported():
            raise ValueError("live reconfiguration is unavailable: "
                             + self._reconfig_refusal())
        guard = switch_guard(cur, new)
        if guard is not None:
            raise ValueError(guard)
        validate_config(new, max_seq_len=self.max_seq_len)
        if (self.prefill_chunk is not None
                and self.max_seq_len % self.prefill_chunk != 0):
            raise ValueError("prefill_chunk no longer divides "
                             "max_seq_len")  # unreachable; belt+braces
        t0 = time.perf_counter()
        # the whole mutation runs under _switch_lock: handler-thread
        # submit() takes the same lock around its registration, so an
        # admission lands fully before this switch (fit-checked below
        # and carried) or fully after it (validated by submit's own
        # fail-fast against the NEW pool) — never half-registered
        # across the scheduler/pool swap
        with self._switch_lock:
            # ZERO dropped streams is the contract: refuse a pool no
            # in-flight request fits instead of quietly failing it
            # (the same bound submit() enforces at admission)
            if new.kv_pages is not None:
                per = new.kv_page_size
                for req in list(self._requests.values()):
                    if req.done.is_set():
                        continue
                    need = -(-(len(req.prompt_ids)
                               + req.max_new_tokens) // per)
                    if need > new.kv_pages:
                        raise ValueError(
                            f"refusing switch: rid={req.rid} needs "
                            f"{need} kv pages, the proposed pool has "
                            f"{new.kv_pages} (no stream may be "
                            "dropped)")
            folded = self._prepare_fold(new)
            applied, apply_err = new, None
            try:
                self._apply_exec_config(new)
            except Exception as e:  # noqa: BLE001 — e.g. the new pool
                # OOMs after the old one was freed: restore the OLD
                # config's geometry (zeros pool — the folded streams
                # re-prefill from token ids either way) instead of
                # leaving the engine cacheless and unservable
                log.exception("reconfigure rebuild failed; restoring "
                              "the previous config")
                applied, apply_err = cur, e
                self._apply_exec_config(cur)
            carried = self._requeue_folded(applied, folded)
        if apply_err is not None:
            self._wake.set()
            raise ValueError(
                f"switch to {new.to_dict()} failed; previous config "
                f"restored with {carried} stream(s) requeued: "
                f"{apply_err}") from apply_err
        self.config_epoch += 1
        self.stats.config_switches += 1
        dt = time.perf_counter() - t0
        SWITCHES.labels(reason=reason).inc()
        SWITCH_SECONDS.observe(dt)
        set_config_info(self.current_config())
        entry = {"t": round(time.time(), 3), "reason": reason,
                 "from": cur.to_dict(), "to": new.to_dict(),
                 "seconds": round(dt, 4), "carried": carried,
                 "epoch": self.config_epoch}
        self._switch_log.append(entry)
        if self.events is not None:
            # engine-level summary event (rid=None) beside the
            # per-request ones _requeue_folded published: one line
            # answers what switched, to what, and how many streams rode
            self.events.publish("reconfigured", reason=reason,
                                epoch=self.config_epoch,
                                carried=carried,
                                seconds=round(dt, 4),
                                to=new.to_dict())
        if self._autotuner is not None and reason == "manual":
            # keep the auto controller's view of "current" in sync with
            # an operator's switch (it would otherwise keep proposing
            # moves relative to the superseded config); manual reasons
            # never arm the rollback guard — the operator's call stands
            self._autotuner.on_switched(
                new, cur, self._autotuner.window_service_tps(), reason)
        log.warning("engine reconfigured (%s) in %.3fs: %s -> %s, "
                    "%d stream(s) carried (epoch %d)", reason, dt,
                    cur.to_dict(), new.to_dict(), carried,
                    self.config_epoch)
        self._wake.set()
        return True

    def _storage_name(self) -> str:
        """The LIVE pool's storage-dtype name ("int8"/"int4" for the
        quantized tiers, the numpy dtype name otherwise) — the identity
        a host-tier entry's raw slices are layout-bound to."""
        if self.kv_quant:
            return self._kv_dtype_name
        return np.dtype(self._pool_dtype).name

    def _target_storage_name(self, new) -> str:
        """What _setup_paged_exec would resolve `new`'s storage to —
        mirrors its pool_dtype resolution so the host-tier survival
        check compares the names the rebuild will actually use."""
        if new.kv_dtype in ("int8", "int4"):
            return new.kv_dtype
        if new.kv_dtype is not None:
            from cake_tpu.utils.devices import resolve_kv_dtype
            return np.dtype(resolve_kv_dtype(new.kv_dtype)).name
        return np.dtype(self._base_cache_dtype).name

    def _host_tier_survives(self, new) -> bool:
        """Whether spilled host-tier entries stay valid across a switch
        to `new`: the rebuilt pool must still be paged with the SAME
        page geometry and storage dtype — entries are raw pool slices,
        so a matching pool re-installs them verbatim (page COUNT may
        change freely; entries reference contents, not page ids)."""
        return (self.paged and new.kv_pages is not None
                and new.kv_page_size == self._pager.page_size
                and self._target_storage_name(new) == self._storage_name())

    def _prepare_fold(self, new) -> set:
        """Host-side half of the fold: clear every slot's mappings,
        release pages through the OLD allocator (before the rebuild
        replaces it), and drop state the old pool's bytes back
        (spilled pages, the prefix registry). After this, every
        unfinished request is slotless and will re-prefill from token
        ids — so it is safe regardless of whether the rebuild lands
        the NEW config or rolls back to the old geometry. Caller holds
        _switch_lock, engine thread only. Returns the rids that held
        slots — the streams the switch actually folds (queued requests
        just ride along untouched)."""
        folded = set()
        for slot in range(self.max_slots):
            req = self._slot_req[slot]
            self._slot_req[slot] = None
            if req is not None:
                req.slot = -1
                folded.add(req.rid)
            self._release_slot_pages(slot)
        self._mixed_pending.clear()
        self._page_blocked_rid = None
        self._pending_page_preempt = None
        self._page_starved = False
        self._cur_decode = {}
        self._implicated = ()
        if self._host_tier is not None:
            if self._host_tier_survives(new):
                # PR 9 gap closed: victim entries are raw per-page pool
                # slices (dtype-blind install), valid in ANY rebuilt
                # pool with the same page geometry + storage dtype —
                # keep them so spilled/preempted streams resume from
                # their pages instead of re-prefilling. Prefix entries
                # still die with the registry below (their pids and
                # refcounts do not survive the fold), and a surviving
                # victim whose admission shape no longer matches is
                # dropped by _alloc_slot_pages' entry validation.
                for key in self._host_tier.keys():
                    if not (isinstance(key, tuple) and key
                            and key[0] == "victim"):
                        self._host_tier.drop(key)
            else:
                # geometry or storage dtype changed: spilled pages are
                # OLD-pool layout/dtype; a restore into the rebuilt
                # pool would scatter stale bytes
                self._host_tier.clear()
        if self.paged or new.kv_pages is not None:
            # the paged registry points at pool pages that die with the
            # old pool (and a dense registry's (k, v) entries mean
            # nothing to a paged successor) — auto-prefix heads
            # re-register on their next request
            with self._rid_lock:
                self._prefixes.clear()
                self._auto_pids.clear()
            self._prefix_last_hit = {}
            self._prefix_pages_shared = 0
            _PREFIX_PAGES_SHARED.set(0)
        return folded

    def _requeue_folded(self, applied, folded: set) -> int:
        """Scheduler half of the fold, AFTER the rebuild landed: fold
        every unfinished request into its prompt and requeue under the
        config that was actually applied (the target, or the restored
        old geometry if the rebuild failed) — the recovery resubmit
        minus backoff/implication: seniority and class survive (SLO
        requeue), preempt budgets are untouched, nothing is
        quarantined. Caller holds _switch_lock (handler-thread
        submit() serializes against the scheduler swap on the same
        lock). Returns the number of streams the switch actually
        FOLDED (requests that held a slot — `folded` from
        _prepare_fold; queued requests requeue/resubmit too but are
        not counted or trace-stamped: the switch never touched them)."""
        carried = 0
        if self._slo:
            for rid, req in sorted(self._requests.items()):
                if req.done.is_set():
                    continue
                req._kv_restored = False
                remaining = req.max_new_tokens - len(req.out_tokens)
                if remaining <= 0:
                    # was retiring this iteration — it already holds
                    # every token it asked for
                    self._finish_recovered(req)
                    continue
                # requeue preserves the original enqueue time
                # (seniority) and the preemption count; False just
                # means the request was still QUEUED — nothing to do
                active = self.scheduler.requeue(
                    rid, len(req.prompt_ids) + len(req.out_tokens),
                    remaining)
                if active or rid in folded:
                    self.tracer.span(rid, "reconfigured",
                                     generated=len(req.out_tokens))
                    if self.events is not None:
                        self.events.publish(
                            "reconfigured", rid=rid,
                            generated=len(req.out_tokens))
                    carried += 1
            self.scheduler.resize(applied.slots)
        else:
            # FIFO has no requeue: rebuild the scheduler at the new
            # slot count and resubmit in rid order (arrival order).
            # Capacity must cover QUEUED + formerly-ACTIVE requests:
            # active slots did not count against the old queue cap, so
            # a full queue plus occupied slots would overflow a
            # same-capacity rebuild and drop the overflow — widen to
            # whatever is unfinished right now (at most old_slots over
            # the configured cap; later rebuilds use _max_queue again)
            unfinished = sum(1 for r in self._requests.values()
                             if not r.done.is_set())
            self.scheduler = make_scheduler(
                applied.slots, max(self._max_queue, unfinished),
                priority_classes=False, config=self._sched_cfg)
            for rid, req in sorted(self._requests.items()):
                if req.done.is_set():
                    continue
                req._kv_restored = False
                remaining = req.max_new_tokens - len(req.out_tokens)
                if remaining <= 0:
                    self._finish_recovered(req)
                    continue
                if not self.scheduler.submit(
                        rid, len(req.prompt_ids) + len(req.out_tokens),
                        remaining):
                    # capacity was sized above: cannot happen — but a
                    # dropped stream must be LOUD
                    from cake_tpu.serve.errors import as_engine_error
                    self._drop_request(req, as_engine_error(
                        RuntimeError("reconfigure resubmit failed")))
                    continue
                if rid in folded:
                    self.tracer.span(rid, "reconfigured",
                                     generated=len(req.out_tokens))
                    if self.events is not None:
                        self.events.publish(
                            "reconfigured", rid=rid,
                            generated=len(req.out_tokens))
                    carried += 1
        return carried

    def _apply_exec_config(self, new) -> None:
        """Rebuild the config-dependent execution state under the new
        knobs: step fns, KV cache/pool, per-slot mirrors, PRNG keys and
        the flight recorder's config namespace. Engine thread only,
        after _fold_all_for_switch (no slot holds device state)."""
        from cake_tpu.models.llama.model import prefill_slot_chunk
        B = new.slots
        self.max_slots = B
        self._decode_scan = max(1, new.decode_scan)
        self.paged = new.kv_pages is not None
        self.kv_quant = new.kv_dtype in ("int8", "int4")
        self._kv_dtype_name = new.kv_dtype
        # free the OLD cache/pool BEFORE building the new one: unlike
        # _reset_after_error (where donation already consumed the
        # buffers), reconfigure's old pool is fully live — keeping
        # both resident would transiently double KV HBM and OOM
        # exactly under the memory pressure a switch is meant to
        # relieve. Safe: every slot was folded (the resume re-prefills
        # from token ids, no old-pool bytes needed); dense prefix
        # entries live outside the cache and are kept/cleared above.
        for leaf in jax.tree_util.tree_leaves(self.cache):
            if hasattr(leaf, "delete"):
                try:
                    leaf.delete()
                except Exception:  # noqa: BLE001 — already-donated
                    pass
        self.cache = None
        if self.paged:
            self._setup_paged_exec(new.kv_pages, new.kv_page_size,
                                   new.paged_attn, self._kv_host_pages)
        else:
            self.paged_attn = None
            self.attn_impl = {}
            self._host_tier = None
            self._prefill_slot = prefill_slot
            self._decode_step = decode_step_ragged
            self._decode_scan_impl = _decode_scan
            self._prefill_chunk_step = prefill_slot_chunk
            self.cache = KVCache.create(self.config, B, self.max_seq_len,
                                        dtype=self._base_cache_dtype)
        self._prefix_capable = True
        self._capture_cache_identity()
        # per-slot mirrors at the new width
        self._pos = np.zeros(B, np.int64)
        self._last_tok = np.zeros(B, np.int64)
        self._steps = np.zeros(B, np.int64)
        self._temp = np.full(B, self.defaults.temperature or 0.0,
                             np.float32)
        self._top_p = np.ones(B, np.float32)
        self._penalty = np.full(B, self.defaults.repeat_penalty,
                                np.float32)
        self._ring = jnp.full((B, self.defaults.repeat_last_n), -1,
                              jnp.int32)
        self._slot_req = [None] * B
        # fold a reset counter into the rebuild key exactly like
        # _reset_after_error: restoring the startup keys would replay
        # already-consumed sampling streams
        self._reset_count += 1
        self._keys = jax.random.split(
            jax.random.fold_in(jax.random.PRNGKey(self._key_seed),
                               self._reset_count), B)
        self._last_jit = None
        # re-namespace the jit accountant so the new config's compiled
        # signatures can never alias the old config's
        flavor = (f"paged-{self.paged_attn}" if self.paged else "dense")
        self.flight.rebind(
            impl=flavor,
            key_prefix=(self.config, B, self.max_seq_len,
                        str(self._cache_dtype), flavor))

    def autotune_state(self) -> dict:
        """GET /api/v1/autotune: mode, live config, switch/decision
        history, and (auto mode) the controller's window signals."""
        out = {
            "mode": self.autotune_mode,
            "epoch": self.config_epoch,
            "config": self.current_config().to_dict(),
            "switches": self.stats.config_switches,
            "rollbacks": self.stats.config_rollbacks,
            "switch_in_flight": self._switch_inflight,
            "switch_log": list(self._switch_log),
        }
        at = self._autotuner
        if at is not None:
            out["controller"] = at.state()
            out["policy"] = at.policy.to_dict()
        return out

    def _gather_autotune_signals(self, now: float):
        """One sliding-window sample from telemetry the engine already
        keeps: arrival/service deltas from EngineStats, MFU/HBM from
        the flight recorder, queue depth from the scheduler, pool
        occupancy from the allocator, TTFT from the tracer ring."""
        from cake_tpu.autotune import AutotuneSignals
        st = self.stats
        submitted = self._next_rid - 1
        cur = (now, submitted, st.requests_completed,
               st.tokens_generated, st.shed)
        prev, self._autotune_prev = self._autotune_prev, cur
        if prev is None:
            prev = cur
        dt = max(1e-6, now - prev[0])
        util = self.flight.utilization(include_prefill=True)
        pages_frac = 0.0
        if self.paged:
            total = self.cache.n_pages
            pages_frac = (total - self._pager.free_pages) / total
        depths = getattr(self.scheduler, "class_depths", None)
        ttfts = self.tracer.recent_ttfts(32)
        p99 = None
        if ttfts:
            xs = sorted(ttfts)
            p99 = xs[min(len(xs) - 1, int(0.99 * len(xs)))]
        pressure = getattr(self.scheduler, "queue_pressure", None)
        return AutotuneSignals(
            t=now,
            offered_rps=(submitted - prev[1]) / dt,
            completed_rps=(st.requests_completed - prev[2]) / dt,
            service_tps=(st.tokens_generated - prev[3]) / dt,
            queue_depth=self.scheduler.queue_depth,
            queue_depth_by_class=depths() if depths else {},
            mfu=util.get("mfu"), hbm_util=util.get("hbm_util"),
            pages_in_use_frac=pages_frac,
            shed_rps=(st.shed - prev[4]) / dt,
            ttft_p99_s=p99,
            # quality signals (obs/slo.py + sched aging pressure): what
            # the policy's v2 guards and the rollback guard key on —
            # the 1m window matches the controller's decision horizon
            ttft_p99_by_class=self.slo.ttft_p99_by_class("1m"),
            attainment=self.slo.attainment_by_class("1m"),
            queue_pressure=pressure() if pressure is not None else 0.0,
        )

    @engine_thread_only
    def _autotune_tick(self) -> None:
        """Auto-mode controller drive, called from the engine loop
        between iterations: sample signals every interval, apply the
        controller's switch/rollback decision inline (this IS the
        engine thread, so the switch happens at a step boundary)."""
        from cake_tpu.autotune import ROLLBACKS
        at = self._autotuner
        if at is None:
            return
        now = time.monotonic()
        if now - self._autotune_last < at.config.interval_s:
            return
        self._autotune_last = now
        decision = at.decide(self._gather_autotune_signals(now))
        if decision is None:
            return
        target, reason = decision
        old = self.current_config()
        pre_rate = at.window_service_tps()
        try:
            if not self._reconfigure_sync(target, reason):
                # spelled-differently-but-identical target (the
                # engine's default-aware key normalization caught it):
                # adopt the target spelling as "current" so the
                # controller stops re-proposing the no-op every tick
                at.on_switched(target, old, pre_rate, "noop")
                return
        except Exception as e:  # noqa: BLE001
            if reason == "rollback":
                # a REFUSED revert (e.g. a stream admitted under the
                # new pool no longer fits the old one) must NOT pin
                # the known-good pre-switch config: stay put — the
                # regressed config is already pinned, so once load
                # drains the policy re-proposes the good one normally
                log.warning("rollback revert refused; staying on the "
                            "current config: %s", e)
            else:
                # an unswitchable policy target must not spin: pin it
                # so the controller stops proposing it
                log.warning("autotune switch refused (%s); pinning: "
                            "%s", reason, e)
                at.pin(target, why=str(e))
            return
        at.on_switched(target, old, pre_rate, reason)
        if reason == "rollback":
            ROLLBACKS.inc()
            self.stats.config_rollbacks += 1

    def _reset_after_error(self) -> None:
        # the jitted steps donate the cache/keys/ring buffers; after a
        # failed call they may already be deleted — rebuild so the engine
        # survives (transient OOM/XLA error must not brick serving)
        self.cache = self._fresh_cache()
        self._pos[:] = 0
        self._last_tok[:] = 0
        self._steps[:] = 0
        self._dev_held.clear()
        B = self.max_slots
        self._ring = jnp.full((B, self.defaults.repeat_last_n), -1,
                              jnp.int32)
        # fold a reset counter into the rebuild key: restoring the
        # STARTUP keys would replay already-consumed sampling streams
        # (duplicate "random" completions after a transient error).
        # The counter advances identically on every process (followers
        # replay the reset op), so multi-host keys stay in lockstep.
        self._reset_count += 1
        self._keys = jax.random.split(
            jax.random.fold_in(jax.random.PRNGKey(self._key_seed),
                               self._reset_count), B)

    def _fresh_cache(self) -> KVCache:
        if not isinstance(self.cache, KVCache) and not self.paged:
            # custom cache pytree (sp engine): rebuild zeros from the
            # (shape, dtype, sharding) captured at init — the donated
            # buffers themselves may already be freed. PagedKVCache is
            # also not a KVCache but MUST take its own branch below: a
            # zeros rebuild would map every slot to page 0 (create()
            # fills the table with -1) and leak the allocator's pages.
            # jit-with-out_shardings, NOT device_put: each shard zeros
            # in place (no full-buffer host transient), and it is the
            # only valid construction over a multi-process mesh, where
            # device_put to non-addressable devices raises
            # (create_sp_engine_cache precedent).
            specs = list(self._cache_shardings)
            make = jax.jit(
                lambda: type(self.cache)(*(
                    jnp.zeros(shape, dtype)
                    for (shape, dtype, _s) in specs)),
                out_shardings=type(self.cache)(*(
                    s for (_shape, _dtype, s) in specs)))
            return make()
        if self.paged:
            from cake_tpu.models.llama.paged import (
                PageAllocator, PagedKVCache,
            )
            # the rebuild loses every slot's KV; reset the allocator and
            # table bookkeeping with it. Registered prefixes lived in
            # the (now gone) pool pages, so the registry is cleared too
            # — auto-prefix heads re-register on their next request
            self._pager = PageAllocator(self.cache.n_pages,
                                        self.cache.page_size)
            self._slot_pages = {}
            self._slot_prefix_pages = {}
            self._mixed_pending = {}
            self._prefix_pages_shared = 0
            _PREFIX_PAGES_SHARED.set(0)
            with self._rid_lock:
                self._prefixes.clear()
                self._auto_pids.clear()
            self._prefix_last_hit = {}
            with self._rid_lock:
                # staged shipments referenced the failed requests'
                # admissions; post-reset resubmits prefill locally
                self._adopt_store.clear()
            if self._host_tier is not None:
                # spilled victims/prefixes belonged to the failed
                # requests / cleared registry — stale shortcuts only
                self._host_tier.clear()
            if self._specp is not None:
                # every stream's draft/suffix pages lived in the
                # allocator just reset; drop the spec states and
                # rebuild the draft pool (streams re-activate lazily
                # after their recovery resubmit)
                self._specp.spec_streams.clear()
                self.d_cache = PagedKVCache.create(
                    self._specp.draft_config, self.max_slots,
                    self.cache.n_pages, self.cache.page_size,
                    self.max_seq_len, dtype=self._pool_dtype)
            return self._fresh_pool(self.cache.n_pages,
                                    self.cache.page_size)
        fresh = KVCache.create(self.config, self.max_slots,
                               self.cache.max_seq_len
                               if self.ring else self.max_seq_len,
                               dtype=self._cache_dtype)
        return KVCache(
            k=jax.device_put(fresh.k, self._cache_shardings.k),
            v=jax.device_put(fresh.v, self._cache_shardings.v),
        )

    # -- step telemetry seams (obs/steps.py) -----------------------------

    def _obs_jit(self, name: str, key: tuple, fn, args: tuple,
                 kwargs: Optional[dict] = None):
        """Pre-dispatch compile/cost accounting for one step-fn call:
        a new (engine-config, name, key) signature bumps
        cake_jit_compiles_total{fn} and captures cost_analysis FLOPs /
        bytes from one extra lowering (trace only, no XLA compile) —
        run NOW, before the dispatch consumes its donated buffers."""
        return self.flight.jit_step(
            name, key, lambda: obs_steps.lower_cost(fn, args, kwargs))

    def _page_kw(self) -> dict:
        if not self.paged:
            return {}
        return {"pages_free": self._pager.free_pages,
                "pages_total": self.cache.n_pages}

    def _record_step(self, kind: str, *, rows: int, tokens: int,
                     dispatch_s=None, device_s=None, wall_s=None,
                     js=None, paged_step_s: Optional[float] = None,
                     **split) -> None:
        """Append one flight record for the step that just completed,
        attaching the pending dispatch's cost info (js, or the
        engine-thread mailbox _last_jit) and page-pool occupancy.
        `split` carries the mixed step's occupancy breakdown
        (rows_decode / rows_prefill / rows_idle) and the dispatched
        rows' `rids` (the per-request explain's step linkage).
        paged_step_s: a mixed or decode step's wall seconds for the
        histogram that compares the fold and pallas paged attention
        impls (scan/burst callers pass their per-step average; a dense
        engine observes nothing). All of it under the `record` span."""
        with self.flight.span("record"):
            if paged_step_s is not None and self.paged:
                _PAGED_ATTN_STEP.labels(
                    path="mixed" if kind == "mixed" else "decode"
                ).observe(paged_step_s)
            if js is None:
                js, self._last_jit = self._last_jit, None
            self.flight.record(
                kind, rows=rows, tokens=tokens, dispatch_s=dispatch_s,
                device_s=device_s, wall_s=wall_s,
                cost=js.cost if js is not None else None,
                compiled=bool(js is not None and js.new),
                impl=self._step_impl(kind),
                **split, **self._page_kw())

    # -- SLO scheduling: preemption + shed seams (cake_tpu/sched) --------

    def _set_queue_gauges(self) -> None:
        depths = getattr(self.scheduler, "class_depths", None)
        if depths is None:
            return
        for c, d in depths().items():
            _QUEUE_DEPTH.labels(c).set(d)

    @engine_thread_only
    def _maybe_preempt(self) -> None:
        """Reclaim at most one decoding slot per iteration for a
        starved higher class: first for a page-starved admission noted
        last iteration (reason=pages), else for the best-scored waiting
        request when every slot is taken (reason=slots). Victim choice
        (youngest slot of the worst class, preemption budget respected)
        lives in the scheduler; the recompute fold lives here."""
        pend, self._pending_page_preempt = self._pending_page_preempt, None
        cands = []
        if pend is not None:
            cands = [(v, "pages")
                     for v in self.scheduler.preemption_victims(pend)]
        if not cands:
            cands = [(v, "slots")
                     for v in self.scheduler.slot_preemption_victims()]
        for (rid, slot), reason in cands:
            if self._preempt_slot(rid, slot, reason):
                return

    def _preempt_slot(self, rid: int, slot: int, reason: str) -> bool:
        """Recompute-style preemption of one decoding slot: the victim's
        generated tokens fold into its prompt (exactly the
        checkpoint-resume fold, serve/checkpoint.resume — _do_prefill
        re-prefills prompt+generated and the next sampled token is the
        one an uninterrupted greedy run would emit), its pages release
        through the refcounted allocator (shared prefix pages just
        decref), and it requeues WITH its original seniority to
        re-prefill when capacity returns."""
        req = (self._slot_req[slot]
               if 0 <= slot < self.max_slots else None)
        if req is None or req.rid != rid or req.done.is_set():
            return False
        remaining = req.max_new_tokens - len(req.out_tokens)
        if remaining <= 0:
            return False    # retiring this iteration anyway
        if not self.scheduler.requeue(
                rid, len(req.prompt_ids) + len(req.out_tokens),
                remaining, preempted=True):
            return False
        self._slot_req[slot] = None
        req.slot = -1
        req.preemptions += 1
        # spill-over-recompute (cake_tpu/kv host tier): when host pages
        # are free, the victim's OWNED suffix pages (shared prefix
        # pages just decref) move to host RAM before release — resume
        # then restores them and decodes from where it stopped instead
        # of re-prefilling prompt + generated tokens
        spilled = self._spill_victim_pages(req, slot)
        self._release_slot_pages(slot)
        self.stats.preemptions += 1
        _PREEMPTIONS.labels(reason=reason).inc()
        self.tracer.span(rid, "preempted", reason=reason,
                         generated=len(req.out_tokens),
                         spilled=spilled)
        if self.events is not None:
            self.events.publish("preempted", rid=rid, reason=reason,
                                priority=req.priority,
                                generated=len(req.out_tokens),
                                spilled=spilled)
        log.debug("preempted rid=%d (%s, %d tokens %s)", rid, reason,
                  len(req.out_tokens),
                  "spilled to the host tier" if spilled
                  else "fold into the prompt")
        return True

    def _spill_victim_pages(self, req: _Request, slot: int) -> bool:
        """Device->host spill of one preemption victim's owned pages
        (engine thread; the pages are still live — called BEFORE
        _release_slot_pages). False = no tier / no room / mid-prefill
        victim: the recompute fold serves as before."""
        if (self._host_tier is None
                or not getattr(self._sched_cfg, "spill_preempt", True)
                or slot in self._mixed_pending
                or not req.out_tokens):
            return False
        row = self._slot_pages.get(slot) or []
        n_shared = self._slot_prefix_pages.get(slot, 0)
        own = row[n_shared:]
        if not own or not self._host_tier.can_hold(len(own)):
            return False
        from cake_tpu.kv.host_tier import SpilledPages
        try:
            if self._faults is not None:
                # inside the try: an injected fetch fault exercises the
                # documented degradation (fall back to recompute)
                self._faults.check("host_tier.fetch",
                                   step=self.stats.steps)
            arrays = self._host_tier.fetch_pages(self.cache, own)
        except Exception:  # noqa: BLE001 — spill is an optimization
            log.exception("victim spill failed; falling back to "
                          "recompute resume")
            return False
        ok = self._host_tier.put(("victim", req.rid), SpilledPages(
            n_pages=len(own), arrays=arrays, kind="victim",
            pos=int(self._pos[slot]),
            last_tok=int(self._last_tok[slot]),
            n_prefix_tokens=n_shared * self._pager.page_size))
        if ok:
            self.stats.kv_spills += 1
        return ok

    def _release_slot_pages(self, slot: int) -> None:
        """Refcounted release of a slot's page mappings — idempotent
        under the cancel-vs-error race (both teardown paths pop the same
        dict entry; the second caller finds nothing to release). Shared
        prefix pages decref back to the registry's reference instead of
        freeing another slot's live context."""
        if not self.paged or slot < 0:
            return
        # a slot torn down mid-prefill (cancel / preempt / error) must
        # not ride the next mixed step as a ghost chunk row
        self._mixed_pending.pop(slot, None)
        # spec teardown rides the SAME idempotent hook: the stream's
        # draft pages and target suffix-extension pages go back with
        # its base pages, whatever path tears the slot down (finish,
        # cancel, preempt, error) — zero leaked suffix pages
        self._release_spec_state(slot)
        pages = self._slot_pages.pop(slot, None)
        if pages:
            self._pager.release(pages)
        n_shared = self._slot_prefix_pages.pop(slot, 0)
        if n_shared:
            self._prefix_pages_shared -= n_shared
            _PREFIX_PAGES_SHARED.set(self._prefix_pages_shared)

    def _release_spec_state(self, slot: int) -> None:
        """Release a slot's speculative page bookkeeping (idempotent):
        the draft row's pages and the target row's suffix-extension
        pages return to the shared allocator. The device table rows
        keep the stale ids until the next table_set_slot — the same
        already-released-but-still-mapped window every slot teardown
        has, harmless because inactive rows are neither written nor
        read by callers."""
        if self._specp is None:
            return
        st = self._specp.spec_streams.pop(slot, None)
        if st is None:
            return
        if st.d_pages:
            self._pager.release(st.d_pages)
        if st.t_suffix_pages:
            self._pager.release(st.t_suffix_pages)

    def _alloc_slot_pages(self, req: _Request, slot: int,
                          hit=None) -> bool:
        """Admission by pages: map the slot's table row when the pool
        can cover prompt + budget; otherwise requeue the request (it is
        planned again as retiring requests free pages).

        hit: a validated prefix match ((pid, (p_ids, pages, _)), from
        _match_and_validate_prefix) — the slot then allocates only
        SUFFIX + budget pages and maps the shared prefix pages
        (refcount-retained) at the head of its row, so a 1k-token
        system prompt stops costing ceil(1k/page) pages per slot.

        FIFO fairness: a page-starved request becomes the BLOCKING head
        — younger requests requeue behind it instead of being admitted
        past it, or a steady stream of small requests could starve a
        large one forever (the requeue path re-enters the scheduler's
        FIFO at the tail, preserving relative order across cycles)."""
        from cake_tpu.models.llama.paged import table_set_slot
        if self._faults is not None:
            # chaos site for the admission allocator (an injected OOM
            # here surfaces exactly like a real allocation failure)
            self._faults.check("pager.alloc", step=self.stats.steps)
        blocked = getattr(self, "_page_blocked_rid", None)
        if blocked is not None and blocked not in self._requests:
            blocked = self._page_blocked_rid = None  # cancelled/failed
        if blocked is not None and req.rid != blocked:
            # SLO scheduling: a request that OUTRANKS the blocked head
            # (strictly better effective score) may try the pool past
            # it — once the head ages enough its score is best, nothing
            # outranks it, and it keeps first claim on freed pages
            # (the aged blocking head cannot be starved)
            leapfrog = (self._slo
                        and hasattr(self.scheduler, "outranks")
                        and self.scheduler.outranks(req.rid, blocked))
            if not leapfrog:
                return self._requeue_for_pages(req, slot, starved=False)
        prefix_pages: List[int] = []
        n_prefix = 0
        hit_pid = None
        if hit is not None:
            hit_pid = hit[0]
            p_ids, prefix_pages, _ = hit[1]
            n_prefix = len(p_ids)
            if prefix_pages is None:
                # the matched prefix was spilled to the host tier
                # under page pressure: stream it back before mapping
                # (engine thread — pool + table are single-writer)
                prefix_pages = self._restore_prefix(hit_pid)
                if prefix_pages is None:
                    # gone from host too, or no pool room for it right
                    # now: serve this admission without the prefix
                    hit = None
                    hit_pid = None
                    n_prefix = 0
                    prefix_pages = []
        # callers must prefill against the hit that was actually
        # mapped — a restore failure above downgrades it to None, and
        # dispatching the prefix-path prefill anyway would attend
        # never-written pages
        req._effective_hit = hit
        need = len(req.prompt_ids) - n_prefix + req.max_new_tokens
        if self._specp is not None:
            # spec admission gate: admit only when the pool can ALSO
            # cover the stream's worst-case speculative pages — the
            # draft row's whole-context pages (the draft pool shares
            # no prefixes) plus the target row's gamma-token suffix
            # overhang past the base allocation. Activation and
            # per-round extension stay best-effort (a shortfall there
            # degrades the row to plain decode), but admission counting
            # the worst case keeps a pool of spec streams from
            # admitting more residents than it can ever speculate for.
            g = self._specp.live_gamma
            base = len(req.prompt_ids) + req.max_new_tokens
            cap = min(base + g, self.max_seq_len)
            spec_extra = (self._pager.pages_for(cap)
                          + max(self._pager.pages_for(cap)
                                - self._pager.pages_for(base), 0))
            if (self._pager.pages_for(need) + spec_extra
                    > self._pager.free_pages):
                return self._requeue_for_pages(req, slot, starved=True)
        pages = self._pager.alloc(need)
        if pages is None and self._host_tier is not None:
            # consult the host tier before refusing admission: COLD
            # shared-prefix pages (registry-only references, no slot
            # mapping them) spill to host RAM, freeing device pages —
            # the prefix streams back on its next hit instead of being
            # the reason this request waits
            missing = (self._pager.pages_for(need)
                       - self._pager.free_pages)
            if self._spill_cold_prefixes(missing, keep_pid=hit_pid):
                pages = self._pager.alloc(need)
        if pages is None and self._host_tier is not None:
            # still short after the cold spills: oversubscribe — park
            # decode-RESIDENT streams (LRU by admission) in the host
            # tier until the admission fits or no candidate remains
            while (pages is None
                   and self._spill_resident_stream(req.rid)):
                pages = self._pager.alloc(need)
        if pages is None:
            return self._requeue_for_pages(req, slot, starved=True)
        # preempted victim whose pages were spilled (spill-over-
        # recompute): validated against the CURRENT admission shape —
        # a prefix evicted/re-registered between spill and resume
        # changes the row layout, and the stale entry must not restore
        ent = None
        if self._host_tier is not None:
            ent = self._host_tier.peek(("victim", req.rid))
            if ent is not None and (ent.n_prefix_tokens != n_prefix
                                    or ent.n_pages != len(pages)):
                self._host_tier.drop(("victim", req.rid))
                ent = None
            elif ent is not None:
                # counted as a restore; _restore_victim installs it
                ent = self._host_tier.pop(("victim", req.rid))
        if prefix_pages:
            # retain AFTER the suffix alloc: a requeued admission must
            # leave no dangling references behind
            self._pager.retain(prefix_pages)
            self._slot_prefix_pages[slot] = len(prefix_pages)
            self._prefix_pages_shared += len(prefix_pages)
            _PREFIX_PAGES_SHARED.set(self._prefix_pages_shared)
        row = list(prefix_pages) + pages
        self._slot_pages[slot] = row
        self.cache = self.cache._replace(
            table=table_set_slot(self.cache.table, slot, row))
        if self.kv_quant:
            # fresh pages must not inherit a previous occupant's
            # scales (kv/quantized_pool.reset_page_scales); a restore
            # below overwrites them with the spilled scales anyway
            from cake_tpu.kv.quantized_pool import reset_page_scales
            self.cache = reset_page_scales(self.cache, pages)
        if ent is not None:
            self._restore_victim(req, slot, pages, ent)
        if req.rid == blocked:
            self._page_blocked_rid = None
        # LRU stamp for _spill_resident_stream's victim choice: a
        # re-admission (restored or recompute-folded) counts as RECENT
        # use, so the same stream is not immediately re-parked; the
        # token watermark starts its anti-thrash residency quantum
        req._admit_seq = self._admit_seq
        req._resident_base = len(req.out_tokens)
        self._admit_seq += 1
        return True

    def _restore_victim(self, req: _Request, slot: int,
                        pages: List[int], ent) -> None:
        """host->device restore of a spilled preemption victim: the
        saved page contents scatter into the freshly-mapped suffix
        pages (bit-identical round trip) and the slot's mirrors resume
        at the spilled frontier — the next decode step samples exactly
        the token an uninterrupted run would have. Sets _kv_restored
        so the admission path skips the recompute prefill. ent: the
        validated entry _alloc_slot_pages already popped from the
        host tier."""
        from cake_tpu.kv.host_tier import HostTier
        if self._faults is not None:
            # an injected install fault propagates into the iteration
            # failure — the recovery path resubmits the victim through
            # the recompute fold (the entry was already popped)
            self._faults.check("host_tier.install",
                               step=self.stats.steps)
        self.cache = HostTier.install_pages(self.cache, pages,
                                            ent.arrays)
        self._temp[slot] = req.temperature
        self._top_p[slot] = req.top_p
        self._penalty[slot] = req.repeat_penalty
        self._prime_ring(slot, list(req.prime_tokens)
                         + list(req.out_tokens))
        self._pos[slot] = ent.pos
        self._last_tok[slot] = ent.last_tok
        self.stats.kv_restores += 1
        req._kv_restored = True
        self.tracer.span(req.rid, "kv_restored", pages=ent.n_pages)
        log.debug("restored rid=%d from the host tier (%d pages, "
                  "pos %d)", req.rid, ent.n_pages, ent.pos)

    def _capture_shipment(self, req: _Request) -> None:
        """Disaggregated PREFILL host (engine thread, inside _emit's
        retirement, before _release_slot_pages frees the row): fetch
        the pages holding the prompt's KV — raw pool slices, scale
        sidecars included, dtype-blind — and hand a Shipment to the
        request's ship_sink. Failure hands None: the decode peer
        degrades to local prefill, so this must never raise."""
        from cake_tpu.kv.host_tier import HostTier, pool_dtype_name
        from cake_tpu.kv.transfer import Shipment
        ship = None
        try:
            if self._faults is not None:
                # inside the try: an injected ship fault degrades to
                # the peer's local prefill, like a real fetch failure
                self._faults.check("kv.ship", step=self.stats.steps)
            if not self.paged or not req.out_tokens:
                raise ValueError("nothing to ship (unpaged or no "
                                 "first token)")
            row = self._slot_pages.get(req.slot) or []
            P = self._pager.page_size
            n_tokens = len(req.prompt_ids)
            n_written = -(-n_tokens // P)
            if n_written > len(row):
                raise ValueError(
                    f"slot row holds {len(row)} pages; prompt needs "
                    f"{n_written}")
            pages = row[:n_written]
            ship = Shipment(
                epoch=0,   # stamped by the plane with the PEER's epoch
                dtype=pool_dtype_name(self.cache),
                page_size=P, n_tokens=n_tokens, n_written=n_written,
                first_tok=int(req.out_tokens[0]), pages=list(pages),
                arrays=HostTier.fetch_pages(self.cache, pages),
                handoff={
                    # the journal admit/emit schema's fields — what the
                    # decode host needs to adopt the stream
                    "rid": req.rid, "prompt_len": n_tokens,
                    "max_new_tokens": req.max_new_tokens,
                    "temperature": req.temperature,
                    "top_p": req.top_p,
                    "repeat_penalty": req.repeat_penalty,
                    "priority": req.priority,
                    "first_lp": float(req.out_logprobs[0])
                    if req.out_logprobs else 0.0,
                })
            self.stats.kv_ships += 1
            self.tracer.span(req.rid, "kv_shipped", pages=n_written)
        except Exception:  # noqa: BLE001 — shipping is best-effort
            log.exception("kv shipment capture failed rid=%d; peer "
                          "will prefill locally", req.rid)
            ship = None
        try:
            req.ship_sink(ship)
        except Exception:  # noqa: BLE001 — never raise into _emit
            log.exception("ship_sink failed rid=%d", req.rid)

    def _adopt_install(self, req: _Request, slot: int, ent) -> bool:
        """Disaggregated DECODE host (engine thread, from _do_prefill/
        _mixed_admit after the row is allocated): install the shipped
        pages into the slot's freshly-mapped row and resume the stream
        at the shipped frontier — mirrors _restore_victim, with the
        peer-sampled first token emitted verbatim. False = refused
        (stale epoch, geometry drift, injected fault): the caller
        falls through to whole-prompt local prefill, which rewrites
        the row's pages and scales — the documented degradation."""
        from cake_tpu.kv.host_tier import HostTier, pool_dtype_name
        from cake_tpu.kv.transfer import note_adopt
        outcome = "fault"
        try:
            if self._faults is not None:
                self._faults.check("kv.adopt", step=self.stats.steps)
            if ent.epoch != self.config_epoch:
                outcome = "epoch"
                raise ValueError(
                    f"shipment config epoch {ent.epoch} != engine "
                    f"epoch {self.config_epoch} (reconfigured while "
                    "the shipment flew)")
            pool_dt = pool_dtype_name(self.cache)
            row = self._slot_pages.get(slot) or []
            if (ent.page_size != self._pager.page_size
                    or ent.dtype != pool_dt
                    or ent.n_tokens != len(req.prompt_ids)
                    or ent.n_written > len(row)):
                outcome = "geometry"
                raise ValueError(
                    f"shipment geometry (page_size={ent.page_size}, "
                    f"dtype={ent.dtype}, n_tokens={ent.n_tokens}, "
                    f"n_written={ent.n_written}) does not fit this "
                    f"pool (page_size={self._pager.page_size}, "
                    f"dtype={pool_dt}, row={len(row)} pages)")
            self.cache = HostTier.install_pages(
                self.cache, row[:ent.n_written], ent.arrays)
        except Exception:  # noqa: BLE001 — adoption is best-effort
            note_adopt(outcome)
            log.exception("kv adoption refused rid=%d; degrading to "
                          "local prefill", req.rid)
            return False
        self._temp[slot] = req.temperature
        self._top_p[slot] = req.top_p
        self._penalty[slot] = req.repeat_penalty
        self._prime_ring(slot, list(req.prime_tokens)
                         + [ent.first_tok])
        self._pos[slot] = ent.n_tokens
        self._last_tok[slot] = ent.first_tok
        self.stats.kv_adopts += 1
        note_adopt("adopted")
        self.tracer.span(req.rid, "kv_adopted", pages=ent.n_written)
        if self.events is not None:
            self.events.publish("kv_adopted", rid=req.rid,
                                pages=ent.n_written, dtype=ent.dtype)
        # the peer's first token emits verbatim — identity with the
        # colocated engine is by construction, and the stream's SSE
        # starts here, not after a local re-prefill
        self._emit(req, ent.first_tok,
                   logprob=float(ent.handoff.get("first_lp", 0.0)))
        return True

    def _spill_cold_prefixes(self, n_pages_needed: int,
                             keep_pid=None) -> int:
        """Spill least-recently-hit COLD prefixes (every page at
        refcount 1 — only the registry holds them) to the host tier
        until n_pages_needed device pages are freed, skipping keep_pid
        (the admission's own matched prefix). Engine thread only.
        Returns the number of pages freed."""
        if self._host_tier is None or n_pages_needed <= 0:
            return 0
        from cake_tpu.kv.host_tier import SpilledPages
        with self._rid_lock:
            entries = list(self._prefixes.items())
        entries.sort(
            key=lambda kv: self._prefix_last_hit.get(kv[0], 0.0))
        freed = 0
        for pid, (p_ids, pages, _extra) in entries:
            if freed >= n_pages_needed:
                break
            if pid == keep_pid or pages is None:
                continue
            if any(self._pager.refcount(p) != 1 for p in pages):
                continue          # hot: some slot maps these pages
            if not self._host_tier.can_hold(len(pages)):
                continue
            try:
                if self._faults is not None:
                    self._faults.check("host_tier.fetch",
                                       step=self.stats.steps)
                arrays = self._host_tier.fetch_pages(self.cache, pages)
            except Exception:  # noqa: BLE001 — spill is optional
                log.exception("cold prefix spill failed (pid=%d)", pid)
                continue
            if not self._host_tier.put(
                    ("prefix", pid),
                    SpilledPages(n_pages=len(pages), arrays=arrays,
                                 kind="prefix")):
                continue
            with self._rid_lock:
                self._prefixes[pid] = (p_ids, None, ("prefix", pid))
            self._pager.release(pages)
            self.stats.kv_spills += 1
            freed += len(pages)
            log.debug("spilled cold prefix %d (%d pages) to the host "
                      "tier", pid, len(pages))
        return freed

    def _restore_prefix(self, pid: int) -> Optional[List[int]]:
        """host->device restore of a spilled prefix: allocate fresh
        pool pages, scatter the saved contents back, and re-point the
        registry entry. None when the host entry was LRU-evicted (the
        prefix is gone — unregister it so matches stop) or the pool
        has no room right now (entry kept; the hit degrades to a
        whole-prompt prefill for this admission)."""
        if self._host_tier is None:
            return None
        from cake_tpu.kv.host_tier import HostTier
        ent = self._host_tier.peek(("prefix", pid))
        with self._rid_lock:
            entry = self._prefixes.get(pid)
        if entry is None:
            if ent is not None:
                self._host_tier.drop(("prefix", pid))
            return None
        if ent is None:
            # evicted from the host tier: the prefix exists nowhere —
            # drop the registration (auto-prefix re-registers its head
            # on the next matching request, the stale-pid heal path)
            with self._rid_lock:
                self._prefixes.pop(pid, None)
            return None
        pages = self._pager.alloc(ent.n_pages * self._pager.page_size)
        if pages is None:
            return None
        if self._faults is not None:
            self._faults.check("host_tier.install",
                               step=self.stats.steps)
        ent = self._host_tier.pop(("prefix", pid))
        self.cache = HostTier.install_pages(self.cache, pages,
                                            ent.arrays)
        with self._rid_lock:
            self._prefixes[pid] = (entry[0], pages, None)
        self._prefix_last_hit[pid] = time.monotonic()
        self.stats.kv_restores += 1
        log.debug("restored prefix %d from the host tier (%d pages)",
                  pid, ent.n_pages)
        return pages

    def _requeue_for_pages(self, req: _Request, slot: int,
                           starved: bool) -> bool:
        self._slot_req[slot] = None
        req.slot = -1
        self._page_starved = True
        if starved and getattr(self, "_page_blocked_rid", None) is None:
            self._page_blocked_rid = req.rid
        if self._slo:
            # requeue (not cancel+submit): seniority survives, so the
            # aging score keeps counting from the original admission
            ok = self.scheduler.requeue(
                req.rid, len(req.prompt_ids) + len(req.out_tokens),
                req.max_new_tokens - len(req.out_tokens))
        else:
            # folded shape, like the requeue above: a parked
            # decode-resident stream (_spill_resident_stream) can be
            # page-starved at RE-admission — resubmitting its original
            # budget would let the scheduler grant max_new_tokens on
            # top of what it already generated
            self.scheduler.cancel(req.rid)
            ok = self.scheduler.submit(
                req.rid, len(req.prompt_ids) + len(req.out_tokens),
                req.max_new_tokens - len(req.out_tokens))
        if not ok:
            req.error = RuntimeError(
                "kv page pool exhausted and admission queue full")
            self._requests.pop(req.rid, None)
            if getattr(self, "_page_blocked_rid", None) == req.rid:
                self._page_blocked_rid = None
            if self._host_tier is not None:
                self._host_tier.drop(("victim", req.rid))
            self._journal_retire(req, "error", error=str(req.error))
            self.tracer.finish(req.rid, "error", error=str(req.error))
            req.done.set()
        else:
            self.tracer.span(req.rid, "requeued")
            if starved and self._slo and self._preemption:
                # note the starved class for the TOP of the next
                # iteration: preempting mid-wave would leave the
                # already-planned decode rows writing through a
                # released page-table row
                r = CLASS_RANK[req.priority]
                cur = self._pending_page_preempt
                self._pending_page_preempt = (r if cur is None
                                              else min(cur, r))
        return False

    def _live_decode_rows(self, decode_plan):
        """Re-validate a decode plan after a mid-wave resident spill:
        plan() ran before admissions, so a slot parked by
        _spill_resident_stream may still carry a planned decode row —
        pointing at pages already released (and possibly re-allocated
        to the admission that triggered the park)."""
        self._resident_parked = False
        live = []
        for rid, slot in decode_plan:
            req = self._slot_req[slot]
            if req is not None and req.rid == rid:
                live.append((rid, slot))
        return live

    @engine_thread_only
    def _spill_resident_stream(self, exclude_rid: int) -> bool:
        """Decode-resident spill — oversubscribe the KV pool like
        virtual memory: when admission would be refused even after
        cold-prefix spills, park the LEAST-RECENTLY-ADMITTED decoding
        stream's owned suffix pages in the host tier and requeue it.
        The victim resumes through the same two paths a preemption
        victim does (_restore_victim when its pages round-trip, the
        fold-tokens-into-prompt recompute otherwise), so its token
        stream is identical to an uninterrupted run. Returns True when
        a stream was parked (its pages are now free), False when no
        candidate qualifies — callers retry the allocation per park.

        Candidates come from _cur_decode (this iteration's planned
        decode rows), NEVER same-wave admissions: a re-admitted
        preemption victim earlier in this prefill wave has out_tokens
        but its prefill may still be in flight on device."""
        if (self._host_tier is None
                or not getattr(self._sched_cfg, "spill_resident", True)):
            return False
        quantum = getattr(self._sched_cfg, "resident_quantum", 8)
        best = None
        for slot, rid in self._cur_decode.items():
            req = (self._slot_req[slot]
                   if 0 <= slot < self.max_slots else None)
            if (req is None or req.rid != rid or req.rid == exclude_rid
                    or req.done.is_set() or slot in self._mixed_pending
                    or not req.out_tokens
                    or req.max_new_tokens - len(req.out_tokens) <= 0):
                continue
            # anti-thrash: the victim must have USED its residency —
            # quantum-sized time-slices, not one-token ping-pong
            if (len(req.out_tokens)
                    - getattr(req, "_resident_base", 0) < quantum):
                continue
            own = (self._slot_pages.get(slot)
                   or [])[self._slot_prefix_pages.get(slot, 0):]
            # FREE capacity only — a park must never LRU-evict an
            # existing entry: a spilled prefix is its only copy (an
            # eviction unregisters it), and evicting another parked
            # stream just trades one recompute for another
            if not own or len(own) > self._host_tier.free_pages:
                continue
            seq = getattr(req, "_admit_seq", 0)
            if best is None or seq < best[0]:
                best = (seq, rid, slot)
        if best is None:
            return False
        _, rid, slot = best
        req = self._slot_req[slot]
        remaining = req.max_new_tokens - len(req.out_tokens)
        if self._slo:
            # seniority survives (the _preempt_slot discipline): the
            # parked stream keeps aging from its original admission
            if not self.scheduler.requeue(
                    rid, len(req.prompt_ids) + len(req.out_tokens),
                    remaining):
                return False
        else:
            # resubmit as it will RE-prefill: generated tokens folded
            # into the prompt, budget reduced to the remainder — the
            # scheduler retires on ITS budget count, so the original
            # max_new here would let the stream over-generate
            self.scheduler.cancel(rid)
            if not self.scheduler.submit(
                    rid, len(req.prompt_ids) + len(req.out_tokens),
                    remaining):
                # admission queue full: the victim has nowhere to wait
                # — it errors exactly like a page-starved admission
                # with a full queue (_requeue_for_pages), and its
                # pages still come back to the pool
                self._slot_req[slot] = None
                req.slot = -1
                self._release_slot_pages(slot)
                self._resident_parked = True
                req.error = RuntimeError(
                    "kv page pool exhausted and admission queue full")
                self._requests.pop(rid, None)
                self._journal_retire(req, "error", error=str(req.error))
                self.tracer.finish(rid, "error", error=str(req.error))
                req.done.set()
                return True
        from cake_tpu.kv.host_tier import note_resident_spill
        self._slot_req[slot] = None
        req.slot = -1
        spilled = self._spill_victim_pages(req, slot)
        self._release_slot_pages(slot)
        self._resident_parked = True
        self.stats.kv_resident_spills += 1
        note_resident_spill()
        self.tracer.span(rid, "resident_spilled",
                         generated=len(req.out_tokens), spilled=spilled)
        if self.events is not None:
            self.events.publish("resident_spilled", rid=rid,
                                generated=len(req.out_tokens),
                                spilled=spilled)
        log.debug("parked decode-resident rid=%d (%d tokens %s)", rid,
                  len(req.out_tokens),
                  "spilled to the host tier" if spilled
                  else "fold into the prompt")
        return True

    def _prefill_admit(self, rid: int, slot: int):
        """The head of every admission (the `schedule` phase), dense
        (_do_prefill) and paged (_mixed_admit): bind the slot, fold a
        preempted request's tokens into its prompt, name the failure
        blast radius. Returns (req, t0, ids, prime), or None when the
        request was cancelled. Whatever needs pages (allocation, the
        host tier's restore, a shipped prefill's adoption) lives in
        _mixed_admit."""
        req = self._requests.get(rid)
        if req is None:  # cancelled between plan and here
            self.scheduler.cancel(rid)
            return None
        # the step record being put together is the boundary that
        # admits this request (/api/v1/requests joins /api/v1/steps)
        self.tracer.prefill_start(rid, step=self.flight.next_step)
        t0 = time.perf_counter()
        req.slot = slot
        self._slot_req[slot] = req
        ids = req.prompt_ids
        prime = req.prime_tokens
        if req.out_tokens:
            # preempted-and-requeued request (tokens exist before this
            # prefill only via preemption): recompute-style resume —
            # the generated tokens fold into the prompt and the
            # penalty ring reconstructs over the whole transcript,
            # exactly the checkpoint-resume fold (serve/checkpoint
            # .resume), so the re-prefill leaves cache and sampling
            # state as an uninterrupted run would have them
            ids = list(req.prompt_ids) + list(req.out_tokens)
            prime = list(req.prime_tokens) + list(req.out_tokens)
        # this admission is the failure blast radius from here on; the
        # fault site carries the prefill length so match_len= rules can
        # target one request's prefill (the poison-request drill)
        self._implicated = ((rid, slot),)
        if self._faults is not None:
            self._faults.check("engine.prefill", step=self.stats.steps,
                               n_tokens=len(ids))
        return req, t0, ids, prime

    def _do_prefill(self, rid: int, slot: int, defer: bool = False):
        """Prefill one admission of a DENSE engine (slot, ring or
        pipelined cache). A paged engine reaches neither this nor any
        helper below it (_prefill_device, _prefixed_prefill_device,
        _prefill_chunked, _do_prefill_batch; only the admission head
        _prefill_admit is shared): its prompts ride _do_mixed.
        defer=False: dispatch, fetch, emit — the multi-host lockstep
        path. defer=True: dispatch only; returns
        (req, t0, slot, dev) for _do_prefill_batch, which fetches every
        admission's first token in ONE host round-trip (a per-admission
        fetch waits for the device once per request — it adds up in
        TTFT when a wave of requests arrives together)."""
        with self.flight.span("schedule"), self.flight.part("admit_pages"):
            admitted = self._prefill_admit(rid, slot)
        if admitted is None:
            return None
        self.flight.admitted()
        req, t0, ids, prime = admitted
        hit = (self._match_and_validate_prefix(ids)
               if self._prefix_capable else None)
        n_top = self._n_top_for([slot])
        if hit is not None:
            hit_pid, entry = hit
            # the follower resolves the pid in ITS registry (mirrored by
            # register_prefix ops — wire ordering guarantees presence)
            # and re-derives the window plan from shared config —
            # identical dispatch on every process
            self._publish({
                "op": "prefill_prefixed", "pid": hit_pid, "ids": ids,
                "slot": slot, "temp": req.temperature,
                "top_p": req.top_p, "penalty": req.repeat_penalty,
                "prime": list(prime), "n_top": n_top,
            })
            out = self._prefixed_prefill_device(
                hit_pid, ids, slot, req.temperature, req.top_p,
                req.repeat_penalty, prime, n_top=n_top,
                entry=entry, defer=defer)
            self.stats.prefix_hits += 1
            if self.events is not None:
                self.events.publish("prefix_hit", rid=rid, pid=hit_pid,
                                    tokens_saved=len(entry[0]))
        else:
            # covers whole-prompt AND chunked prefill — _prefill_device
            # picks between them from (prefill_chunk, len) alone, the
            # same deterministic rule a multi-host follower applies to
            # this published op
            self._publish({
                "op": "prefill", "ids": ids, "slot": slot,
                "temp": req.temperature, "top_p": req.top_p,
                "penalty": req.repeat_penalty,
                "prime": list(prime), "n_top": n_top,
            })
            out = self._prefill_device(
                ids, slot, req.temperature, req.top_p,
                req.repeat_penalty, prime, n_top=n_top,
                defer=defer)
        if defer:
            return (req, t0, slot, out)
        tok, lp, top = out
        dt = time.perf_counter() - t0
        self.stats.prefill_time_s += dt
        self._record_step("prefill", rows=1, tokens=1, wall_s=dt,
                          rids=(rid,))
        with self.flight.span("emit"):
            self._emit(req, tok, logprob=lp, top=top)
        return None

    # admissions per first-token fetch in _do_prefill_batch: a fetch
    # costs one host round-trip — groups of 4 amortize it 4x while
    # early arrivals in a big wave still stream their first token
    # after ~4 prefills instead of after the whole wave (p50 TTFT)
    PREFILL_FLUSH = 4

    @engine_thread_only
    def _do_prefill_batch(self, prefill_plan) -> None:
        """Admit a wave of requests with one first-token fetch per
        PREFILL_FLUSH admissions: each group's prefills + first-token
        samples are dispatched back to back (the device chains them
        through the donated cache), then a single jax.device_get
        collects the group's first tokens. Single-host only — a
        follower replays per-admission ops synchronously."""
        pend = []
        pend_js = []   # each admission's _JitStep, in pend order

        def flush():
            # the whole GROUP is the failure blast radius: a deferred
            # prefill error (dispatched async above) materializes at
            # this device_get, after later admissions overwrote the
            # per-admission _implicated — without this, an organic
            # poison prefill would charge its crash to whichever
            # admission happened to defer last
            self._implicated = tuple(
                (req.rid, slot) for (req, _t0, slot, _dev) in pend)
            with self.flight.span("fetch"):
                hosts = jax.device_get([dev for (_, _, _, dev) in pend])
            # one wall-clock interval per GROUP: the admissions overlap
            # (dispatched back to back, fetched together), so summing
            # per-request spans would count the same wall time up to
            # PREFILL_FLUSH times
            dt = time.perf_counter() - pend[0][1]
            self.stats.prefill_time_s += dt
            # one record per admission GROUP (per-admission walls would
            # multi-count the overlap), with the group's SUMMED FLOPs /
            # bytes over the group wall — and a compile anywhere in the
            # group flags the record (a single admission's js would hide
            # the other members' costs and compiles)
            flops = sum(js.cost.flops for js in pend_js
                        if js is not None and js.cost is not None)
            nbytes = sum(js.cost.bytes_accessed for js in pend_js
                         if js is not None and js.cost is not None)
            cost = (obs_steps.CostInfo(flops=flops, bytes_accessed=nbytes)
                    if flops or nbytes else None)
            with self.flight.span("record"):
                self.flight.record(
                    "prefill", rows=len(pend), tokens=len(pend), wall_s=dt,
                    cost=cost,
                    compiled=any(js is not None and js.new
                                 for js in pend_js),
                    rids=[req.rid for (req, _t0, _s, _d) in pend])
            with self.flight.span("emit"):
                for (req, t0, slot, _), host in zip(pend, hosts):
                    tok, lp, top = self._finish_prefill_complete(slot,
                                                                 host)
                    self._emit(req, tok, logprob=lp, top=top)
            pend.clear()
            pend_js.clear()

        for rid, slot in prefill_plan:
            p = self._do_prefill(rid, slot, defer=True)
            if p is not None:
                pend.append(p)
                pend_js.append(self._last_jit)
                self._last_jit = None
            if len(pend) >= self.PREFILL_FLUSH:
                flush()
        if pend:
            flush()

    # -- token-level continuous batching (the paged engine) --------------

    def _prime_ring(self, slot: int, prime) -> None:
        """Reset one slot's repeat-penalty ring + step counter, seeding
        it from `prime` (checkpoint resume / preemption fold): each
        prior token at its true step index and the counter continuing
        from there, so subsequent writes land where they always would."""
        self._ring = self._ring.at[slot].set(-1)
        self._steps[slot] = 0
        if prime:
            N = self._ring.shape[1]
            row = np.full(N, -1, np.int32)
            start = max(0, len(prime) - N)
            for i, t in enumerate(prime[start:], start=start):
                row[i % N] = t
            self._ring = self._ring.at[slot].set(jnp.asarray(row))
            self._steps[slot] = len(prime)

    @engine_thread_only
    def _do_mixed(self, prefill_plan, decode_plan) -> None:
        """One engine iteration of token-level continuous batching:
        admissions map their pages and join the VERY NEXT device step
        as prefill-chunk rows alongside the decode rows — no
        alternating prefill-then-decode phases, so the MXU sees one
        well-occupied mixed launch instead of two under-occupied ones.

        decode_scan interaction (the K-step-burst admission-delay fix):
        the decode programs run only while NO prompt is mid-prefill,
        and chain (one step in flight, or K-step scan bursts) only
        while nobody waits in the queue (_host_attention,
        _scan_steps_for's queue gate); the moment a request is
        admitted, the loop falls back to single mixed steps so its
        chunks ride every step instead of stalling behind a K-token
        scan burst. Those too are kept one in flight (_mixed_burst)."""
        if prefill_plan:
            with self.flight.span("schedule"):
                for rid, slot in prefill_plan:
                    self._mixed_admit(rid, slot)
        if not self._mixed_pending:
            # pure decode: the decode programs are strictly cheaper
            # here (C=1 step kept in flight, K-step scan bursts) and no
            # admission is waiting on a step boundary
            if decode_plan and self._resident_parked:
                # an admission above parked a decode-resident slot
                # (_spill_resident_stream): drop its stale row before
                # the device step (_mixed_burst re-validates per
                # row; the decode programs do not)
                decode_plan = self._live_decode_rows(decode_plan)
            if decode_plan and self._specp is not None:
                # spec rows ride one batched draft+verify round; rows
                # the partition leaves behind (prefill frontier, page
                # pressure, sampling options, window cap, degraded)
                # fall through to the plain decode paths below
                decode_plan = self._do_spec_paged(decode_plan)
            if decode_plan:
                self._decode_rows(decode_plan, chain=not prefill_plan)
            return
        self._mixed_burst(decode_plan)

    def _mixed_admit(self, rid: int, slot: int) -> None:
        """The paged engine's one admission: after the shared head
        (_prefill_admit) match a prefix, allocate pages (or restore
        them from the host tier, or adopt a shipped prefill), set up
        the sampling state — and NO device dispatch: the prompt's
        windows ride the next mixed step(s) as chunk rows. Two parts of
        the `schedule` span: admit_pages (everything up to the row's
        pages), admit_ring (the sampling state and the ring's eager
        device launches)."""
        with self.flight.part("admit_pages"):
            ready = self._mixed_admit_pages(rid, slot)
        if ready is None:
            return
        req, ids, prime, off = ready
        with self.flight.part("admit_ring"):
            self._temp[slot] = req.temperature
            self._top_p[slot] = req.top_p
            self._penalty[slot] = req.repeat_penalty
            self._prime_ring(slot, prime)
        self._pos[slot] = off
        self._mixed_pending[slot] = {"req": req, "ids": ids, "off": off}

    def _mixed_admit_pages(self, rid: int, slot: int):
        """_mixed_admit up to the row's pages. Returns (req, ids, prime,
        the offset its windows start at), or None where no prompt is
        left to prefill: cancelled, requeued for pages, restored from
        the host tier or adopted at its decode frontier."""
        admitted = self._prefill_admit(rid, slot)
        if admitted is None:
            return None
        req, _t0, ids, prime = admitted
        # shipped-prefill adoption (disaggregated decode host): a
        # staged shipment replaces BOTH the prefix match and the local
        # compute — the peer's pages hold the whole prompt, so the row
        # allocates unshared. PEEK only here: the entry must survive a
        # pool-exhausted requeue; it pops after the row exists.
        with self._rid_lock:
            adopt = self._adopt_store.get(rid)
        # match BEFORE page admission: a prefix hit changes the
        # allocation itself (suffix + budget pages only, prefix pages
        # mapped shared)
        hit = (self._match_and_validate_prefix(ids)
               if self._prefix_capable and adopt is None else None)
        if not self._alloc_slot_pages(req, slot, hit):
            return None   # pool exhausted: requeued (or failed) inside
        self.flight.admitted()
        hit = req._effective_hit       # spilled-prefix restore failure
        if getattr(req, "_kv_restored", False):
            # spilled preemption victim restored from the host tier:
            # KV and sampling state already sit at the preemption
            # frontier (the token that recompute-resume would
            # re-derive was already emitted) — the slot resumes
            # mid-decode and must NOT ride the next mixed step as a
            # chunk row
            req._kv_restored = False
            return None
        if adopt is not None:
            with self._rid_lock:
                self._adopt_store.pop(rid, None)
            if not req.out_tokens \
                    and self._adopt_install(req, slot, adopt):
                # the slot resumes as a DECODE row from the shipped
                # frontier — it must not also ride as a chunk row
                return None
            # refused (stale epoch / geometry / injected fault): fall
            # through — local prefill rewrites the row's pages and
            # scales, the documented degradation
        off = 0
        if hit is not None:
            # shared prefix pages already mapped at the row head
            # (_alloc_slot_pages): the windows start AFTER them
            off = len(hit[1][0])
            self.stats.prefix_hits += 1
            _PREFIX_PAGED_HITS.inc()
            _PREFIX_TOKENS_SAVED.inc(off)
            if self.events is not None:
                self.events.publish("prefix_hit", rid=req.rid,
                                    pid=hit[0], tokens_saved=off)
        return req, ids, prime, off

    def _run_mixed_step(self, step, carry, n_tokens: int) -> tuple:
        """Dispatch the sampled mixed step program of size n_tokens
        (make_mixed_sampled) on the packed step the host built and a
        carry, through the compile accountant; the cache, the keys and
        the ring are donated and replaced. carry None: a stretch's
        first step, which no row reads a carry in. Returns ((tokens,
        logprobs, top ids, top logprobs, [a sparse model's counters])
        on the device, the carry)."""
        if carry is None:
            zero = np.zeros(self.max_slots, np.int32)
            carry = tuple(self._held("carry." + name, a) for name, a in (
                ("tok", zero), ("pos", zero), ("steps", zero),
                ("live", zero != 0)))
        fargs = (self.params, jnp.asarray(step), self.cache, self.rope,
                 self.config, self._keys, self._ring,
                 self._held("temp", self._temp),
                 self._held("top_p", self._top_p),
                 self._held("penalty", self._penalty), carry)
        # one n_top form: the cap's top ids always, dropped on the host
        # for the rows that did not ask
        kw = {"n_tokens": n_tokens, "top_k": self.defaults.top_k,
              "n_top": self.n_top}
        js = self._obs_jit("mixed_step", (step.shape[1] - 4, n_tokens),
                           self._mixed_step_fn, fargs, kw)
        # the launch alone: the staging of the step, the options and a
        # first carry lies above it in the `dispatch` span
        with self.flight.part("launch"):
            (nxt, lp, tids, tlps, self.cache, self._keys, self._ring,
             carry, *moe) = self._mixed_step_fn(*fargs, **kw)
        # a step of several dispatches compiled if any of them did
        js.new |= self._last_jit is not None and self._last_jit.new
        self._last_jit = js
        return (nxt, lp, tids, tlps, moe), carry

    def _attn_q_tiles(self, qlen, rows: List[int]) -> dict:
        """A mixed step's attn_q_tiles / attn_q_tiles_window (obs/steps):
        the query tiles the mixed attention kernel folds for the
        step's active rows, counted from their q_len as the kernel
        does, and the tiles of their whole windows. Nothing where the
        rows do not go through that kernel as they are (the family's
        kernel_rows)."""
        if "mixed" not in self._family.kernel_rows:
            return {}
        from cake_tpu.ops.ragged_paged_attention import mixed_q_tiles
        C = self._mixed_chunk
        return {"attn_q_tiles": sum(mixed_q_tiles(int(qlen[slot]), C)
                                    for slot in rows),
                "attn_q_tiles_window": len(rows) * mixed_q_tiles(C, C)}

    def _window_pages(self, pos, qlen, groups) -> dict:
        """A mixed record's window_pages / window_folds (obs/steps):
        what a query tile of the latent window kernel walks, over the
        step's dispatches and the layers that run it. A dispatch's
        window is the row with the most tokens, as its program picks
        it (the first of them; a dispatch of single tokens runs the
        kernel over that row all the same). Nothing where the family
        has no such kernel."""
        if self._window_walk is None:
            return {}
        pages = folds = 0
        for rows in groups:
            n = np.where(rows, qlen, 0)
            row = int(np.argmax(n))
            walked = self._window_walk(int(pos[row]) + max(int(n[row]), 1)
                                       - 1)
            pages, folds = pages + walked[0], folds + walked[1]
        return {"window_pages": pages, "window_folds": folds}

    def _mixed_walk_of(self, family):
        """A dispatch's rows -> (pages, table entries, folds) of its
        layer's cake_mixed_attn call, as the host can know them: the
        family's own form of the call (Family.mixed_attn_walk: the
        dispatch's one window in entries), or every slot as it is where
        the rows go through the kernel so (kernel_rows), at the pages a
        fold the kernel takes for these shapes. None where neither
        holds."""
        if family.mixed_attn_walk is not None:
            window = family.mixed_attn_walk(self.config, self.cache,
                                            self._mixed_chunk)

            def walk(pos, n):
                # the dispatch's one window: the row with the most
                # tokens, if it holds more than one
                row = int(np.argmax(n))
                return window(int(pos[row]), int(n[row]) if n[row] > 1 else 0)

            return walk
        if "mixed" not in family.kernel_rows:
            return None
        from cake_tpu.ops import ragged_paged_attention as rpa
        c, P = self.config, self.cache.page_size
        max_pages = self.cache.max_pages
        block = rpa.mixed_block(
            P, c.num_attention_heads, c.num_key_value_heads, c.head_dim,
            self._mixed_chunk, max_pages,
            jnp.dtype(self.params["embed"].dtype).itemsize,
            1 if self.kv_quant else jnp.dtype(self._pool_dtype).itemsize)

        def walk(pos, n):
            walked = [rpa.mixed_walk(int(p), int(q), P, max_pages, block)
                      for p, q in zip(pos, n)]
            return (sum(w[0] for w in walked), len(n) * max_pages,
                    sum(w[1] for w in walked))

        return walk

    def _mixed_attn_pages(self, pos, qlen, groups) -> dict:
        """A mixed record's mixed_attn_pages / mixed_attn_pages_table /
        mixed_attn_folds (obs/steps): what the mixed attention kernel
        walks a layer over the step's dispatches, counted from the
        positions dispatched as the kernel counts its trips
        (rpa.mixed_walk). Nothing where the family gives the host no
        way to know its call."""
        if self._mixed_attn_walk is None:
            return {}
        pages = table = folds = 0
        for rows in groups:
            walked = self._mixed_attn_walk(pos, np.where(rows, qlen, 0))
            pages, table, folds = (pages + walked[0], table + walked[1],
                                   folds + walked[2])
        return {"mixed_attn_pages": pages, "mixed_attn_pages_table": table,
                "mixed_attn_folds": folds}

    def _mla_decode_pages(self, positions) -> dict:
        """A record's mla_decode_pages / mla_decode_folds (obs/steps):
        what its single-token rows, at `positions`, walk through the
        latent page kernel over the layers that run it, and the softmax
        updates those pages take (the family's decode_walk). Nothing
        where the family has no such kernel."""
        if self._decode_walk is None:
            return {}
        walked = [self._decode_walk(int(pos)) for pos in positions]
        return {"mla_decode_pages": sum(w[0] for w in walked),
                "mla_decode_folds": sum(w[1] for w in walked)}

    def _attn_pages(self, steps: List[tuple]) -> dict:
        """A decode record's attn_pages / attn_pages_table (obs/steps):
        the KV pages the decode attention kernel streams a layer for
        the active rows, counted from the positions dispatched as the
        kernel counts its trips (position // page + 1), and the entries
        of the page table, which is what a grid over (slot, page)
        stepped through; beside them what a latent family's page kernel
        walks (_mla_decode_pages). steps: (position of its first token,
        tokens) for each active row; a scan's record sums its steps.
        Nothing where the decode rows do not go through that kernel (a
        dense cache; the family's kernel_rows)."""
        if (not self.paged or not steps
                or "decode" not in self._family.kernel_rows):
            return {}
        P = self.cache.page_size
        last = self.max_seq_len - 1
        at = [min(pos + i, last) for pos, n in steps for i in range(n)]
        return {"attn_pages": sum(pos // P + 1 for pos in at),
                "attn_pages_table": (max(n for _pos, n in steps)
                                     * self.max_slots
                                     * self.cache.max_pages),
                **self._mla_decode_pages(at)}

    def _mixed_groups(self, qlen) -> List[np.ndarray]:
        """The rows of a mixed step ([B] bool masks) by dispatch: slot
        order, as many as the largest packed size holds. One group
        unless three rows or more prefill at once (a family of one
        window a dispatch: two or more)."""
        budget = self._mixed_buckets[-1]
        windows = (len(qlen) if self._family.windows is Windows.FIT
                   else 1)
        groups, used, wide = [np.zeros(len(qlen), bool)], 0, 0
        for slot in np.flatnonzero(qlen):
            if (used + qlen[slot] > budget
                    or wide + (qlen[slot] > 1) > windows):
                groups.append(np.zeros(len(qlen), bool))
                used = wide = 0
            groups[-1][slot] = True
            used += int(qlen[slot])
            wide += int(qlen[slot] > 1)
        return groups

    def _warm_mixed_buckets(self) -> None:
        """Run the mixed step once at every packed size with all rows
        idle (an idle row touches neither pages nor output nor its
        key), so that whatever token counts arrive later, no step
        compiles or loads a program: the traffic's own warm-up cannot
        be relied on to touch every size. A chained step, on the carry
        the program returned, runs the same executable (one device,
        uncommitted arrays either way), and the program has one n_top
        form, so these runs are all its variants."""
        idle = np.zeros((self.max_slots, self._mixed_chunk + 4), np.int32)
        marks = [time.perf_counter()]
        # (the launch is a part of the `dispatch` span; these belong to
        # no step)
        with STARTUP.phase("warm_steps"), self.flight.span("dispatch"):
            for bucket in self._mixed_buckets:
                out, _carry = self._run_mixed_step(idle, None, bucket)
                marks.append(time.perf_counter())
        self.flight.discard_open()
        # where start-up first waits for the device: for these runs and
        # for all it launched before them, the weights' draw first
        with STARTUP.phase("weights_ready"):
            jax.block_until_ready(out)
        self._last_jit = None
        log.info("mixed step: sizes %s ready in %.2f s (traced and "
                 "loaded in %s s, then %.2f s for the device)",
                 self._mixed_buckets, time.perf_counter() - marks[0],
                 " + ".join(f"{b - a:.2f}" for a, b in zip(marks, marks[1:])),
                 time.perf_counter() - marks[-1])

    @engine_thread_only
    def _mixed_burst(self, decode_plan) -> None:
        """A stretch of mixed steps with one in flight. A step: every
        decode row contributes its last token (q_len=1), every
        mid-prefill slot its next window (q_len=n at its current
        offset); rows whose window ends their prompt sample their first
        token from the same launch the decode rows sample their next
        (the program samples: make_mixed_sampled). Step k+1 is
        dispatched BEFORE k is fetched: a decode row's token, position
        and step count come from k's carry on the device, a prompt's
        next window from the host, which knows it without k's result.
        Then ONE fetch of k's tokens and counters, its record, and its
        emit while k+1 runs. When the last prompt of the stretch has
        ended, the carry goes on into the sampled decode program
        (_decode_stretch), so the decode steps after it stay chained.

        The stretch ends as a decode stretch does (_decode_stretch's
        chain_break): when the host needs the loop back
        (_host_attention), after an emit in which a row
        finished (its slot is the planner's), before a row would pass
        max_seq_len, after STRETCH_STEPS dispatches. Rows join between
        stretches only (_mixed_admit). An engine that may not chain
        runs the same program and fetches it at once."""
        span = self.flight.span
        B, C = self.max_slots, self._mixed_chunk
        pending = self._mixed_pending
        # the stretch's rows, slot -> rid: the plan's decode rows (an
        # admission may have parked one: _spill_resident_stream) and
        # every mid-prefill slot (this iteration's admissions are in
        # no plan yet)
        rows_of = {slot: rid for rid, slot in decode_plan
                   if self._slot_req[slot] is not None
                   and self._slot_req[slot].rid == rid}
        rows_of.update((slot, p["req"].rid) for slot, p in pending.items())
        plan = [(rows_of[slot], slot) for slot in sorted(rows_of)]
        # the rids a record carries are those of the iteration the run
        # loop would have spent on the step: its plan, then every
        # mid-prefill slot
        planned = [rid for rid, _slot in decode_plan]
        # blast radius: every decode row AND every mid-prefill slot
        # rides these launches
        self._implicated = tuple(decode_plan) + tuple(
            (p["req"].rid, slot) for slot, p in pending.items())
        # tokens sampled by steps the host has not fetched, per slot
        # (_decode_stretch), and the stretch's dispatches
        shipped: dict = {}
        flying = _Flying()
        # a follower replays every step from its host mirrors; the
        # paged speculative engine's rows go back to its partition
        # after every step (_do_spec_paged)
        may_chain = not self._multihost and self._specp is None
        # what takes over when no prompt is left: the sampled decode
        # program on the same rows, if this engine keeps one in flight
        tail_dispatch, tail_complete, tail_chain_break = self._decode_stretch(
            plan, 1,
            (may_chain and self._decode_scan_impl is not None
             and self._decode_scan <= 1),
            shipped, flying)

        def chain_break(n_inflight) -> Optional[str]:
            if not pending:
                return tail_chain_break(n_inflight)
            # as _decode_stretch's gate, without its budget term (a
            # prompt's windows are work); a window's positions lie
            # inside its prompt
            if not may_chain:
                return "sync"
            if flying.sent >= STRETCH_STEPS:
                return "stretch_cap"
            if any(self._slot_req[s] is None for s in rows_of):
                return "row_finished"
            if any(self._pos[s] + shipped.get(s, 0) + 1 >= self.max_seq_len
                   for s in rows_of if s not in pending):
                return "window_end"
            return None

        def dispatch(state):
            if not pending:
                # the last window went with the step before: decode on
                # from its carry
                devs, state = tail_dispatch(state)
                return partial(tail_complete, devs), state
            if self._faults is not None:
                # once a step, as _decode_stretch's dispatch
                if flying.sent:
                    self._faults.check("engine.step",
                                       step=self.stats.steps)
                self._faults.check("engine.mixed", step=self.stats.steps)
            t_start = time.perf_counter()
            with span("build"):
                # the step, a row a slot: the window, then position,
                # q_len, step count and flags (make_mixed_sampled)
                step = np.zeros((B, C + 4), np.int32)
                tokens, pos, qlen, flags = (step[:, :C], step[:, C],
                                            step[:, C + 1], step[:, C + 3])
                step[:, C + 2] = self._steps
                # where the kernels find each row: the host's count of
                # their walk goes by it
                at = np.zeros(B, np.int64)
                decode_rows: List[int] = []
                for rid, slot in plan:
                    req = self._slot_req[slot]
                    if slot in pending or req is None or req.rid != rid:
                        continue
                    ahead = shipped.get(slot, 0)
                    if req.max_new_tokens - len(req.out_tokens) <= ahead:
                        continue    # its last token is in the step in flight
                    tokens[slot, 0] = self._last_tok[slot]
                    pos[slot] = min(self._pos[slot], self.max_seq_len - 1)
                    qlen[slot] = 1
                    flags[slot] = ROW_ACTIVE | ROW_SAMPLE | (
                        ROW_FROM_CARRY if ahead else 0)
                    # (the program reads a row's position off the carry:
                    # the mirror plus what the steps in flight ship)
                    at[slot] = min(self._pos[slot] + ahead,
                                   self.max_seq_len - 1)
                    decode_rows.append(slot)
                chunk_rows: List[int] = []
                finished: List[tuple] = []
                # (a dict keeps insertion order: the first key is the
                # prompt admitted first)
                for slot in ([next(iter(pending))]
                             if self._family.windows is Windows.STEP
                             else sorted(pending)):
                    p = pending[slot]
                    ids, off = p["ids"], p["off"]
                    n = min(C, len(ids) - off)
                    tokens[slot, :n] = ids[off:off + n]
                    pos[slot] = at[slot] = off
                    qlen[slot] = n
                    flags[slot] = ROW_ACTIVE
                    chunk_rows.append(slot)
                    if off + n >= len(ids):
                        flags[slot] |= ROW_SAMPLE
                        finished.append((slot, p["req"]))
                sampled = decode_rows + [slot for slot, _req in finished]
                groups = self._mixed_groups(qlen)
                rids = planned + [pending[slot]["req"].rid
                                  for slot in chunk_rows]
                tiles = {**self._attn_q_tiles(qlen,
                                              decode_rows + chunk_rows),
                         **self._window_pages(pos, qlen, groups),
                         **self._mla_decode_pages(
                             at[slot] for slot in np.flatnonzero(qlen == 1)),
                         **self._mixed_attn_pages(at, qlen, groups)}
            with span("dispatch"):
                # every layer runs over the step's tokens packed out of
                # their windows (paged.mixed_step_paged), at the smallest
                # size that holds them. A step over the largest size runs
                # in several dispatches, each over some of its rows and
                # sampling those: a row reads and writes its own pages,
                # key and ring only, so the rows of one step do not care
                # which of them share a program.
                t0d = time.perf_counter()
                self._last_jit = None
                outs, computed = [], 0
                for rows in groups:
                    size = mixed_bucket_for(self._mixed_buckets,
                                            int(qlen[rows].sum()))
                    out, state = self._run_mixed_step(
                        step if len(groups) == 1
                        else np.where(rows[:, None], step, 0), state, size)
                    outs.append((rows, out))
                    computed += size
                disp = time.perf_counter() - t0d
            js, self._last_jit = self._last_jit, None
            # the prefill frontiers, known without the step's result: a
            # row whose window ended its prompt is a decode row of the
            # next step, fed from the carry
            for slot in chunk_rows:
                p = pending[slot]
                p["off"] += int(qlen[slot])
                self._pos[slot] = p["off"]
            for slot, _req in finished:
                del pending[slot]
            for slot in sampled:
                shipped[slot] = shipped.get(slot, 0) + 1
            self.stats.steps += 1
            chained = flying.dispatched()
            # from the second step on the planner would list every row
            planned[:] = [rid for rid, _slot in plan]
            devs = (outs, decode_rows, chunk_rows, finished, sampled, rids,
                    int(qlen.sum()), computed, tiles, t_start, disp, js,
                    chained)
            return partial(complete, devs), state

        def complete(devs):
            (outs, decode_rows, chunk_rows, finished, sampled, rids, n_real,
             computed, tiles, t_start, disp, js, chained) = devs
            with span("fetch"):
                # ONE fetch: the sampled tuple and the counters of every
                # dispatch of the step
                got = jax.device_get([out for _rows, out in outs])
            wall = flying.fetched(t_start)
            nxt, lp, tids, tlps, _moe = got[0]
            for (rows, _out), (nxt_g, lp_g, tids_g, tlps_g, _m) in zip(
                    outs[1:], got[1:]):
                nxt, lp = np.where(rows, nxt_g, nxt), np.where(rows, lp_g, lp)
                tids = np.where(rows[:, None], tids_g, tids)
                tlps = np.where(rows[:, None], tlps_g, tlps)
            moe = [m for *_sampled, moe_g in got for m in moe_g]
            # split the step wall by TOKEN share so the prefill/decode
            # accounting stays meaningful under the mixed default (a mixed
            # step IS both phases in one launch; all-to-decode would report
            # prefill_time_s == 0 forever, and a per-row split would
            # undercount a C-token chunk against a 1-token decode row)
            pf = wall * (n_real - len(decode_rows)) / n_real
            self.stats.prefill_time_s += pf
            self.stats.decode_time_s += wall - pf
            waited = self.flight.open_phase("fetch")
            # written after the fetch and before the emit: a request
            # whose first token this step sampled is still a prefill
            # row at the record's ts
            self._record_step(
                "mixed", rows=len(decode_rows) + len(chunk_rows),
                tokens=len(sampled), wall_s=wall, paged_step_s=wall,
                dispatch_s=disp,
                device_s=wall if chained else waited, fetch_wait_s=waited,
                js=js, rows_decode=len(decode_rows),
                rows_prefill=len(chunk_rows),
                rows_idle=B - len(decode_rows) - len(chunk_rows),
                rids=rids, tokens_real=n_real, tokens_computed=computed,
                moe=np.sum(moe, axis=0) if moe else None, chained=chained,
                **tiles)

            def emit(req, slot):
                self._steps[slot] += 1
                self._last_tok[slot] = nxt[slot]
                self._emit(req, int(nxt[slot]), logprob=float(lp[slot]),
                           top=(list(zip(tids[slot].tolist(),
                                         tlps[slot].tolist()))
                                if req.want_top else []))

            with span("emit"):
                for slot in decode_rows:
                    req = self._slot_req[slot]
                    if req is None or req.rid != rows_of[slot]:
                        # it ended in the step before (EOS: the program
                        # froze it there)
                        continue
                    self._pos[slot] += 1
                    emit(req, slot)
                for slot, req in finished:
                    if self._slot_req[slot] is req:
                        emit(req, slot)
            for slot in sampled:
                shipped[slot] -= 1
            if self._journal is not None:
                # once per completed step, as the run loop flushes once
                # per iteration: no later and no rarer than before
                with span("admin"):
                    self._journal.flush()

        # the first dispatch is the step the run loop planned, whoever
        # waits; only what follows is gated
        self._drive_burst(dispatch, lambda finish: finish(), chain_break,
                          first_unconditional=True)

    def _match_and_validate_prefix(self, ids: List[int]):
        """(pid, (p_ids, k, v)) of the longest matching registered prefix
        that can serve this prompt without clamping over live cache
        entries, or None. Returns the ENTRY, not just the pid: a
        concurrent eviction (handler-thread auto-prefix FIFO) must not
        turn the engine thread's later lookup into a KeyError."""
        hit = self._match_prefix(ids)
        if hit is None:
            return None
        pid, p_ids, k, v = hit
        plan = self._prefix_window_plan(p_ids, ids)
        if plan is None:
            return None
        # LRU recency for the cold-prefix spill policy (host tier)
        self._prefix_last_hit[pid] = time.monotonic()
        return (pid, (p_ids, k, v))

    def _prefix_window_plan(self, p_ids: List[int], ids: List[int]):
        """(chunk_suffix, C_or_bucket) for a prefix-hit prefill, or None
        when the suffix windows would clamp over the live prefix. Pure
        function of (p_ids, ids, prefill_chunk, max_seq_len, engine
        flavor) — the coordinator decides with it and a multi-host
        follower re-derives the identical plan from the published op.

        One clamp rule for every engine: windows (or the padded
        single-program bucket) must never clamp over the live prefix.
        The pipelined engine ALWAYS windows the suffix at pos0 = P (it
        has no single-program prefixed-prefill variant); the dense
        engine windows only when --prefill-chunk applies, else takes
        its single program (prefill_slot_prefixed). A paged engine's
        suffix rides the mixed step's windows from the prefix's end
        (_mixed_admit) and is held to the same rule."""
        C = self.prefill_chunk
        suffix = ids[len(p_ids):]
        pipelined = (self._prefill_slot is not prefill_slot
                     and not self.paged)
        if pipelined or (C and len(suffix) > C):
            Cw = C or bucket_length(len(suffix), self.max_seq_len)
            n_win = -(-len(suffix) // Cw)
            if len(p_ids) + n_win * Cw <= self.max_seq_len:
                return (True, Cw)
            return None   # last window would clamp over the prefix
        bucket = bucket_length(len(suffix), self.max_seq_len)
        if len(p_ids) + bucket > self.max_seq_len:
            # the padded window would clamp over the live prefix
            # (dynamic_update_slice clamps out-of-range starts)
            return None
        return (False, bucket)

    def _prefixed_prefill_device(self, pid: int, ids, slot: int,
                                 temp: float, top_p: float, penalty: float,
                                 prime, n_top: int = 0,
                                 entry=None, defer: bool = False) -> tuple:
        """Prefix-hit prefill: install the cached prefix KV, prefill only
        the suffix, sample the first token. Runs identically on the
        coordinator (which passes the matched `entry` so a concurrent
        eviction cannot invalidate the pid between match and use) and,
        via the prefill_prefixed op, every follower (which resolves the
        pid in its mirrored registry — safe by wire ordering: evictions
        arrive as unregister ops on this same thread)."""
        ids = list(ids)
        if entry is None:
            with self._rid_lock:
                entry = self._prefixes[pid]
        p_ids, pk, pv = entry
        plan = self._prefix_window_plan(p_ids, ids)
        if plan is None:  # cannot happen for a published op; be loud
            raise RuntimeError(
                f"prefix {pid} no longer serves prompt of len {len(ids)}")
        chunk_suffix, width = plan
        suffix = ids[len(p_ids):]
        _PREFIX_TOKENS_SAVED.inc(len(p_ids))
        if chunk_suffix:
            from cake_tpu.models.llama.model import install_prefix_slot
            self.cache = install_prefix_slot(self.cache, pk, pv,
                                             jnp.int32(slot))
            logits = self._prefill_chunked(suffix, slot, width,
                                           pos0=len(p_ids))
        else:
            padded = suffix + [0] * (width - len(suffix))
            fargs = (self.params, jnp.asarray([padded], jnp.int32),
                     jnp.asarray([len(suffix)], jnp.int32),
                     jnp.int32(slot), pk, pv, self.cache, self.rope,
                     self.config)
            js = self._obs_jit("prefill_prefixed",
                               (width, int(pk.shape[2])),
                               prefill_slot_prefixed, fargs)
            logits, self.cache = prefill_slot_prefixed(*fargs)
            self._last_jit = js
        return self._finish_prefill(logits, slot, len(ids), temp,
                                    top_p, penalty, prime, n_top=n_top,
                                    defer=defer)

    def _prefill_raw(self, ids, slot: int):
        """Whole-prompt prefill device call (no sampling-state changes)."""
        with self.flight.span("build"):
            ids = list(ids)
            bucket = bucket_length(len(ids), self.max_seq_len)
            padded = ids + [0] * (bucket - len(ids))
            toks = jnp.asarray([padded], jnp.int32)
            plen = jnp.asarray([len(ids)], jnp.int32)
            fargs = (self.params, toks, plen, jnp.int32(slot), self.cache,
                     self.rope, self.config)
        with self.flight.span("dispatch"):
            js = self._obs_jit("prefill_slot", (bucket,),
                               self._prefill_slot, fargs)
            logits, self.cache = self._prefill_slot(*fargs)
            self._last_jit = js
        return logits

    def _prefill_device(self, ids, slot: int, temp: float, top_p: float,
                        penalty: float, prime, n_top: int = 0,
                        defer: bool = False) -> tuple:
        """Prefill one slot (whole-prompt or chunked, decided from
        shared config + prompt length) + first-token sample: the
        device-and-mirror sequence of _do_prefill's non-prefix branch,
        replayed verbatim by multi-host followers (run_follower_loop) so
        the SPMD dispatch sequence cannot drift between processes."""
        ids = list(ids)
        C = self.prefill_chunk
        if C and (len(ids) > C or self.ring):
            # ring mode routes EVERY prompt through chunk windows — the
            # whole-bucket path would write past the ring capacity
            logits = self._prefill_chunked(ids, slot, C)
        else:
            logits = self._prefill_raw(ids, slot)
        return self._finish_prefill(logits, slot, len(ids), temp,
                                    top_p, penalty, prime, n_top=n_top,
                                    defer=defer)

    def _finish_prefill(self, logits, slot: int, prompt_len: int,
                        temp: float, top_p: float, penalty: float,
                        prime, n_top: Optional[int] = None,
                        defer: bool = False) -> tuple:
        """Configure the slot's sampling state and sample its first
        token. Returns (token_id, logprob, top-N alternatives), or the
        deferred device tuple when defer=True (_do_prefill_batch fetches
        it together with the whole admission wave's)."""
        if self._multihost:
            # replicated logits -> local host copy, so sampling is a
            # process-local computation (identical on every process by
            # determinism) instead of a cross-process collective
            logits = np.asarray(logits)
        with self.flight.span("sample"):
            self._pos[slot] = prompt_len
            self._temp[slot] = temp
            self._top_p[slot] = top_p
            self._penalty[slot] = penalty
            self._prime_ring(slot, prime)
            wide = jnp.broadcast_to(logits,
                                    (self.max_slots, logits.shape[-1]))
        # sample the first token with the slot's own key/options
        sampled = self._sample_rows(wide, rows=[slot], n_top=n_top,
                                    defer=defer)
        if defer:
            return sampled          # device tuple for _do_prefill_batch
        return self._finish_prefill_complete(slot, sampled,
                                             mirrors_done=True)

    def _finish_prefill_complete(self, slot: int, host,
                                 mirrors_done: bool = False) -> tuple:
        """Host half of _finish_prefill: mirror advance (unless
        _sample_rows already did it) + first-token unpack."""
        if not mirrors_done:
            host = self._sample_complete([slot], host)
        first, first_lp, tids, tlps = host
        top = (list(zip(tids[slot].tolist(), tlps[slot].tolist()))
               if tids.size else [])
        return int(first[slot]), float(first_lp[slot]), top

    def _prefill_chunked(self, ids: List[int], slot: int, C: int,
                         pos0: int = 0):
        """Walk a prompt (or a prefix-cache suffix starting at absolute
        position pos0) through slot `slot` in fixed C-token windows —
        the engine analog of the generator's --prefill-chunk path, using
        the same chunk_windows contract."""
        from cake_tpu.models.llama.generator import chunk_windows
        logits = None
        for window, n_real, start in chunk_windows(ids, C):
            with self.flight.span("build"):
                fargs = (self.params, jnp.asarray([window], jnp.int32),
                         jnp.asarray([n_real], jnp.int32),
                         jnp.int32(slot), jnp.int32(pos0 + start),
                         self.cache, self.rope, self.config)
            with self.flight.span("dispatch"):
                js = self._obs_jit("prefill_chunk", (C,),
                                   self._prefill_chunk_step, fargs)
                logits, self.cache = self._prefill_chunk_step(*fargs)
                self._last_jit = js
        return logits

    # -- paged speculative decoding (cake_tpu/spec) ---------------------------

    @engine_thread_only
    def _do_spec_paged(self, decode_plan):
        """One batched draft+verify round over PAGED KV for this
        iteration's spec-eligible decode rows; returns the rows the
        round did NOT cover (the caller's plain decode paths take
        them). Page discipline per row and round: extend BOTH table
        rows to cover pos..pos+gamma before dispatch (spec_round_paged
        writes gamma+1 positions in each pool; writes past the mapped
        pages silently drop, which would zero an ACCEPTED position's
        KV), then truncate back to the accepted frontier after the
        fetch — `free_pages + live_pages == n_pages` holds again before
        the method returns."""
        if self._specp is None:
            return decode_plan
        from cake_tpu.sched import partition_rows
        g = self._specp.live_gamma
        spec_rows, plain = partition_rows(
            decode_plan, lambda rid, slot: self._spec_row_ready(rid, slot, g))
        if not spec_rows:
            return plain
        t0 = time.perf_counter()
        plan = []
        for rid, slot in spec_rows:
            if self._spec_extend_rows(slot, g):
                plan.append((self._slot_req[slot], slot))
            else:
                # pool pressure mid-flight: the row decodes plain this
                # iteration and tries again when pages free up
                plain.append((rid, slot))
        if not plan:
            self.stats.decode_time_s += time.perf_counter() - t0
            return plain
        # chaos site for the verify pass — the kv.ship failure
        # discipline: an INJECTED verify fault is absorbed here
        # (penalize the rows' acceptance signal, truncate their
        # extensions, degrade repeat offenders, decode plain this
        # iteration); organic dispatch errors below still propagate to
        # the recovery path with the round's rows implicated
        if self._faults is not None:
            try:
                self._faults.check("spec.verify", step=self.stats.steps)
            except Exception as exc:  # noqa: BLE001 — injected faults
                from cake_tpu.faults.plan import InjectedFault
                if not isinstance(exc, InjectedFault):
                    raise
                self._spec_verify_failed(plan, g, exc)
                self.stats.decode_time_s += time.perf_counter() - t0
                return plain + [(req.rid, s) for req, s in plan]
        self._implicated = tuple((req.rid, s) for req, s in plan)
        sp = self._specp
        active = np.zeros(self.max_slots, bool)
        for _req, slot in plan:
            active[slot] = True
        last = jnp.asarray(self._last_tok[:, None], jnp.int32)
        pos = jnp.asarray(np.minimum(self._pos, self.max_seq_len - 1),
                          jnp.int32)
        fargs = (self.params, sp.draft_params, self.cache, self.d_cache,
                 last, pos, jnp.asarray(active), self._keys,
                 jnp.asarray(self._temp), self.rope, sp.rope,
                 self.config, sp.draft_config, g)
        js = self._obs_jit("spec_round_paged", (g,),
                           self._spec_round_fn, fargs)
        t0d = time.perf_counter()
        (out, n_emit, self.cache, self.d_cache,
         self._keys) = self._spec_round_fn(*fargs)
        disp = time.perf_counter() - t0d
        # ONE batched fetch for every row's round
        t0f = time.perf_counter()
        out_h, n_emit_h = jax.device_get((out, n_emit))
        fetch = time.perf_counter() - t0f
        round_tokens = proposed = accepted = 0
        for req, slot in plan:
            if req.done.is_set():
                continue
            n = int(n_emit_h[slot])
            round_tokens += n
            proposed += g
            accepted += n - 1
            toks = [int(t) for t in out_h[slot, :n]]
            self.stats.spec_proposed += g
            self.stats.spec_accepted += n - 1
            pos0 = int(self._pos[slot])
            self._last_tok[slot] = toks[-1]
            self._steps[slot] += n
            for j, tok in enumerate(toks):
                # per-token position so _emit's cap check sees the
                # value a single-step loop would have had
                self._pos[slot] = pos0 + j + 1
                self._emit(req, tok)
                if req.done.is_set():
                    break   # EOS / budget mid-round: drop the tail
            # cache frontier for the next round: the round wrote n
            # accepted positions regardless of the emission budget
            self._pos[slot] = pos0 + n
            # a finished row's _emit tail already tore its spec state
            # down with the slot (zero leaked suffix pages); for live
            # rows, fold the round into the stream's controller signal
            # and give the unaccepted suffix pages back
            st = sp.spec_streams.get(slot)
            if st is not None and st.enabled:
                st.verify_fails = 0
                st.note_round(g, n - 1)
                self._spec_truncate(slot)
                from cake_tpu.spec.state import (
                    STREAM_ACCEPT_FLOOR, STREAM_WARMUP_ROUNDS,
                )
                if (st.rounds >= STREAM_WARMUP_ROUNDS
                        and (st.accept_ema or 0.0) < STREAM_ACCEPT_FLOOR):
                    self._spec_disable(req, slot, "acceptance_collapse")
        self.stats.steps += 1
        sp.note_round(proposed, accepted, round_tokens, len(plan))
        if self._specp.tuner is not None:
            ng = self._specp.tuner.maybe_shrink()
            if ng is not None and ng < self._specp.live_gamma:
                from cake_tpu.spec.state import SPEC_DEGRADED
                self._specp.live_gamma = ng
                SPEC_DEGRADED.labels(action="shrink_gamma").inc()
                log.warning("spec: acceptance EMA %.2f below tuner "
                            "threshold — gamma shrunk to %d",
                            self._specp.accept_ema or 0.0, ng)
                if self.events is not None:
                    self.events.publish(
                        "spec_degraded", action="shrink_gamma",
                        gamma=ng, accept_ema=self._specp.accept_ema)
        if self.events is not None:
            self.events.publish("spec_round", rows=len(plan),
                                proposed=proposed, accepted=accepted,
                                tokens=round_tokens, gamma=g)
        self._record_step("spec", rows=len(plan), tokens=round_tokens,
                          dispatch_s=disp, device_s=fetch,
                          wall_s=disp + fetch, js=js,
                          rids=[req.rid for req, _s in plan])
        self.stats.decode_time_s += time.perf_counter() - t0
        return plain

    def _spec_row_ready(self, rid: int, slot: int, g: int) -> bool:
        """Is this decode row riding THIS iteration's speculative
        round? Temperature-only sampling (top-p / repetition-penalty /
        top-logprobs rows replay exactly on the plain path: the round
        declines the row, the engine serves the request),
        window room for a whole round, >= 1 emitted token (the round
        contract wants last_tok's KV unwritten at the decode frontier),
        and an enabled SpecState — activated lazily here, whatever path
        brought the stream to its frontier (whole/chunked/prefix
        prefill, preemption resume, recovery replay)."""
        if self._specp is None:
            return False
        req = self._slot_req[slot]
        if req is None or req.rid != rid or req.done.is_set():
            return False
        if not req.out_tokens:
            return False
        if req.top_p < 1.0 or req.repeat_penalty != 1.0 or req.want_top:
            return False
        if self._pos[slot] + g + 1 >= self.max_seq_len:
            # too close to the window: the plain path finishes the
            # stream at the cap (the row loses speculation, not its
            # tail tokens)
            return False
        st = self._specp.spec_streams.get(slot)
        if st is not None and st.rid != req.rid:
            # defensive: a slot reused without the teardown hook (not a
            # known path) must not speculate against a stale draft row
            self._release_spec_state(slot)
            st = None
        if st is None:
            return self._spec_activate(req, slot)
        return st.enabled

    def _spec_activate(self, req: _Request, slot: int) -> bool:
        """Opt a decoding stream into speculation: allocate the draft
        row's context pages from the SHARED allocator and run one
        whole-context draft prefill, leaving the draft pool with KV for
        positions 0..pos-1 — exactly the round contract (the last
        emitted token's KV unwritten in both pools). Best-effort: any
        shortfall keeps the row on plain decode (False)."""
        if self._specp is None:
            return False
        from cake_tpu.models.llama.paged import table_set_slot
        pos = int(self._pos[slot])
        ctx = (list(req.prompt_ids) + list(req.out_tokens))[:pos]
        if len(ctx) != pos:
            return False   # frontier/transcript mismatch: stay plain
        d_pages = self._pager.alloc(len(ctx))
        if d_pages is None:
            return False   # pool pressure: retry on a later iteration
        from cake_tpu.spec import SpecState
        self._specp.spec_streams[slot] = SpecState(rid=req.rid,
                                             d_pages=d_pages)
        self.d_cache = self.d_cache._replace(
            table=table_set_slot(self.d_cache.table, slot, d_pages))
        bucket = bucket_length(len(ctx), self.max_seq_len)
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :len(ctx)] = ctx
        sp = self._specp
        fargs = (sp.draft_params, jnp.asarray(toks),
                 jnp.asarray([len(ctx)], jnp.int32), jnp.int32(slot),
                 self.d_cache, sp.rope, sp.draft_config)
        js = self._obs_jit("spec_draft_prefill", (bucket,),
                           self._prefill_slot, fargs)
        _logits, self.d_cache = self._prefill_slot(*fargs)
        self._last_jit = js
        return True

    def _spec_extend_rows(self, slot: int, g: int) -> bool:
        """Pre-round page extension: both table rows must cover
        positions pos..pos+gamma before dispatch. The draft row is one
        list in its SpecState; the target row is the admission base
        (the engine's `_slot_pages` + shared prefix, untouched here)
        plus the state's suffix-extension pages. False = the pool
        cannot cover the round; the row decodes plain this iteration
        (whatever WAS extended stays until its post-round truncation
        or teardown — conservation holds either way)."""
        if self._specp is None:
            return False
        from cake_tpu.models.llama.paged import table_set_slot
        st = self._specp.spec_streams[slot]
        ps = self.cache.page_size
        cover = int(self._pos[slot]) + g + 1
        if cover > len(st.d_pages) * ps:
            extra = self._pager.alloc(cover - len(st.d_pages) * ps)
            if extra is None:
                return False
            st.d_pages = st.d_pages + extra
            self.d_cache = self.d_cache._replace(
                table=table_set_slot(self.d_cache.table, slot,
                                     st.d_pages))
        base = self._slot_row_pages(slot)
        have = (len(base) + len(st.t_suffix_pages)) * ps
        if cover > have:
            extra = self._pager.alloc(cover - have)
            if extra is None:
                return False
            st.t_suffix_pages = st.t_suffix_pages + extra
            self.cache = self.cache._replace(
                table=table_set_slot(self.cache.table, slot,
                                     base + st.t_suffix_pages))
        return True

    def _slot_row_pages(self, slot: int) -> list:
        """A slot's BASE target row (shared prefix pages + its own
        admission pages, in the table order _alloc_slot_pages mapped) —
        the part of the target row spec never owns."""
        return list(self._slot_pages.get(slot, []))

    def _spec_truncate(self, slot: int) -> None:
        """Acceptance truncation: give back every speculative page past
        the accepted frontier — the draft row shrinks to its context
        coverage, the target row to whatever its base allocation does
        not already cover — and remap the shrunk table rows. After this
        the allocator invariant `free_pages + live_pages == n_pages`
        holds with zero pages parked for rejected drafts."""
        if self._specp is None:
            return
        st = self._specp.spec_streams.get(slot)
        if st is None:
            return
        from cake_tpu.models.llama.paged import table_set_slot
        need = self._pager.pages_for(int(self._pos[slot]))
        keep = max(need, 1)     # a decoding row always keeps a page
        if keep < len(st.d_pages):
            self._pager.release(st.d_pages[keep:])
            st.d_pages = st.d_pages[:keep]
            self.d_cache = self.d_cache._replace(
                table=table_set_slot(self.d_cache.table, slot,
                                     st.d_pages))
        base = self._slot_row_pages(slot)
        keep_sfx = max(need - len(base), 0)
        if keep_sfx < len(st.t_suffix_pages):
            self._pager.release(st.t_suffix_pages[keep_sfx:])
            st.t_suffix_pages = st.t_suffix_pages[:keep_sfx]
            self.cache = self.cache._replace(
                table=table_set_slot(self.cache.table, slot,
                                     base + st.t_suffix_pages))

    def _spec_disable(self, req: _Request, slot: int,
                      reason: str) -> None:
        """Per-stream degrade to plain decode — never wedge: release
        every speculative page back to the pool, keep a disabled
        tombstone so the stream is not re-activated, and publish the
        degrade. The stream itself keeps decoding on the plain path
        with its base pages untouched."""
        if self._specp is None:
            return
        st = self._specp.spec_streams.get(slot)
        if st is None or not st.enabled:
            return
        from cake_tpu.models.llama.paged import table_set_slot
        from cake_tpu.spec.state import SPEC_DEGRADED
        if st.d_pages:
            self._pager.release(st.d_pages)
            st.d_pages = []
        if st.t_suffix_pages:
            self._pager.release(st.t_suffix_pages)
            st.t_suffix_pages = []
            self.cache = self.cache._replace(
                table=table_set_slot(self.cache.table, slot,
                                     self._slot_row_pages(slot)))
        st.enabled = False
        SPEC_DEGRADED.labels(action="disabled").inc()
        log.warning("spec: rid=%d degraded to plain decode (%s, "
                    "accept_ema=%.2f after %d rounds)", req.rid, reason,
                    st.accept_ema or 0.0, st.rounds)
        if self.events is not None:
            self.events.publish("spec_degraded", rid=req.rid,
                                action="disabled", reason=reason,
                                accept_ema=st.accept_ema,
                                rounds=st.rounds)

    def _spec_verify_failed(self, plan, g: int, exc) -> None:
        """An injected spec.verify fault: charge a zero-acceptance
        round to every planned row (the controller sees collapse, not
        silence), truncate their pre-round extensions back, and disable
        repeat offenders — the PR-19 kv.ship discipline: degrade, never
        wedge, and the rows finish on the plain path either way."""
        if self._specp is None:
            return
        from cake_tpu.spec.state import DISABLE_AFTER_FAILS
        log.warning("spec.verify fault (%s): %d rows decode plain this "
                    "iteration", exc, len(plan))
        for req, slot in plan:
            st = self._specp.spec_streams.get(slot)
            if st is None or not st.enabled:
                continue
            st.verify_fails += 1
            st.note_round(g, 0)
            self._spec_truncate(slot)
            if st.verify_fails >= DISABLE_AFTER_FAILS:
                self._spec_disable(req, slot, "verify_faults")
        self._specp.note_round(g * len(plan), 0, 0, len(plan))
        if self.events is not None:
            self.events.publish("spec_round", rows=len(plan),
                                proposed=g * len(plan), accepted=0,
                                tokens=0, gamma=g, fault=True)

    def _decode_rows(self, decode_plan, chain: bool = True) -> None:
        """One pure-decode iteration. A single-host engine keeps a step
        in flight (_decode_burst: the sampled one-step program chained
        from the tokens still on the device, or --decode-scan's K-step
        scans). chain=False: this iteration admitted rows that the
        plan does not hold yet (the scheduler plans a wave's admissions
        and its decode rows apart), so one dispatch, then back to the
        planner: a stretch would leave them idle for its whole length.
        The synchronous step (_do_decode: dispatch, sample
        eagerly, fetch, emit) stays where something the engine observes
        says so: followers replay every step from host mirrors
        (multi-host); the step fns bring no sampled program; a row sits
        one token from max_seq_len (the device carry has no window
        freeze); or the rows are what the paged speculative partition
        left behind (one step, then back to the planner: a stretch
        would starve the spec rows)."""
        n = self._scan_steps_for(decode_plan)
        if self._multihost:
            if n > 1:
                self._do_decode_scan(decode_plan, n)
            else:
                self._do_decode(decode_plan)
        elif n > 1 or (self._decode_scan_impl is not None
                       and self._specp is None
                       and all(self._pos[slot] + 1 < self.max_seq_len
                               for _, slot in decode_plan)):
            self._decode_burst(decode_plan, n, chain)
        else:
            self._do_decode(decode_plan)

    @engine_thread_only
    def _do_decode(self, decode_plan) -> None:
        t0 = time.perf_counter()
        self._implicated = decode_plan
        if self._faults is not None:
            self._faults.check("engine.decode", step=self.stats.steps)
        rows = [s for _, s in decode_plan]
        n_top = self._n_top_for(rows)
        self._publish({"op": "decode", "rows": rows, "n_top": n_top})
        pages = self._attn_pages([(int(self._pos[s]), 1) for s in rows])
        nxt, lp, tids, tlps = self._decode_device(rows, n_top=n_top)
        self.stats.steps += 1
        dt = time.perf_counter() - t0
        self.stats.decode_time_s += dt
        self._record_step("decode", rows=len(decode_plan),
                          tokens=len(decode_plan), wall_s=dt,
                          paged_step_s=dt,
                          dispatch_s=self.flight.open_phase("dispatch"),
                          device_s=self.flight.open_phase("fetch"),
                          rids=[r for r, _s in decode_plan],
                          moe=self._take_moe(), chained=False, **pages)
        with self.flight.span("emit"):
            for rid, slot in decode_plan:
                req = self._slot_req[slot]
                if req is None or req.rid != rid:
                    continue
                self._emit(req, int(nxt[slot]), logprob=float(lp[slot]),
                           top=(list(zip(tids[slot].tolist(),
                                         tlps[slot].tolist()))
                                if tids.size else []))

    def _decode_device(self, rows, n_top: Optional[int] = None) -> tuple:
        """One ragged decode step + sample for the given slot rows: the
        device-and-mirror half of _do_decode, shared verbatim by the
        coordinator and multi-host followers."""
        with self.flight.span("build"):
            B = self.max_slots
            active = np.zeros(B, bool)
            for slot in rows:
                active[slot] = True
            toks = jnp.asarray(self._last_tok[:, None], jnp.int32)
            pos = jnp.asarray(
                np.minimum(self._pos, self.max_seq_len - 1), jnp.int32)
            fargs = (self.params, toks, pos, jnp.asarray(active),
                     self.cache, self.rope, self.config)
        with self.flight.span("dispatch"):
            js = self._obs_jit("decode_step", (), self._decode_step,
                               fargs)
            with self.flight.part("launch"):
                logits, self.cache, *moe = self._decode_step(*fargs)
            self._moe_pending += moe
            self._last_jit = js
        if self._multihost:
            logits = np.asarray(logits)  # see _finish_prefill
        nxt, lp, tids, tlps = self._sample_rows(logits, rows=rows,
                                                n_top=n_top)
        self._pos += active  # only active rows advanced
        return nxt, lp, tids, tlps

    def _scan_steps_for(self, decode_plan) -> int:
        """Fixed scan length when multi-step decode is safe right now:
        nobody queued (a waiting request must not see its admission
        delayed by a whole scan) and K more cache writes fit every
        row's window. Rows with under K tokens of max_new_tokens budget
        are fine — the device program freezes each row at its per-row
        budget (make_decode_scan), so the scan cannot overshoot."""
        n = self._decode_scan
        if n <= 1 or self.scheduler.queue_depth > 0:
            return 1
        max_left = 0
        for _, slot in decode_plan:
            req = self._slot_req[slot]
            if req is None:
                return 1
            max_left = max(max_left,
                           req.max_new_tokens - len(req.out_tokens))
            if self._pos[slot] + n >= self.max_seq_len:
                return 1
        # per-row budget freeze (make_decode_scan) makes a scan safe for
        # rows with < n budget; only when EVERY row is on its last token
        # is the single-step program the cheaper dispatch
        if max_left <= 1:
            return 1
        return n

    def _scan_budget(self, decode_plan, n: int,
                     shipped: Optional[dict] = None) -> np.ndarray:
        """Per-row token allowance for one n-step scan: the request's
        remaining max_new_tokens budget, minus tokens already dispatched
        in not-yet-fetched chained scans (`shipped`), capped at n. Rows
        with 0 allowance are frozen by the device program."""
        budget = np.zeros(self.max_slots, np.int32)
        for _, slot in decode_plan:
            req = self._slot_req[slot]
            if req is None:
                continue
            left = req.max_new_tokens - len(req.out_tokens)
            if shipped:
                left -= shipped.get(slot, 0)
            budget[slot] = max(0, min(n, left))
        return budget

    def _do_decode_scan(self, decode_plan, n: int) -> None:
        """n ragged decode steps + sampling as one compiled program
        (synchronous: dispatch, fetch, emit — the multi-host lockstep
        path; single-host serving uses _decode_burst instead)."""
        t0 = time.perf_counter()
        self._implicated = decode_plan
        if self._faults is not None:
            self._faults.check("engine.decode", step=self.stats.steps)
        rows = [s for _, s in decode_plan]
        n_top = self._n_top_for(rows)
        budget = self._scan_budget(decode_plan, n)
        # n_top must ride the op: in a multi-host scan the sampling is
        # INSIDE the mesh program, so a follower compiling the n_top=0
        # variant while the coordinator runs n_top=20 would dispatch a
        # different program and wedge the collective. budget rides it
        # for the same reason followers cannot derive it (no requests).
        self._publish({"op": "decode_scan", "rows": rows, "n": n,
                       "n_top": n_top, "budget": budget.tolist()})
        outs, _state = self._dispatch_scan_device(rows, n, n_top, budget)
        fetched = self._fetch_scan(outs)
        self.stats.steps += n
        dt = time.perf_counter() - t0
        self.stats.decode_time_s += dt
        self._record_step("decode_scan", rows=len(decode_plan),
                          tokens=int(budget.sum()), wall_s=dt,
                          paged_step_s=dt / n,
                          rids=[r for r, _s in decode_plan])
        self._complete_scan(decode_plan, n, fetched, budget)

    def _decode_burst(self, decode_plan, n: int,
                      chain: bool = True) -> None:
        """A stretch of pure decode with one dispatch in flight
        (_decode_stretch through _drive_burst). n = 1 is the sampled
        one-step program (records of kind `decode`), n > 1
        --decode-scan's K-step scans."""
        self._implicated = decode_plan
        # n = 1: the first dispatch is the step the synchronous path
        # would have run, whoever waits; only what follows is gated
        self._drive_burst(
            *self._decode_stretch(decode_plan, n, chain, {}, _Flying()),
            first_unconditional=(n == 1))

    def _decode_stretch(self, decode_plan, n: int, chain: bool,
                        shipped: dict, flying: "_Flying") -> tuple:
        """_drive_burst's (dispatch, complete, chain_break) for decode
        steps on the rows of decode_plan: dispatch k+1 (its inputs
        chained on device from k's final carry — zero host round-trips
        between them) BEFORE fetching k's tokens, so the fetch and the
        emit of k run while the device computes k+1. The stretch ends
        when the host needs the loop back (_host_attention),
        when a row of its plan finished (the slot is the planner's to
        fill), when no row has budget or window left, after
        STRETCH_STEPS dispatches, and after its first where the caller
        says the plan is about to change (chain=False). Single-host
        only: a follower rebuilds its inputs from its mirrors, which
        match the chained carry for live rows but diverge for rows that
        froze (EOS) inside an earlier not-yet-fetched dispatch —
        lockstep multi-host serving keeps the synchronous paths
        instead.

        shipped: tokens dispatched in not-yet-fetched programs, per
        slot: added at dispatch, removed at fetch — budget math and the
        window guard both project the device state past the stale host
        mirrors by exactly this amount. flying: the stretch's
        dispatches. Both are the caller's: a stretch of mixed steps
        goes on into this one with a step of its own in flight
        (_mixed_burst)."""
        span = self.flight.span
        rows = [s for _, s in decode_plan]
        rids = [r for r, _s in decode_plan]
        n_top = self._n_top_for(rows)
        kind = "decode" if n == 1 else "decode_scan"

        def chain_break(_n_inflight) -> Optional[str]:
            # real work remains, and the PROJECTED device position
            # (host mirror + unfetched in-flight tokens) still fits the
            # window: the mirror lags the device by the in-flight
            # dispatches, and the device program has no max_seq freeze.
            # (The per-slot `shipped` dict is finer-grained than the
            # driver's in-flight count, so the latter goes unused.)
            # A row that finished in the emit just before this gate
            # left its slot to the planner, and a caller that waits for
            # each reply sends its next request a few ms from now, too
            # late for this gate: chaining on made that arrival wait
            # out two steps and meet the next one (twice as many double
            # admissions behind a stretch, and itl_p95_ms moving with
            # their count; my chip run, PR 29). The step in flight
            # covers the time the arrival needs.
            if not chain:
                return "sync"
            if flying.sent >= STRETCH_STEPS:
                return "stretch_cap"
            if any(self._slot_req[s] is None for s in rows):
                return "row_finished"
            if not self._scan_budget(decode_plan, n, shipped).any():
                return "budget"
            if any(self._pos[s] + shipped.get(s, 0) + n >= self.max_seq_len
                   for s in rows):
                return "window_end"
            return None

        def dispatch(state):
            if self._faults is not None:
                # the chaos plane's sites fire once a step, as when
                # every step was an iteration of the run loop (which
                # checked engine.step before this stretch's first)
                if flying.sent:
                    self._faults.check("engine.step",
                                       step=self.stats.steps)
                self._faults.check("engine.decode", step=self.stats.steps)
            t_start = time.perf_counter()
            with span("build"):
                # recomputed rather than smuggled out of chain_break:
                # nothing host-side changes between the gate and the
                # dispatch (same thread), and an explicit recompute
                # keeps _drive_burst's chain_break a pure gate
                budget = self._scan_budget(decode_plan, n, shipped)
                # the device's positions: the mirrors lag them by what
                # the dispatches in flight ship
                pages = self._attn_pages(
                    [(int(self._pos[s]) + shipped.get(s, 0),
                      int(budget[s])) for s in rows if budget[s]])
            t0d = time.perf_counter()
            outs, state = self._dispatch_scan_device(
                rows, n, n_top, budget, state=state)
            disp = time.perf_counter() - t0d
            js, self._last_jit = self._last_jit, None
            for slot in rows:
                shipped[slot] = shipped.get(slot, 0) + int(budget[slot])
            self.stats.steps += n
            return (outs, budget, t_start, disp, js,
                    flying.dispatched(), pages), state

        def complete(devs):
            outs_k, budget_k, t_start, disp_k, js_k, chained, pages = devs
            with span("fetch"):
                fetched = self._fetch_scan(outs_k)
            wall = flying.fetched(t_start)
            self.stats.decode_time_s += wall
            moe = fetched[4]
            waited = self.flight.open_phase("fetch")
            self._record_step(
                kind, rows=int(np.count_nonzero(budget_k)),
                tokens=int(budget_k.sum()), wall_s=wall,
                paged_step_s=wall / n, dispatch_s=disp_k,
                device_s=wall if chained else waited, fetch_wait_s=waited,
                js=js_k, rids=rids,
                moe=np.sum(moe, axis=0) if moe else None,
                chained=chained, **pages)
            with span("emit"):
                self._complete_scan(decode_plan, n, fetched, budget_k)
            for slot in rows:
                shipped[slot] -= int(budget_k[slot])
            if self._journal is not None:
                # once per completed step, as the run loop flushes once
                # per iteration: no later and no rarer than before
                with span("admin"):
                    self._journal.flush()

        return dispatch, complete, chain_break

    def _complete_scan(self, decode_plan, n: int, fetched,
                       budget) -> None:
        """Emit one fetched scan's tokens and advance the host mirrors.
        A row emits min(its budget, EOS cut) tokens; the device program
        froze it at exactly that point (budget freeze + EOS freeze in
        make_decode_scan), so mirrors advance by the emitted count."""
        toks_host, lps_host, tops_i_host, tops_l_host = fetched[:4]
        for rid, slot in decode_plan:
            req = self._slot_req[slot]
            if req is None or req.rid != rid:
                continue
            pos0 = int(self._pos[slot])
            b = int(budget[slot])
            emitted = 0
            for j in range(b):
                # per-token position so _emit's cap check sees the value a
                # single-step loop would have had
                self._pos[slot] = pos0 + j + 1
                emitted = j + 1
                self._last_tok[slot] = toks_host[slot, j]
                self._emit(req, int(toks_host[slot, j]),
                           logprob=float(lps_host[slot, j]),
                           top=(list(zip(tops_i_host[slot, j].tolist(),
                                         tops_l_host[slot, j].tolist()))
                                if tops_i_host.size else []))
                if req.done.is_set():
                    # EOS/budget: the device froze the row here too
                    break
            self._steps[slot] += emitted
            self._pos[slot] = pos0 + emitted

    def _dispatch_scan_device(self, rows, n: int, n_top: int, budget,
                              state=None):
        """Device dispatch half of a K-step scan, shared verbatim with
        multi-host followers (via _decode_scan_device). In multi-host
        mode keys/ring are localized around the call (host numpy in,
        replicated output localized), so the surrounding single-step ops
        keep their process-local sampling while the scan itself runs
        sampling inside the mesh program identically on every process.
        state: a previous dispatch's final carry to chain from
        (single-host bursts); None rebuilds the inputs from the host
        mirrors.
        Returns ((tokens, logprobs, top ids, top logprobs, [a sparse
        model's expert counters]) on the device, the final carry)."""
        with self.flight.span("build"):
            B = self.max_slots
            if state is None:
                active = np.zeros(B, bool)
                for slot in rows:
                    active[slot] = True
                last_tok, pos, steps, active = (self._placed(a) for a in (
                    self._last_tok.astype(np.int32),
                    np.minimum(self._pos, self.max_seq_len - 1)
                    .astype(np.int32),
                    self._steps.astype(np.int32), active))
            else:
                last_tok, pos, steps, active = state
            keys, ring = self._placed(self._keys), self._placed(self._ring)
            if self._multihost:
                keys, ring = np.asarray(keys), np.asarray(ring)
            fargs = (self.params, last_tok, pos, active, self.cache,
                     self.rope, self.config, keys, ring, steps,
                     self._held("temp", self._temp),
                     self._held("top_p", self._top_p),
                     self._held("penalty", self._penalty),
                     self._held("budget", np.asarray(budget, np.int32)))
            fkw = dict(num_steps=n, top_k=self.defaults.top_k, n_top=n_top)
        with self.flight.span("dispatch"):
            js = self._obs_jit(
                "decode_scan" if n > 1 else "decode_step_sampled",
                (n, n_top), self._decode_scan_impl, fargs, fkw)
            with self.flight.part("launch"):
                (toks, lps, tops_i, tops_l, self.cache, keys_o, ring_o,
                 state_o, *moe) = self._decode_scan_impl(*fargs, **fkw)
            self._last_jit = js
        if self._multihost:
            keys_h, ring_h = jax.device_get((keys_o, ring_o))
            keys_o, ring_o = jnp.asarray(keys_h), jnp.asarray(ring_h)
        self._keys, self._ring = keys_o, ring_o
        return (toks, lps, tops_i, tops_l, moe), state_o

    def _held(self, name: str, host: np.ndarray):
        """`host` on the device, copied again only when it differs from
        the last copy under `name`: a stretch of chained decode steps
        changes neither its sampling options nor, until a row runs
        out, its budget, so its dispatches send the device nothing."""
        got = self._dev_held.get(name)
        if got is None or not np.array_equal(got[0], host):
            got = self._dev_held[name] = (host.copy(), self._placed(host))
        return got[1]

    def _placed(self, x):
        """x on the device(s), where the sampled programs leave their
        own small outputs (DecodePrograms.out_sharding); followers keep
        process-local copies."""
        sharding = getattr(self._decode_scan_impl, "out_sharding", None)
        if sharding is None or self._multihost:
            return jnp.asarray(x)
        return jax.device_put(x, sharding)

    @staticmethod
    def _fetch_scan(outs) -> tuple:
        # ONE batched fetch: sequential np.asarray calls each wait for
        # the device and copy to the host, so four of them would pay
        # that round-trip four times per scan
        return jax.device_get(outs)

    def _decode_scan_device(self, rows, n: int, n_top: int,
                            budget=None) -> tuple:
        """Synchronous dispatch+fetch (follower replay path)."""
        if budget is None:
            budget = np.full(self.max_slots, n, np.int32)
        outs, _state = self._dispatch_scan_device(
            rows, n, n_top, np.asarray(budget, np.int32))
        return self._fetch_scan(outs)

    def _finalize_scan_mirrors(self, rows, n: int, toks_host,
                               budget=None) -> None:
        """Follower-side mirror advance after a replayed scan. MUST
        agree with the coordinator's emit loop in _complete_scan: a row
        ends at min(its budget, EOS cut) — exactly where the device
        program froze it (budget freeze + EOS freeze in
        make_decode_scan)."""
        eos = self.config.eos_token_ids
        for slot in rows:
            pos0 = int(self._pos[slot])
            b = n if budget is None else int(budget[slot])
            end = b
            for j in range(b):
                if int(toks_host[slot, j]) in eos:
                    end = j + 1
                    break
            self._steps[slot] += end
            if end:
                self._last_tok[slot] = toks_host[slot, end - 1]
            self._pos[slot] = pos0 + end

    def _n_top_for(self, rows) -> int:
        """cap when any of the rows' requests asked for top_logprobs,
        else 0 (both variants are separately compiled and cached; on a
        follower no requests exist, so this is always 0 — safe, because
        multi-host sampling is process-local, not a collective)."""
        for r in rows:
            req = self._slot_req[r]
            if req is not None and req.want_top:
                return self.n_top
        return 0

    def _sample_rows(self, logits, rows: List[int],
                     n_top: Optional[int] = None, defer: bool = False):
        """Sample all B rows; advance keys/ring only for `rows` (so an
        inactive slot's PRNG stream is untouched). n_top: explicit value
        in multi-host replay (it rides every op so coordinator and
        followers compile the SAME sampling program — different n_top
        variants may fuse differently and flip a sampled token near a
        top-p boundary); None derives it from the rows' requests.
        defer=True returns the device tuple without fetching (the
        caller batches the fetch and runs _sample_complete itself)."""
        with self.flight.span("sample"):
            B = self.max_slots
            row_mask = np.zeros(B, bool)
            for r in rows:
                row_mask[r] = True
            (nxt, self._keys, self._ring, lp, top_ids,
             top_lps) = _masked_sample(
                jnp.asarray(row_mask), self._keys, logits, self._ring,
                jnp.asarray(self._steps, jnp.int32),
                jnp.asarray(self._temp), jnp.asarray(self._top_p),
                jnp.asarray(self._penalty), top_k=self.defaults.top_k,
                n_top=self._n_top_for(rows) if n_top is None else n_top,
            )
            dev = (nxt, lp, top_ids, top_lps)
        if defer:
            return dev
        # one batched fetch, not four sequential round-trips (see
        # _decode_scan_device): the host waiting for the device. A
        # sparse model's expert counters ride the same fetch.
        moe, self._moe_pending = self._moe_pending, []
        with self.flight.span("fetch"):
            *host, moe = jax.device_get(dev + (moe,))
        self._moe_fetched += moe
        return self._sample_complete(rows, host)

    def _take_moe(self):
        """The expert counters (in the order of obs/steps.MOE_COUNTERS)
        of the step programs fetched since the last record, summed;
        None for a dense model or when nothing was fetched (a mixed
        step that sampled no row leaves its counters for the next
        step's fetch)."""
        got, self._moe_fetched = self._moe_fetched, []
        return np.sum(got, axis=0) if got else None

    def _sample_complete(self, rows: List[int], host) -> tuple:
        """Host half of _sample_rows: advance the sampled rows' step and
        last-token mirrors from the (already fetched) host tuple."""
        nxt_host, lp_h, tids_h, tlps_h = host
        for r in rows:
            self._steps[r] += 1
            self._last_tok[r] = nxt_host[r]
        return (nxt_host, lp_h, tids_h, tlps_h)

    # -- token plumbing -------------------------------------------------------

    def _emit(self, req: _Request, token_id: int,
              logprob: float = 0.0, top=None) -> None:
        # one clock read at each seam of a row's token (obs/steps.
        # EMIT_SEAMS; flight.add_emit at the end): trace, report,
        # detok, stream, retire
        clock = time.perf_counter
        now = clock()
        req.out_logprobs.append(logprob)
        req.out_top.append(top or [])
        if not req.out_tokens:
            req.first_token_t = now
            self.tracer.first_token(req.rid)
            # per-class TTFT (includes queue wait and any
            # preemption-induced requeues): the latency the SLO
            # scheduler exists to protect, labeled so interactive and
            # batch distributions separate on one scrape
            _SCHED_TTFT.labels(req.priority).observe(now - req.submit_t)
        else:
            self.tracer.token(req.rid)
        t_trace = clock()
        req.out_tokens.append(token_id)
        if req.crash_count:
            # a step that emits for this request succeeded: the crash
            # implication is no longer CONSECUTIVE — forgiven
            req.crash_count = 0
        if self._journal is not None:
            # buffered; one emit record per (request, iteration) lands
            # at the run loop's flush. The count is ABSOLUTE (replayed
            # prior generations included) — the SSE event-id coordinate
            self._journal.note_emit(
                req.rid, token_id,
                len(req.replayed_tokens) + len(req.out_tokens))
        self.stats.tokens_generated += 1
        eos = token_id in self.config.eos_token_ids
        hit_cap = (self._pos[req.slot] + 1 >= self.max_seq_len)
        finished = self.scheduler.report(req.rid, 1, eos or hit_cap)
        t_detok = t_stream = t_end = t_report = clock()
        if req.stream is not None:
            # final=finished: flush any held-back UTF-8 tail — a stream
            # ending on an incomplete sequence would otherwise deliver
            # less text than the buffered response for the same request
            delta = self._incremental_text(req, final=finished)
            t_detok = t_stream = t_end = clock()
            if delta or finished:
                self._stream_out(req, delta, finished)
                t_stream = t_end = clock()
        if finished:
            req.finish_t = now
            if req.ship_sink is not None:
                # disaggregated prefill host: fetch the slot's written
                # pages BEFORE release frees them — the sink queues the
                # shipment for the transfer channel's writer thread
                self._capture_shipment(req)
            self._slot_req[req.slot] = None
            self._release_slot_pages(req.slot)
            self._requests.pop(req.rid, None)
            self.stats.requests_completed += 1
            if self._shed is not None:
                self._shed.observe_retire()
            self._journal_retire(req, "retired")
            self.tracer.finish(req.rid, "retired",
                               output_tokens=len(req.out_tokens))
            req.done.set()
            t_end = clock()
        self.flight.add_emit(now, t_trace, t_report, t_detok, t_stream,
                             t_end)

    def _stream_out(self, req: _Request, delta: str,
                    finished: bool) -> None:
        """A delta to the request's stream callback. One that returns
        True only queued it (the API server's stream writer:
        api/stream_writer.ChatStream.feed), and the recorder signals the
        writer for it; any other was called for the token itself."""
        try:
            if req.stream_wants_count:
                handed = req.stream(delta, finished, len(req.out_tokens))
            else:
                handed = req.stream(delta, finished)
        except Exception:  # noqa: BLE001
            log.exception("stream callback failed rid=%d", req.rid)
            return
        self.flight.add_stream(handed is True)

    def _incremental_text(self, req: _Request, final: bool = False) -> str:
        """The text that the tokens emitted since the last call
        finalize: the step's one (more only for a stream attached to a
        request under way), EOS never among them."""
        det = req._detok
        if det is None:
            det = req._detok = StreamDetokenizer(self.tokenizer)
        eos = self.config.eos_token_ids
        fresh = [t for t in req.out_tokens[req._detok_seen:]
                 if t not in eos]
        req._detok_seen = len(req.out_tokens)
        before = det.decoded_ids
        delta = det.add(fresh, final=final)
        self.flight.add_detok_ids(det.decoded_ids - before)
        return delta

    def _fail_all(self, err: Exception, snapshot: bool = False) -> None:
        # beat-the-reference failure handling (the reference is fail-stop
        # with total state loss, client.rs:50-59): on a FATAL failure,
        # snapshot the in-flight requests BEFORE failing them, so a
        # restarted cluster resumes every interrupted generation
        # token-exact (serve/checkpoint resume semantics) instead of
        # losing them with the process. snapshot=True only from fatal
        # paths (heartbeat loss, a failure the engine cannot reset from)
        # — a transient reset-and-continue error must not leave a stale
        # snapshot that resurrects long-errored requests after a later
        # unclean exit.
        from cake_tpu.serve.errors import as_engine_error
        # clients always see the TYPED form: a retryable engine reset
        # maps to 503 + Retry-After at the API instead of a bare 500
        err = as_engine_error(err)
        with self._ckpt_lock:
            if snapshot:
                self._snapshot_before_fail()
            # claim the registry under the lock (two racing _fail_all
            # callers — health monitor + signal handler — each fail a
            # disjoint set), but run the per-request teardown OUTSIDE
            # it: _journal_retire takes _rid_lock, and the declared
            # lock order (_rid_lock before _ckpt_lock) forbids
            # acquiring it while _ckpt_lock is held
            doomed = []
            for rid in list(self._requests):
                req = self._requests.pop(rid, None)
                if req is not None:
                    doomed.append((rid, req))
        for rid, req in doomed:
            req.error = err
            self.scheduler.cancel(rid)
            with self._rid_lock:
                self._adopt_store.pop(rid, None)
            if self._host_tier is not None:
                self._host_tier.drop(("victim", rid))
            if req.slot >= 0:
                # cakelint: skip[affinity] fatal path: the engine thread is wedged or has exited; cross-thread teardown is deliberate
                self._slot_req[req.slot] = None
                self._release_slot_pages(req.slot)
            self._journal_retire(req, "error", error=str(err))
            self.tracer.finish(rid, "error", error=str(err),
                               output_tokens=len(req.out_tokens))
            req.done.set()

    def shutdown_save(self, path: str) -> None:
        """Clean-shutdown checkpoint: save the live registry — UNLESS
        this process wrote a pre-fail snapshot and it still holds
        resumable records, in which case that file is the authoritative
        failure-time state (serving was over; saving the emptied
        registry would clobber it). Holds the same lock as _fail_all so
        a SIGTERM racing a heartbeat failure cannot read
        _prefail_written before the pre-fail write lands."""
        from cake_tpu.serve import checkpoint
        with self._ckpt_lock:
            if (getattr(self, "_prefail_written", False)
                    and checkpoint.has_resumable(path)):
                log.info("keeping pre-fail snapshot at %s", path)
                return
            checkpoint.write(checkpoint.snapshot(self), path)
            if self._journal is not None:
                # compaction handshake: the snapshot now owns every
                # journaled record — truncating keeps the two restart
                # sources disjoint (serve/journal.py)
                self._journal.truncate("checkpoint")

    def _snapshot_before_fail(self, requests=None) -> None:
        """Best-effort pre-fail checkpoint (no-op unless api.start armed
        `snapshot_path`). Caller must hold _ckpt_lock. Inline and
        device-free by construction: arming pairs with
        checkpoint.warm_fingerprint, so the fingerprint is memoized and
        the snapshot is pure Python plus one local write — safe even
        with the mesh wedged on a dead host. The guard below keeps it
        that way if the arming contract ever drifts.

        requests: records captured with checkpoint.snapshot_requests
        BEFORE the registry was emptied — the engine loop's fatal path
        fails its clients first (fast) and writes the snapshot after,
        from this capture. Sets `_prefail_written`, which the shutdown
        save consults to avoid clobbering this file (api/server.py
        save_and_exit)."""
        path = getattr(self, "snapshot_path", None)
        if not path:
            return
        if requests is None and not self._requests:
            # fatal declared after the registry was already emptied by
            # an engine-loop failure (the same event, seen twice): use
            # that failure's capture if it is fresh — requests from an
            # old, genuinely recovered error must not resurrect
            stash = getattr(self, "_fail_recs", None)
            # the window must cover the heartbeat stale interval (the
            # monitor is exactly the thread that arrives late) — cli
            # sets fail_recs_ttl from --heartbeat-timeout
            ttl = getattr(self, "fail_recs_ttl", 60.0)
            if stash is not None and time.monotonic() - stash[0] < ttl:
                requests = stash[1]
            else:
                return
        if getattr(self, "_ckpt_fingerprint", None) is None:
            log.warning("pre-fail snapshot skipped: fingerprint was not "
                        "warmed at arming time (would touch a possibly "
                        "wedged device)")
            return
        try:
            from cake_tpu.serve import checkpoint
            snap = checkpoint.snapshot(self, requests=requests)
            if not any(checkpoint.is_resumable(r)
                       for r in snap["requests"]):
                return   # nothing worth preserving
            checkpoint.write(snap, path)
            self._prefail_written = True
            if self._journal is not None:
                # same handshake as shutdown_save: the pre-fail
                # snapshot supersedes the journaled history
                self._journal.truncate("checkpoint")
            log.info("pre-fail snapshot saved to %s", path)
        except Exception:  # noqa: BLE001
            log.exception("pre-fail snapshot failed")


# The most dispatches one stretch of in-flight decode makes before it
# hands the engine thread back to _run_loop (_decode_burst): the
# autotune tick, the queue gauges, preemption and the journal's
# compaction check run between iterations only, and one long request
# with nobody else arriving must not starve them. 32 steps are ~0.5 s at
# a 16 ms step (1.8 s at the four-chip engine's 57 ms), and the one
# unhidden host gap a stretch end costs (~8 ms) is under 2 % of it.
STRETCH_STEPS = 32


class QueueFullError(Exception):
    """Admission queue full. retry_after: computed seconds a client
    should wait before retrying — derived from the measured service
    rate when load shedding is on, else a 1s floor (the API surfaces
    it as HTTP 429 + Retry-After, api/server.py)."""

    def __init__(self, msg: str = "engine queue full",
                 retry_after: float = 1.0):
        super().__init__(msg)
        self.retry_after = retry_after


class _Flying:
    """The dispatches of one stretch (_decode_stretch, _mixed_burst):
    how many were sent, how many of them the host has not fetched, and
    when the newest fetch ended."""

    __slots__ = ("sent", "unfetched", "fetch_t1")

    def __init__(self):
        self.sent = self.unfetched = 0
        self.fetch_t1 = 0.0

    def dispatched(self) -> bool:
        """Count one dispatch. True: it is chained, sent while the one
        before it was unfetched."""
        chained = self.unfetched > 0
        self.sent += 1
        self.unfetched += 1
        return chained

    def fetched(self, t_start: float) -> float:
        """Count the fetch that just ended, of the dispatch begun at
        t_start. Returns what that dispatch added to the loop: from its
        own start, or from the end of the fetch before it when it was
        queued behind that step, never less than the device needed.
        For a chained step that period is the best reading of the
        device's time too; its fetch alone waited for less."""
        t1 = time.perf_counter()
        self.unfetched -= 1
        wall = t1 - max(t_start, self.fetch_t1)
        self.fetch_t1 = t1
        return wall


def _builtin_forward_ragged(params, tokens, cache, pos, active, rope,
                            config):
    from cake_tpu.models.llama.model import forward_ragged
    return forward_ragged(params, tokens, cache, pos, active, rope, config)


# module-level so the jit cache is shared across engine instances
# (restart flows, test suites); a paged family's programs are its
# module's (models/family.py)
_decode_scan = make_decode_scan(_builtin_forward_ragged)


def _ring_forward_ragged(params, tokens, cache, pos, active, rope, config):
    from cake_tpu.models.llama.model import forward_ragged_ring
    return forward_ragged_ring(params, tokens, cache, pos, active, rope,
                               config)


_decode_scan_ring = make_decode_scan(_ring_forward_ragged)
