"""Step-level performance telemetry: flight recorder, MFU/cost
accounting, recompile counters, device gauges, live profiler capture.

PR 1 gave serving *request*-level observability; this module opens the
engine's *step* loop — where all the throughput lives — with four
pieces, all dependency-free:

  * **Step flight recorder** (`StepTelemetry`): one bounded-ring record
    per engine step (kind prefill/decode/decode_scan/spec/mixed —
    mixed records additionally split occupancy into decode rows vs
    prefill-chunk rows vs idle rows and feed the
    `cake_mixed_step_rows_total{kind}` counters —, attention
    impl, batch occupancy, tokens emitted, page-pool free/total,
    dispatch wall seconds, device seconds, per-step MFU / HBM
    utilization, whether the step compiled). Served at
    `GET /api/v1/steps`, optionally appended as JSONL (`--step-log`,
    via the shared obs/jsonl.py writer).

  * **XLA cost accounting** (`JitAccountant` + `lower_cost`): the first
    dispatch of each (step fn, signature) pair runs one extra *lowering*
    (trace only — no XLA compile) and reads
    ``Lowered.cost_analysis()`` FLOPs + bytes-accessed. Combined with
    the measured step time this yields `cake_step_mfu{kind}` and
    `cake_step_hbm_util{kind}`; every new signature also bumps
    `cake_jit_compiles_total{fn}`, and its step record carries `jit_s`:
    what the trace, the lowering, the backend (compile or cache load)
    and the cost analysis took across it (obs/startup.py's counters,
    fed by jax.monitoring). A rising compile counter during
    steady-state decode is a shape-leak recompilation storm —
    previously invisible.

  * **Device gauges** (`refresh_device_gauges`): per-device HBM
    live/peak/limit bytes from `Device.memory_stats()` — a graceful
    no-op on backends without stats (CPU). Refreshed at scrape time and
    on the serving heartbeat (parallel/health.py).

  * **Live profiler capture** (`ProfileCapture` / module `PROFILER`):
    `POST /api/v1/profile {"seconds": N}` grabs a jax.profiler trace
    (`.xplane.pb`; a Perfetto JSON too with `"perfetto": true`) from
    the *running* serving process (utils/profiling.capture_trace),
    single-flight-guarded — a second concurrent capture gets
    `ProfileBusyError` (HTTP 409).

  * **Step phases** (`StepTelemetry.span`): the engine loop times its
    own phases (PHASES: admin, schedule, build, gate, dispatch,
    sample, fetch, record, emit, release) into each record's `phases` and
    `gap_s`, and shows them as `cake/<phase>` TraceAnnotations carrying
    the step number, so a capture's host plane joins `/api/v1/steps` by
    step.

  * **The engine thread's clock** (PR 50): a record's `loop_s` is the
    thread's seconds since the record before it (or since a `wait`
    ended), so `loop_s` less the sum of its `phases` is what lay
    outside every span; `offcpu` is, by phase, a span's wall seconds
    less its thread's CPU seconds (`time.thread_time`): under `fetch`
    and `dispatch` the device or the runtime, under any other phase
    the thread WAITING while it had work (the interpreter lock, a
    collection on another thread, the kernel's scheduler), under
    `none` the same of the loop outside every span; `gc_s`,
    `gc_n`, `gc_max_s` are the process's collections since the record
    before (one `gc.callbacks` hook, `_GcWatch`; a generation-2
    collection is a `cake/gc` annotation on the thread that ran it).

  * **Stretch boundaries**: why a chain of in-flight steps ended
    (BREAKS: `chain_break` on the next record that is not chained,
    `cake_chain_breaks_total{cause}`, a stat of that step's first
    `cake/dispatch`), what the host did while the device drained
    (PARTS: one level under the phases, `StepTelemetry.part`, a
    record's `parts` and `rows_admitted`), and whether a chained step's
    successor reached the device after it had finished (`late`, from
    the step's own fetch against LATE_FETCH_S). `detok_ids`: the ids
    the streaming detokeniser handed to the tokenizer's decode inside
    `emit` (a few a token, however long the output). `stream_chunks`,
    `stream_direct`, `stream_wakes`: the deltas that `emit` left for
    the API server's stream writer (api/stream_writer.py: an append
    each), those it handed to a callback a token, and the times it
    signalled the writer, once where the span closed
    (`cake_stream_chunks_total{path}`, `cake_stream_writer_wakes_total`).

MFU here is model-FLOPs utilization: (program FLOPs from
cost_analysis) / (peak chip FLOP/s x measured step seconds), clamped to
1.0. A device kind that is not in the peak table (a CPU) has no peak,
so its records carry no mfu/hbm_util at all. HBM utilization is
bytes-accessed over the chip's HBM bandwidth the same way. Both are estimates from *unoptimized* HLO:
fusion changes the real byte traffic, but the trend per step and the
fold-vs-pallas/bucket-vs-bucket comparisons are exactly what they are
for.
"""

from __future__ import annotations

import functools
import gc
import logging
import threading
import time
import weakref
from collections import deque
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from cake_tpu.obs import metrics as _m
from cake_tpu.obs import startup as _startup
from cake_tpu.obs.jsonl import JsonlAppender

log = logging.getLogger(__name__)

# Peak dense bf16 matmul FLOP/s by device_kind substring (public TPU
# specs), first match wins. The program's single table (the
# benchmark keeps its own, benchmarks/harness/peaks.py, keyed by exact
# device_kind). A kind that is not in the table has no peak: the
# lookups return None and no utilization is reported for it (a CPU
# lane prints no mfu/hbm_util at all).
PEAK_FLOPS = [
    ("v5 lite", 197e12), ("v5e", 197e12),
    ("v5p", 459e12), ("v5", 459e12),
    ("v6", 918e12),
    ("v4", 275e12),
    ("v3", 123e12),
]

# HBM bandwidth (bytes/s) by device_kind substring (same entries).
HBM_BPS = [
    ("v5 lite", 819e9), ("v5e", 819e9),
    ("v5p", 2765e9), ("v5", 2765e9),
    ("v6", 1640e9),
    ("v4", 1228e9),
    ("v3", 900e9),
]


def _lookup(table, kind: str) -> Optional[float]:
    k = (kind or "").lower()
    return next((v for sub, v in table if sub in k), None)


def peak_flops_for(kind: str) -> Optional[float]:
    return _lookup(PEAK_FLOPS, kind)


def hbm_bps_for(kind: str) -> Optional[float]:
    return _lookup(HBM_BPS, kind)


# -- metric families (module-level so the lint/README coverage gate sees
#    them whether or not an engine ran) --------------------------------------

_STEPS_TOTAL = _m.counter(
    "cake_steps_total",
    "Engine steps recorded by the flight recorder, by step kind",
    labelnames=("kind",))
_DECODE_CHAINED = _m.counter(
    "cake_decode_steps_chained_total",
    "Decode steps dispatched from the tokens still on the device while "
    "the step before them was in flight (of cake_steps_total"
    "{kind=\"decode\"})")
_MIXED_CHAINED = _m.counter(
    "cake_mixed_steps_chained_total",
    "Mixed steps dispatched while the step before them was in flight, "
    "their decode rows fed from the tokens still on the device (of "
    "cake_steps_total{kind=\"mixed\"})")
_CHAINED_LATE = _m.counter(
    "cake_chained_steps_late_total",
    "Chained decode and mixed steps whose own fetch returned at once "
    "(under obs/steps.LATE_FETCH_S): the device had finished them "
    "before the host sent the step after them (of "
    "cake_decode_steps_chained_total + cake_mixed_steps_chained_total)")
_CHAIN_BREAKS = _m.counter(
    "cake_chain_breaks_total",
    "Steps dispatched with nothing in flight before them, by why the "
    "chain before them ended (obs/steps.BREAKS; idle = the loop had "
    "nothing to run)",
    labelnames=("cause",))
_STREAM_CHUNKS = _m.counter(
    "cake_stream_chunks_total",
    "Streamed deltas by who writes them: the API server's stream "
    "writer (an append on the engine thread, one wake-up a step) or a "
    "handler (a callback a token: an embedder's, a test's, a stream "
    "the writer gave back to its handler thread)",
    labelnames=("path",))
_STREAM_WAKES = _m.counter(
    "cake_stream_writer_wakes_total",
    "Times the engine thread signalled the stream writer: once where "
    "an emit span that left it deltas closed")
_GC_PAUSE = _m.counter(
    "cake_gc_pause_seconds_total",
    "Seconds the process spent in the interpreter's cyclic collections "
    "while a step recorder was open, by generation (every thread waits "
    "out a collection; gc_s on the step record)",
    labelnames=("generation",))
_GC_COLLECTIONS = _m.counter(
    "cake_gc_collections_total",
    "The interpreter's cyclic collections while a step recorder was "
    "open, by generation (gc_n on the step record)",
    labelnames=("generation",))
_STEP_DISPATCH = _m.histogram(
    "cake_step_dispatch_seconds",
    "Per-step dispatch wall seconds, by step kind",
    labelnames=("kind",))
_STEP_MFU = _m.gauge(
    "cake_step_mfu",
    "Last step's model-FLOPs utilization (cost_analysis FLOPs / peak "
    "chip FLOPs x step seconds), by step kind",
    labelnames=("kind",))
_STEP_HBM = _m.gauge(
    "cake_step_hbm_util",
    "Last step's HBM-bandwidth utilization (cost_analysis bytes / HBM "
    "bandwidth x step seconds), by step kind",
    labelnames=("kind",))
_JIT_COMPILES = _m.counter(
    "cake_jit_compiles_total",
    "New jit signatures dispatched per step fn (a rise during "
    "steady-state decode is a shape-leak recompilation storm)",
    labelnames=("fn",))
_DEV_HBM_IN_USE = _m.gauge(
    "cake_device_hbm_bytes_in_use",
    "Live HBM bytes per device (Device.memory_stats; absent on CPU)",
    labelnames=("device",))
_DEV_HBM_PEAK = _m.gauge(
    "cake_device_hbm_peak_bytes",
    "Peak HBM bytes per device since process start",
    labelnames=("device",))
_DEV_HBM_LIMIT = _m.gauge(
    "cake_device_hbm_bytes_limit",
    "HBM byte capacity per device",
    labelnames=("device",))
_MIXED_ROWS = _m.counter(
    "cake_mixed_step_rows_total",
    "Row-slots processed by mixed continuous-batching steps, by row "
    "kind (decode = one-token decode rows, prefill = prefill-chunk "
    "rows, idle = empty slots in the launch)",
    labelnames=("kind",))
_MIXED_TOKENS = _m.counter(
    "cake_mixed_tokens_total",
    "Tokens the mixed steps held: one a decode row, a window's real "
    "tokens a prefill-chunk row")
_MIXED_TOKENS_COMPUTED = _m.counter(
    "cake_mixed_tokens_computed_total",
    "Token positions the mixed steps' layers ran over: the packed sizes "
    "of each step's dispatches (over cake_mixed_tokens_total: how well "
    "the sizes fit the traffic)")
_MIXED_ATTN_TILES = _m.counter(
    "cake_mixed_attn_q_tiles_total",
    "Query tiles the mixed attention kernel folded a live page into, "
    "summed over the mixed steps' active rows: one for a row whose real "
    "queries lie in its first tile (a decode row), the window's for any "
    "other (ops/ragged_paged_attention.mixed_q_tiles)")
_MIXED_ATTN_TILES_WINDOW = _m.counter(
    "cake_mixed_attn_q_tiles_window_total",
    "Query tiles in the windows of the mixed steps' active rows: what a "
    "kernel that folded every row's whole window would fold (over it, "
    "cake_mixed_attn_q_tiles_total is the share the kernel does)")
_DECODE_ATTN_PAGES = _m.counter(
    "cake_decode_attn_pages_total",
    "KV pages the decode attention kernel streams a layer, summed over "
    "the decode steps' active rows: position // page + 1 for each, "
    "counted on the host from the positions it dispatches")
_DECODE_ATTN_PAGES_TABLE = _m.counter(
    "cake_decode_attn_pages_table_total",
    "Entries of the decode steps' page tables, slots x pages a slot: "
    "what a kernel that stepped through the whole table would visit "
    "(over it, cake_decode_attn_pages_total is the share that holds "
    "work)")
_WINDOW_PAGES = _m.counter(
    "cake_mla_window_pages_total",
    "Pages a query tile of the latent window kernel walks, summed over "
    "the layers that run it and the mixed steps' dispatches: the live "
    "pages of the window's row (a sliding layer's ring), counted on the "
    "host from the window's last position "
    "(ops/mla_attention.window_walk)")
_WINDOW_FOLDS = _m.counter(
    "cake_mla_window_folds_total",
    "Softmax updates those pages take a query tile, a block of pages "
    "each (under it, cake_mla_window_pages_total is the pages a fold "
    "shares one accumulator pass among)")
_MLA_DECODE_PAGES = _m.counter(
    "cake_mla_decode_pages_total",
    "Pages the latent page-walking kernel (cake_mla_decode_attn) walks "
    "for the single-token rows of the decode steps and of the mixed "
    "steps' dispatches, summed over the layers that run it: position "
    "// page + 1 a row, counted on the host from the positions it "
    "dispatches (ops/mla_attention.pages_walk)")
_MLA_DECODE_FOLDS = _m.counter(
    "cake_mla_decode_folds_total",
    "Softmax updates those pages take, a block of pages each (under it, "
    "cake_mla_decode_pages_total is the pages a fold shares one "
    "accumulator pass among: ops/mla_attention.decode_block)")
_MIXED_ATTN_PAGES = _m.counter(
    "cake_mixed_attn_pages_total",
    "KV pages the mixed attention kernel walks a layer, summed over the "
    "rows (or a window's entries) of the mixed steps' calls: from the "
    "page of a row's first query's first key to that of its last real "
    "query, none for an idle row, counted on the host from the positions "
    "it dispatches (ops/ragged_paged_attention.mixed_walk)")
_MIXED_ATTN_PAGES_TABLE = _m.counter(
    "cake_mixed_attn_pages_table_total",
    "Entries of those calls' page tables, rows x pages a row: what a "
    "grid over (row, page) stepped through (over it, "
    "cake_mixed_attn_pages_total is the share that holds work)")
_MIXED_ATTN_FOLDS = _m.counter(
    "cake_mixed_attn_folds_total",
    "Softmax updates those pages take, a block of pages each (under it, "
    "cake_mixed_attn_pages_total is the pages a fold shares one "
    "accumulator pass among: ops/ragged_paged_attention.mixed_block)")
# The step programs' counters: (record key, series) by the group a
# family's trunk returns them in. The ORDER of a program's vector is
# the trunk's and is stated beside it (a family's `counters`,
# models/family.py); COUNTER_SERIES below maps key -> series once, and a
# recorder is given its engine's keys (StepTelemetry(counters=)).
#
# sparse-expert counters, computed in the step program from the group
# sizes its grouped matmuls walk (ops/moe.MoEStats, summed or averaged
# over the layers by paged.scan_layers_paged_stats) and fetched with the
# sampled tokens; absent for a dense model
MOE_COUNTERS = (
    ("moe_rows", _m.counter(
        "cake_moe_rows_total",
        "(token, expert) rows the expert matmuls computed, all layers")),
    ("moe_rows_padded", _m.counter(
        "cake_moe_rows_padded_total",
        "Rows the expert matmuls' tiles covered, tile padding included")),
    ("moe_load_max", _m.counter(
        "cake_moe_expert_load_max",
        "Tokens on a layer's busiest expert, mean over layers, summed "
        "over steps (over cake_moe_expert_load_mean: the imbalance)")),
    ("moe_load_mean", _m.counter(
        "cake_moe_expert_load_mean",
        "Tokens on a layer's average expert, mean over layers, summed "
        "over steps")),
    ("moe_experts_touched", _m.counter(
        "cake_moe_experts_touched_total",
        "Experts with at least one token, all layers: the expert "
        "weights the steps had to read")),
)
# a step program whose layers hold a SHARE of their router's experts,
# or select their keys (models/moe/glm_dsa.trunk); the rows above then
# count the held experts' rows alone
DSA_COUNTERS = (
    ("moe_rows_routed", _m.counter(
        "cake_moe_rows_routed_total",
        "(token, expert) pairs the routers chose over ALL their "
        "experts, all layers (over cake_moe_rows_total: the share "
        "computed here)")),
    ("dsa_keys_visible", _m.counter(
        "cake_dsa_keys_visible_total",
        "Keys visible to the query tokens, summed over tokens and "
        "attention layers")),
    ("dsa_keys_selected", _m.counter(
        "cake_dsa_keys_selected_total",
        "Keys the sparse indexer selected (attended), summed over "
        "tokens and attention layers")),
    ("dsa_rows_distinct", _m.counter(
        "cake_dsa_rows_distinct_total",
        "Distinct cache rows a dispatch selected, summed over "
        "attention layers: what no kernel can avoid reading")),
    ("dsa_index_layers", _m.counter(
        "cake_dsa_index_layers_total",
        "Attention layers that computed their key sets, summed over "
        "dispatches")),
    ("dsa_index_reused", _m.counter(
        "cake_dsa_index_reused_total",
        "Attention layers that reused the set of the layer below, "
        "summed over dispatches")),
    ("dsa_select_keys_walked", _m.counter(
        "cake_dsa_select_keys_walked_total",
        "Keys in the blocks cake_dsa_select walked (up to the window's "
        "last position), summed over windows and indexer layers")),
    ("dsa_select_keys_table", _m.counter(
        "cake_dsa_select_keys_table_total",
        "Keys the table is wide for those windows and layers (over it, "
        "cake_dsa_select_keys_walked_total: the share of the table a "
        "selection costs)")),
    ("dsa_index_keys_scored", _m.counter(
        "cake_dsa_index_keys_scored_total",
        "Keys in the blocks cake_dsa_index visited (up to the window's "
        "last position), summed over windows and indexer layers (over "
        "cake_dsa_select_keys_table_total: the share of the table a "
        "score pass costs)")),
)
# a model with recurrent blocks (models/moe/nemotron_h.trunk): the rows'
# recurrent state and the two forms of the scan
SSM_COUNTERS = (
    ("ssm_state_rows", _m.counter(
        "cake_ssm_state_rows_total",
        "Rows whose recurrent state a step read and wrote, summed over "
        "Mamba blocks (a row with no token in a dispatch costs none)")),
    ("ssm_tokens_scanned", _m.counter(
        "cake_ssm_tokens_scanned_total",
        "Tokens through the chunked scan (a prompt's windows), summed "
        "over Mamba blocks")),
    ("ssm_tokens_stepped", _m.counter(
        "cake_ssm_tokens_stepped_total",
        "Tokens through the one-step recurrence (a row's single "
        "token), summed over Mamba blocks")),
    ("ssm_state_resets", _m.counter(
        "cake_ssm_state_resets_total",
        "Rows whose recurrent state a step zeroed: a request took the "
        "slot")),
)
SSM_STATE_BYTES = _m.gauge(
    "cake_ssm_state_bytes",
    "Bytes of the rows' recurrent state beside the page pool (SSM state "
    "and conv tails, every Mamba block, every slot)")


# a model with convolutions inside attention (models/moe/zaya.trunk)
CCA_COUNTERS = (
    ("cca_tail_rows", _m.counter(
        "cake_cca_tail_rows_total",
        "Rows whose conv tail a step read and wrote, summed over layers "
        "(a row with no token in a dispatch costs none)")),
    ("router_choice_by_bias", _m.counter(
        "cake_cca_router_choice_by_bias_total",
        "(token, layer) choices of an expert that the router's "
        "balancing bias changed (0 where nothing reads the bias)")),
)
CCA_TAIL_BYTES = _m.gauge(
    "cake_cca_tail_bytes",
    "Bytes of the rows' conv tails beside the page pool (every layer, "
    "every slot)")


# a model with sliding-window latent layers beside its full ones
# (models/moe/glm_dsa.trunk), whose dsa_* then count the FULL layers
# alone
SWA_COUNTERS = (
    ("swa_keys_visible", _m.counter(
        "cake_swa_keys_visible_total",
        "Keys visible to the query tokens (position + 1), summed over "
        "tokens and sliding-window layers")),
    ("swa_keys_attended", _m.counter(
        "cake_swa_keys_attended_total",
        "Keys the sliding-window layers attended (the visible ones "
        "inside the window), summed over tokens and sliding layers")),
    ("swa_layers", _m.counter(
        "cake_swa_layers_total",
        "Sliding-window layers run, summed over dispatches")),
)
# a model whose latent layers attend every visible key and whose
# router is limited to groups of experts (deepseek_v2:
# models/moe/glm_dsa.trunk's dense kind of layer)
MLA_DENSE_COUNTERS = (
    ("moe_tokens_group_held", _m.counter(
        "cake_moe_tokens_group_held_total",
        "Tokens whose chosen expert groups include the group held "
        "here, summed over expert layers (over cake_moe_rows_routed_"
        "total / experts a token: the share of tokens this chip's "
        "group serves)")),
    ("mla_keys_attended", _m.counter(
        "cake_mla_keys_attended_total",
        "Keys the single-token rows attended (position + 1 each), "
        "summed over rows and latent layers: what cake_mla_decode_attn "
        "walked")),
)
# a router wider than the expert matrices it indexes (longcat_flash's
# zero-compute experts: models/moe/glm_dsa.trunk's shortcut layers)
ZERO_EXPERT_COUNTERS = (
    ("moe_pairs_zero", _m.counter(
        "cake_moe_pairs_zero_total",
        "Routed (token, expert) pairs that chose a zero-compute expert "
        "(an index past the router's routed experts: the pair adds its "
        "weight times the layer's input and reaches no matrix), summed "
        "over expert layers (over cake_moe_rows_routed_total: the share "
        "of pairs that cost nothing)")),
)
# a model with Kimi Delta Attention layers
# (models/moe/bailing_hybrid.trunk): the two forms of the delta rule and
# the rows' matrix state
KDA_COUNTERS = (
    ("kda_tokens_chunked", _m.counter(
        "cake_kda_tokens_chunked_total",
        "Tokens through the chunked delta rule (a prompt's windows), "
        "summed over KDA layers")),
    ("kda_tokens_stepped", _m.counter(
        "cake_kda_tokens_stepped_total",
        "Tokens through the one-step delta rule (a row's single token), "
        "summed over KDA layers")),
    ("kda_state_rows", _m.counter(
        "cake_kda_state_rows_total",
        "Rows whose matrix state a step read and wrote, summed over KDA "
        "layers (a row with no token in a dispatch costs none)")),
)
KDA_STATE_BYTES = _m.gauge(
    "cake_kda_state_bytes",
    "Bytes of the rows' KDA state beside the page pool (the float32 "
    "matrix a head and the conv tails, every KDA layer, every slot)")
# a model whose sliding-window layers are GQA over a K/V ring a row
# beside full GQA layers on the allocator's pages
# (models/moe/exaone_moe.trunk; its sliding layers count SWA_COUNTERS'
# three as dots3_note's do)
GQA_WINDOW_COUNTERS = (
    ("gqa_full_keys_attended", _m.counter(
        "cake_gqa_full_keys_attended_total",
        "Keys the full layers attended (position + 1 a query: every "
        "visible key), summed over tokens and full layers")),
    ("gqa_window_keys_single", _m.counter(
        "cake_gqa_window_keys_single_total",
        "Keys the single-token rows attended in the sliding layers "
        "(min(position + 1, window) each: what the banded "
        "cake_decode_attn calls folded), summed over rows and sliding "
        "layers")),
    ("gqa_full_keys_single", _m.counter(
        "cake_gqa_full_keys_single_total",
        "Keys the single-token rows attended in the full layers "
        "(position + 1 each: what the full cake_decode_attn calls "
        "folded), summed over rows and full layers")),
    ("gqa_window_pages_walked", _m.counter(
        "cake_gqa_window_pages_walked_total",
        "Ring pages the single-token rows walked in the sliding layers "
        "(the pages that hold the band), summed over rows and sliding "
        "layers")),
    ("gqa_full_pages_walked", _m.counter(
        "cake_gqa_full_pages_walked_total",
        "Pool pages the single-token rows walked in the full layers "
        "(position // page + 1), summed over rows and full layers")),
    ("gqa_rows_single", _m.counter(
        "cake_gqa_rows_single_total",
        "Rows that held one token in a dispatch (what the decode "
        "attention kernel served), summed over dispatches")),
    ("gqa_ring_pages_live", _m.counter(
        "cake_gqa_ring_pages_live_total",
        "Ring pages that hold keys of the rows with tokens in a "
        "dispatch (at most R a row), summed over dispatches")),
    ("gqa_full_pages_live", _m.counter(
        "cake_gqa_full_pages_live_total",
        "Full-pool pages those rows' contexts fill, summed over "
        "dispatches (over cake_gqa_ring_pages_live_total: what a layer "
        "of the other kind would hold)")),
)
# a model whose indexer selects keys over ORDINARY K/V pages
# (models/moe/keye_vl2.trunk; the dsa_* above count it as they count the
# latent families', gqa_rows_single and gqa_full_pages_live as
# exaone_moe's)
DSA_GQA_COUNTERS = (
    ("dsa_keys_single", _m.counter(
        "cake_dsa_keys_single_total",
        "Keys the single-token rows attended (min(position + 1, topk) "
        "each: the selected rows their cake_decode_attn calls folded), "
        "summed over rows and layers")),
    ("dsa_keys_scanned_single", _m.counter(
        "cake_dsa_keys_scanned_single_total",
        "Index keys the single-token rows' indexers scored (position + "
        "1 each: every visible key), summed over rows and layers")),
    ("dsa_walk_pages_single", _m.counter(
        "cake_dsa_walk_pages_single_total",
        "Pool pages the single-token rows walked under their selection's "
        "mask (position // page + 1 each: what cake_decode_attn(selected=) "
        "read to attend the selected keys where they lie), summed over "
        "rows and layers")),
    ("dsa_walk_rows_single", _m.counter(
        "cake_dsa_walk_rows_single_total",
        "Single-token rows that attended by that walk, summed over "
        "dispatches (over cake_gqa_rows_single_total: the share of them "
        "it serves)")),
)
# a model of power retention layers (models/moe/brumby.trunk): the rows'
# matrix state, the page pool of no layers beside it, and the two forms
RETENTION_COUNTERS = (
    ("retention_state_rows", _m.counter(
        "cake_retention_state_rows_total",
        "Rows whose retention state a step read and wrote, summed over "
        "layers (a row with no token in a dispatch costs none)")),
    ("retention_tokens_windowed", _m.counter(
        "cake_retention_tokens_windowed_total",
        "Tokens through the window form (a prompt's windows), summed "
        "over layers")),
    ("retention_tokens_stepped", _m.counter(
        "cake_retention_tokens_stepped_total",
        "Tokens through the one-step update (a row's single token: what "
        "cake_retention_step moved a state for), summed over layers")),
    ("retention_state_resets", _m.counter(
        "cake_retention_state_resets_total",
        "Rows whose retention state a step zeroed: a request took the "
        "slot")),
)
RETENTION_STATE_BYTES = _m.gauge(
    "cake_retention_state_bytes",
    "Bytes of the rows' retention state (the float32 matrix S and the "
    "normaliser z a K/V head, every layer, every slot) beside a page "
    "pool of no layers")
GQA_WINDOW_POOL_BYTES = _m.gauge(
    "cake_gqa_window_pool_bytes",
    "Bytes of the sliding-window layers' K and V pools beside the page "
    "pool (slots x ring pages, every sliding layer)")
COUNTER_SERIES = dict(MOE_COUNTERS + DSA_COUNTERS + SSM_COUNTERS
                      + CCA_COUNTERS + SWA_COUNTERS + MLA_DENSE_COUNTERS
                      + ZERO_EXPERT_COUNTERS
                      + KDA_COUNTERS + GQA_WINDOW_COUNTERS
                      + DSA_GQA_COUNTERS + RETENTION_COUNTERS)
# what a family's cache keeps beside the page pool (family.Beside.gauge)
BESIDE_POOL_BYTES = {"ssm_state_bytes": SSM_STATE_BYTES,
                     "cca_tail_bytes": CCA_TAIL_BYTES,
                     "kda_state_bytes": KDA_STATE_BYTES,
                     "gqa_window_pool_bytes": GQA_WINDOW_POOL_BYTES,
                     "retention_state_bytes": RETENTION_STATE_BYTES}


def refresh_page_gauges(engine) -> None:
    """KV page-pool occupancy gauges for a paged engine (no-op for
    dense). THE single definition — called at scrape time
    (api/server.py) and on the serving heartbeat (parallel/health.py),
    so the two sites cannot drift in names or help text."""
    if not getattr(engine, "paged", False):
        return
    try:
        # the pager is engine-thread state swapped wholesale by a live
        # reconfigure; its declared lock (_switch_lock) pins one
        # consistent pool for this scrape. NON-blocking on purpose: the
        # watchdog and /metrics run through here, and a switch wedged
        # on device work must never take the stall detector (or
        # observability) down with it — on contention the gauges keep
        # their last values for one scrape.
        if engine._switch_lock.acquire(blocking=False):
            try:
                n_total = engine.cache.n_pages
                # cakelint: skip[affinity] _switch_lock held via the non-blocking acquire above (the with-form the checker recognizes would block a wedged switch forever)
                n_free = engine._pager.free_pages
            finally:
                engine._switch_lock.release()
            _m.gauge("cake_engine_kv_pages_total",
                     "KV pages in the pool").set(n_total)
            _m.gauge("cake_engine_kv_pages_free",
                     "KV pages currently free").set(n_free)
        # prefix sharing (serve/engine.py sets this at admission /
        # release; re-set at scrape so a restarted scraper converges
        # without waiting for the next admission)
        _m.gauge("cake_prefix_pages_shared",
                 "Shared prefix pages currently mapped into admitted "
                 "slots' table rows (pool pages saved vs unshared "
                 "admission)").set(
            getattr(engine, "_prefix_pages_shared", 0))
        # KV tiering (cake_tpu/kv): host_tier owns the cake_kv_* gauges
        # AND their refresh — one public seam, so a scrape converges
        # without this module re-implementing the tier's accounting
        from cake_tpu.kv import host_tier as kv_host_tier
        kv_host_tier.refresh_gauges(engine.cache,
                                    getattr(engine, "_host_tier", None))
    except Exception:  # noqa: BLE001 — telemetry must never fail serving
        log.debug("page gauge refresh failed", exc_info=True)


def refresh_device_gauges() -> None:
    """Sync per-device HBM gauges from Device.memory_stats(). Graceful
    no-op on backends without stats (CPU): the gauges simply stay
    sample-less. Called at scrape time (api/server.py) and on the
    serving heartbeat (parallel/health.py)."""
    try:
        from cake_tpu.utils.profiling import device_memory_stats
        stats = device_memory_stats()
    except Exception:  # noqa: BLE001 — a scrape must never fail
        log.debug("device memory stats unavailable", exc_info=True)
        return
    for s in stats:
        if s.get("bytes_in_use") is None:
            continue   # backend without memory_stats (CPU)
        dev = str(s["device"])
        _DEV_HBM_IN_USE.labels(device=dev).set(float(s["bytes_in_use"]))
        if s.get("peak_bytes_in_use") is not None:
            _DEV_HBM_PEAK.labels(device=dev).set(
                float(s["peak_bytes_in_use"]))
        if s.get("bytes_limit") is not None:
            _DEV_HBM_LIMIT.labels(device=dev).set(float(s["bytes_limit"]))


# -- collections as events -----------------------------------------------------


class _GcWatch:
    """The interpreter's cyclic collections, process-wide: one
    `gc.callbacks` hook while any step recorder is open (`open` by
    StepTelemetry, `close` by its close()), two clock reads a
    collection. A collection runs on whichever thread's allocation
    crossed the threshold and holds the interpreter lock throughout,
    so every thread waits it out: the sums are the process's, by
    generation, and a recorder takes their delta a record (`mark`).
    The hook takes no lock (a collection can start inside any lock's
    critical section, a metric's included): the series on /metrics are
    brought up to the sums by `refresh_gc_series`, at scrape time. A
    generation-2 collection is also a `cake/gc` TraceAnnotation on its
    thread, so a capture's host plane shows it."""

    RECENT = 256      # pauses kept for a record's `gc_max_s`

    def __init__(self):
        # users alone, never the hook; re-entrant: a collection inside
        # open() may finalize another recorder, whose release closes
        self._lock = threading.RLock()
        self._users = 0
        self._annotation = None
        self._t0: Optional[float] = None
        self._open_ann = None
        self.seconds = [0.0, 0.0, 0.0]
        self.count = [0, 0, 0]
        self._recent: deque = deque(maxlen=self.RECENT)

    def open(self, annotation) -> None:
        with self._lock:
            self._users += 1
            if self._users == 1:
                self._annotation = annotation
                gc.callbacks.append(self._hook)

    def close(self) -> None:
        with self._lock:
            self._users -= 1
            if self._users == 0:
                gc.callbacks.remove(self._hook)
                # closed by a finalizer inside a collection: its second
                # call will not come
                ann, self._open_ann = self._open_ann, None
                if ann is not None:
                    ann.__exit__(None, None, None)

    def _hook(self, phase: str, info: dict) -> None:
        if phase == "start":
            if info["generation"] == 2:
                self._open_ann = self._annotation("cake/gc")
                self._open_ann.__enter__()
            self._t0 = time.perf_counter()
            return
        t1 = time.perf_counter()
        t0, self._t0 = self._t0, None
        ann, self._open_ann = self._open_ann, None
        if ann is not None:
            ann.__exit__(None, None, None)
        if t0 is None:      # hooked between a collection's two calls
            return
        g = info["generation"]
        self.seconds[g] += t1 - t0
        self.count[g] += 1
        self._recent.append(t1 - t0)

    def mark(self) -> Tuple[float, int]:
        """(seconds, collections) so far, all generations."""
        return sum(self.seconds), sum(self.count)

    def longest(self, last: int) -> float:
        """The longest of the last `last` collections (of the RECENT
        kept); 0.0 for none."""
        k = min(last, len(self._recent))
        return max(list(self._recent)[-k:]) if k > 0 else 0.0


GC_WATCH = _GcWatch()


def refresh_gc_series() -> None:
    """Bring cake_gc_pause_seconds_total / cake_gc_collections_total up
    to the watch's sums (scrape time: api/server.py)."""
    for g in range(3):
        _GC_PAUSE.labels(generation=str(g)).set_total(GC_WATCH.seconds[g])
        _GC_COLLECTIONS.labels(generation=str(g)).set_total(
            GC_WATCH.count[g])


# -- XLA cost accounting ------------------------------------------------------


@dataclass(frozen=True)
class CostInfo:
    """One compiled program's cost_analysis numbers (unoptimized HLO)."""

    flops: float = 0.0
    bytes_accessed: float = 0.0


def _normalize_cost(ca) -> Optional[CostInfo]:
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    if not isinstance(ca, dict):
        return None
    flops = float(ca.get("flops") or 0.0)
    nbytes = float(ca.get("bytes accessed") or 0.0)
    if flops <= 0.0 and nbytes <= 0.0:
        return None
    return CostInfo(flops=flops, bytes_accessed=nbytes)


def lower_cost(fn, args: tuple, kwargs: Optional[dict] = None
               ) -> Optional[CostInfo]:
    """FLOPs + bytes-accessed of fn(*args, **kwargs) via one extra
    LOWERING (trace only — `Lowered.cost_analysis()` runs HLO cost
    analysis without invoking the XLA backend compiler, so this costs a
    trace, not a compile). functools.partial layers and @wraps wrappers
    are unwrapped to reach the jitted callable; anything without
    `.lower` (or whose lowering/analysis raises) yields None — cost
    accounting is best-effort and must never fail a dispatch."""
    kwargs = dict(kwargs or {})
    seen = 0
    while seen < 8:   # bounded unwrap: partial chains + wraps chains
        if isinstance(fn, functools.partial):
            kwargs = {**fn.keywords, **kwargs}
            args = tuple(fn.args) + tuple(args)
            fn = fn.func
        elif getattr(fn, "__wrapped__", None) is not None \
                and not hasattr(fn, "lower"):
            fn = fn.__wrapped__
        else:
            break
        seen += 1
    lower = getattr(fn, "lower", None)
    if lower is None:
        return None
    try:
        return _normalize_cost(lower(*args, **kwargs).cost_analysis())
    except Exception:  # noqa: BLE001 — best-effort accounting
        log.debug("cost_analysis unavailable for %r",
                  getattr(fn, "__name__", fn), exc_info=True)
        return None


class JitAccountant:
    """Process-global compile/cost tracker keyed by (fn name, caller
    signature key). The engine's jit cache is process-global too (its
    step fns are module-level jitted functions), so a global accountant
    mirrors real retrace behavior: a second engine dispatching an
    already-compiled signature counts no compile."""

    def __init__(self):
        self._lock = threading.Lock()
        self._seen: Dict[tuple, Optional[CostInfo]] = {}

    def begin(self, name: str, key: tuple, cost_cb
              ) -> Tuple[bool, Optional[CostInfo], Optional[tuple]]:
        """(is_new_signature, cost, before). On a new signature:
        increments the per-fn compile counter and captures cost via
        cost_cb() (called BEFORE the dispatch executes, while donated
        buffers are still alive), timed into
        cake_jit_cost_analysis_seconds_total; `before` is
        obs/startup.seconds() read ahead of it, so that whoever records
        the step can say what the signature's program cost to make."""
        with self._lock:
            if key in self._seen:
                return False, self._seen[key], None
        before = _startup.seconds()
        cost = None
        try:
            with _startup.PROGRAMS.costing():
                cost = cost_cb()
        except Exception:  # noqa: BLE001
            log.debug("cost callback failed for %s", name, exc_info=True)
        with self._lock:
            if key in self._seen:   # racing thread won
                return False, self._seen[key], None
            self._seen[key] = cost
        _JIT_COMPILES.labels(fn=name).inc()
        return True, cost, before


ACCOUNTANT = JitAccountant()


def _no_cost() -> None:
    return None


class _JitStep:
    """Handle returned by StepTelemetry.jit_step: `.new` says this
    dispatch compiles a fresh signature, `.cost` carries the program's
    CostInfo."""

    __slots__ = ("new", "cost")

    def __init__(self, new: bool, cost: Optional[CostInfo]):
        self.new = new
        self.cost = cost


# -- flight recorder ----------------------------------------------------------

# step kinds whose records carry decode throughput (utilization
# aggregation weights these; prefill is reported per-kind only).
# "mixed" belongs here: a mixed step IS the decode step with prefill
# chunks riding along — excluding it would blind the MFU gauge to the
# very path token-level continuous batching exists to improve.
_DECODE_KINDS = ("decode", "decode_scan", "spec", "mixed")


def _sig(v: Optional[float], digits: int = 6) -> Optional[float]:
    """Round to significant digits (utilization exports: decimal-place
    rounding would collapse legitimately tiny values to 0.0)."""
    return float(f"%.{digits}g" % v) if v is not None else None


@dataclass
class StepRecord:
    """One engine step. dispatch_s is host wall to get the work onto
    the device (for double-buffered bursts, the dispatch half alone);
    device_s is the measured completion wall (the fetch half, a proxy
    for device time on sync paths); wall_s the whole step. A step
    dispatched while the one before it was in flight (`chained`) has
    wall_s = its fetch's end - the previous step's fetch's end, the
    time it added to the loop, and device_s the same."""

    step: int
    ts: float                      # wall-clock
    kind: str                      # prefill | decode | decode_scan | spec
                                   # | mixed
    impl: str                      # dense | ring | paged-fold | ... | custom
    rows: int                      # batch occupancy this step
    tokens: int                    # tokens emitted by this step
    dispatch_s: float
    device_s: float
    wall_s: float
    mfu: Optional[float] = None
    hbm_util: Optional[float] = None
    pages_free: Optional[int] = None
    pages_total: Optional[int] = None
    compiled: bool = False         # this step compiled a new signature
    # mixed-step occupancy split (token-level continuous batching):
    # decode rows vs prefill-chunk rows vs idle rows in the launch
    rows_decode: Optional[int] = None
    rows_prefill: Optional[int] = None
    rows_idle: Optional[int] = None
    # a mixed step's tokens, and the positions its layers ran over (the
    # packed sizes of its dispatches, summed)
    tokens_real: Optional[int] = None
    tokens_computed: Optional[int] = None
    # a mixed step whose rows go through the mixed attention kernel:
    # the query tiles it folds for the step's active rows, and the
    # tiles of their whole windows
    attn_q_tiles: Optional[int] = None
    attn_q_tiles_window: Optional[int] = None
    # a decode step whose rows go through the decode attention kernel:
    # the pages it streams a layer for the step's active rows, and the
    # entries of the whole page table
    attn_pages: Optional[int] = None
    attn_pages_table: Optional[int] = None
    # a mixed step of a latent family: the pages a query tile of the
    # window kernel walks over the step's dispatches and layers, and
    # the softmax updates they take
    window_pages: Optional[int] = None
    window_folds: Optional[int] = None
    # a decode or mixed step of a family whose single-token rows walk
    # their latent pages (cake_mla_decode_attn): the pages those rows
    # walk over the step's dispatches and layers, and the softmax
    # updates they take
    mla_decode_pages: Optional[int] = None
    mla_decode_folds: Optional[int] = None
    # a mixed step whose family tells the host how it calls the mixed
    # attention kernel: the pages the kernel walks a layer over the
    # step's calls, the entries of their page tables, and the softmax
    # updates the pages take
    mixed_attn_pages: Optional[int] = None
    mixed_attn_pages_table: Optional[int] = None
    mixed_attn_folds: Optional[int] = None
    # rids whose rows this step's dispatched batch contained (bounded
    # by the engine's slot count) — the per-request explain endpoint
    # (obs/timeline.py) selects a request's steps through this
    rids: Optional[Tuple[int, ...]] = None
    # host seconds by step phase (StepTelemetry.span) since the previous
    # record: the emit and admin work that followed the previous step,
    # then this step's schedule / build / dispatch / sample / fetch
    phases: Optional[Dict[str, float]] = None
    # end of the previous step's fetch -> start of this step's first
    # dispatch: how long the engine left the device with nothing queued
    # (0.0 for a chained step: the device had the step before it)
    gap_s: Optional[float] = None
    # a decode or mixed step: dispatched from the previous step's
    # on-device carry while that step was still in flight
    chained: Optional[bool] = None
    # the step programs' counters since the previous record that
    # carried them, by record key, in the order of the programs' vector
    moe: Optional[Dict[str, float]] = None
    # a step that is not chained: why the chain before it ended (one of
    # BREAKS; absent where no chain ended or the loop never waited: the
    # engine's first step), and the rows admitted since the record
    # before it
    chain_break: Optional[str] = None
    rows_admitted: Optional[int] = None
    # host seconds by part of a phase (PARTS) since the previous record
    parts: Optional[Dict[str, float]] = None
    # the ids handed to the tokenizer's decode by the detokenisation
    # inside the `emit` span since the previous record (both decodes of
    # a token counted); absent where nothing was detokenised
    detok_ids: Optional[int] = None
    # the deltas the `emit` span left for the stream writer since the
    # previous record, those it handed to a per-token callback, and the
    # signals to the writer (one a span that left it any); absent at 0
    stream_chunks: Optional[int] = None
    stream_direct: Optional[int] = None
    stream_wakes: Optional[int] = None
    # a chained step: the seconds its own fetch waited, and whether
    # that was no wait at all (under LATE_FETCH_S): the device had
    # finished the step before the host sent the one after it
    fetch_wait_s: Optional[float] = None
    late: Optional[bool] = None
    # engine-thread seconds from the record before this one (or from
    # the end of a `wait`) to this one, by perf_counter: less the sum
    # of `phases`, what lay outside every span. Absent on a recorder's
    # first record
    loop_s: Optional[float] = None
    # by phase, a span's wall seconds less its thread's CPU seconds:
    # the thread was not running (under fetch and dispatch it waited
    # for the device or the runtime; under any other phase it had work
    # and could not do it); under "none", the same of loop_s outside
    # every span. Signed differences of two clocks: a few microseconds
    # under 0 where the thread ran throughout, and on a host whose CPU
    # clock ticks coarsely right only in sums
    offcpu: Optional[Dict[str, float]] = None
    # the process's cyclic collections since the record before: their
    # seconds, their number, the longest one (_GcWatch)
    gc_s: Optional[float] = None
    gc_n: Optional[int] = None
    gc_max_s: Optional[float] = None
    # a `compiled` step: what making its programs took from the first
    # new signature's accounting to this record, by part
    # (obs/startup.SECONDS: trace, lower, backend, the cache's load
    # inside backend, cost_analysis)
    jit_s: Optional[Dict[str, float]] = None

    def to_dict(self) -> Dict:
        out = {
            "step": self.step,
            "ts": round(self.ts, 6),
            "kind": self.kind,
            "impl": self.impl,
            "rows": self.rows,
            "tokens": self.tokens,
            "dispatch_s": round(self.dispatch_s, 6),
            "device_s": round(self.device_s, 6),
            "wall_s": round(self.wall_s, 6),
            "compiled": self.compiled,
        }
        # absent, not null or 0, when the device kind has no peak in
        # the table. Significant digits, not decimal places: a
        # compile-inflated step's 1e-7 MFU must stay nonzero
        if self.mfu is not None:
            out["mfu"] = _sig(self.mfu)
        if self.hbm_util is not None:
            out["hbm_util"] = _sig(self.hbm_util)
        if self.pages_total is not None:
            out["pages_free"] = self.pages_free
            out["pages_total"] = self.pages_total
        if self.rows_decode is not None:
            out["rows_decode"] = self.rows_decode
            out["rows_prefill"] = self.rows_prefill
            out["rows_idle"] = self.rows_idle
        if self.tokens_computed is not None:
            out["tokens_real"] = self.tokens_real
            out["tokens_computed"] = self.tokens_computed
        if self.attn_q_tiles is not None:
            out["attn_q_tiles"] = self.attn_q_tiles
            out["attn_q_tiles_window"] = self.attn_q_tiles_window
        if self.attn_pages is not None:
            out["attn_pages"] = self.attn_pages
            out["attn_pages_table"] = self.attn_pages_table
        if self.window_pages is not None:
            out["window_pages"] = self.window_pages
            out["window_folds"] = self.window_folds
        if self.mla_decode_pages is not None:
            out["mla_decode_pages"] = self.mla_decode_pages
            out["mla_decode_folds"] = self.mla_decode_folds
        if self.mixed_attn_pages is not None:
            out["mixed_attn_pages"] = self.mixed_attn_pages
            out["mixed_attn_pages_table"] = self.mixed_attn_pages_table
            out["mixed_attn_folds"] = self.mixed_attn_folds
        if self.rids is not None:
            out["rids"] = list(self.rids)
        if self.phases:
            out["phases"] = {k: round(v, 6)
                             for k, v in self.phases.items()}
        if self.gap_s is not None:
            out["gap_s"] = round(self.gap_s, 6)
        if self.chained is not None:
            out["chained"] = self.chained
        if self.chain_break is not None:
            out["chain_break"] = self.chain_break
        if self.rows_admitted is not None:
            out["rows_admitted"] = self.rows_admitted
        if self.parts:
            out["parts"] = {k: round(v, 6) for k, v in self.parts.items()}
        if self.detok_ids:
            out["detok_ids"] = self.detok_ids
        for key in ("stream_chunks", "stream_direct", "stream_wakes"):
            if getattr(self, key):
                out[key] = getattr(self, key)
        if self.fetch_wait_s is not None:
            out["fetch_wait_s"] = round(self.fetch_wait_s, 6)
            out["late"] = self.late
        if self.loop_s is not None:
            out["loop_s"] = round(self.loop_s, 6)
        if self.offcpu:
            out["offcpu"] = {k: round(v, 6) for k, v in self.offcpu.items()}
        if self.gc_n is not None:
            out["gc_s"] = round(self.gc_s, 6)
            out["gc_n"] = self.gc_n
            out["gc_max_s"] = round(self.gc_max_s, 6)
        if self.moe is not None:
            out.update((key, round(v, 3)) for key, v in self.moe.items())
        if self.jit_s is not None:
            out["jit_s"] = {k: round(v, 6) for k, v in self.jit_s.items()}
        return out


# The step-phase vocabulary (PERF.md §3 lists what each covers in
# serve/engine.py). "gate": a stretch's question whether it may
# dispatch ahead (_drive_burst). "record": the writing of a step's
# record, whose seconds go to the record AFTER it (the loop's clock
# turns over where this span starts). "release": a completed dispatch's
# device outputs die (the runtime gives the interpreter lock away while
# it frees them). "wait" is the idle engine: a trace annotation only,
# it belongs to no step and closes the open phase table.
PHASES = ("admin", "schedule", "build", "gate", "dispatch", "sample",
          "fetch", "record", "emit", "release", "wait")

# Why a chain of in-flight steps ended (serve/engine._drive_burst's
# gate, the first condition that held, in the order it tests them):
# the host wanted the loop back (stop, a request in the queue, a
# cancel, a command); this engine or this step may not chain (sync:
# multi-host, the paged speculative engine, no sampled program, a
# caller whose plan is about to change); STRETCH_STEPS dispatches
# (stretch_cap); a row of the plan finished in the last emit; no row
# has budget left; a row would pass max_seq_len (window_end). "idle" is
# the `wait` span's: the loop had nothing to run.
BREAKS = ("stop", "queue", "cancel", "command", "sync", "stretch_cap",
          "row_finished", "budget", "window_end", "idle")

# The parts of a phase, "<phase>.<part>" (PERF.md §3): one level under
# PHASES, for what a stretch boundary is made of. schedule: the
# scheduler's plan; an admission's head, prefix match, pages, restore
# or adoption (admit_pages); its sampling state and ring, the eager
# device launches (admit_ring). dispatch: the call of the step program
# itself, apart from the staging of its arguments. emit: a row's token
# through serve/engine._emit by the clock reads at its seams, no
# annotation a row (StepTelemetry.add_emit; EMIT_SEAMS in the order
# they run): `rows` what the span does for a row outside _emit (the
# mirrors, the tolist() / zip of the alternatives; from the span's
# start or the row before), `trace` the request tracer and the TTFT
# series, `report` the journal's note, the stats and the scheduler's
# report, `detok` the detokenisation (add_detok_ids beside it counts
# the ids it decodes), `stream` the request's callback (an append for
# the API server's stream writer, add_stream beside it; whatever a
# per-token callback does), `retire` a finished row's release.
EMIT_SEAMS = ("emit.rows", "emit.trace", "emit.report", "emit.detok",
              "emit.stream", "emit.retire")
PARTS = ("schedule.plan", "schedule.admit_pages", "schedule.admit_ring",
         "dispatch.launch") + EMIT_SEAMS
_PART_SPAN = {key: key.split(".")[0] for key in PARTS}

# A chained step whose own fetch waited less than this was `late`: the
# device had finished it before the host dispatched the step after it,
# so it sat idle under a chain (the fetch follows that dispatch at
# once). Measured on a TPU v5 lite (PR 35, 300 fetches a case):
# jax.device_get of a FINISHED step's sampled tuple (tokens, logprobs,
# 20 top ids and logprobs, ten counters) takes 0.55 ms at 16 rows and
# 0.59 ms at 32 with the device idle, 0.60 / 0.65 ms with the next
# program already running (p90 0.69 / 0.79, max 1.16); a fetch that
# does wait reads the rest of the step (17.5 ms of an 18.2 ms
# program). Twice the median under the next program.
LATE_FETCH_S = 0.00125


class _Span:
    """One timed phase of the engine loop (StepTelemetry.span): adds its
    perf_counter seconds to the open step's phase table and shows as a
    `cake/<name>` TraceAnnotation carrying the step number, so a
    profiler capture holds the same span on the host plane, on the
    clock the device planes use. Engine thread only; spans do not nest
    (a nested span's seconds would count in both)."""

    __slots__ = ("_tel", "_name", "_ann", "_t0", "_cpu0")

    def __init__(self, tel: "StepTelemetry", name: str):
        self._tel = tel
        self._name = name

    def __enter__(self):
        tel = self._tel
        stats = {"step": tel._next}
        if (self._name == "dispatch" and tel._break is not None
                and "dispatch" not in tel._phases):
            # a stretch's first dispatch: the capture shows why the
            # chain before it ended where the idle gap ends
            stats["chain_break"] = tel._break
        # outside a capture a TraceAnnotation is a flag test
        self._ann = tel._annotation("cake/" + self._name, **stats)
        self._ann.__enter__()
        tel._open = self._name
        # the CPU clock's two reads lie INSIDE the wall clock's: on the
        # chip's host one costs 5.5 us (a system call; my chip run,
        # PR 50), which then counts in the span and not between spans
        self._t0 = tel._mark = time.perf_counter()
        self._cpu0 = tel._mark_cpu = time.thread_time()
        return self

    def __exit__(self, *exc):
        cpu = time.thread_time() - self._cpu0
        t1 = time.perf_counter()
        self._tel._open = None
        self._ann.__exit__(*exc)
        self._tel._close_span(self._name, self._t0, t1, cpu)
        return False


class _Part:
    """One timed part of the open span (StepTelemetry.part): its
    seconds go to the open step's `parts` under "<span>.<part>", beside
    the span's own in `phases`, and it shows as `cake/<span>.<part>`
    with the step number."""

    __slots__ = ("_tel", "_key", "_ann", "_t0")

    def __init__(self, tel: "StepTelemetry", key: str):
        self._tel = tel
        self._key = key

    def __enter__(self):
        self._ann = self._tel._annotation(
            "cake/" + self._key, step=self._tel._next)
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self._ann.__exit__(*exc)
        parts = self._tel._parts
        parts[self._key] = parts.get(self._key, 0.0) + (t1 - self._t0)
        return False


class StepTelemetry:
    """Per-engine step flight recorder + jit/cost accounting front end.

    capacity bounds the in-memory ring (GET /api/v1/steps); log_path
    additionally appends every record as one JSON line (--step-log,
    shared obs/jsonl.py durability semantics). key_prefix namespaces
    the accountant keys so engines with different configs cannot alias
    each other's signatures. peak_flops/hbm_bps override the
    device-kind tables (tests pin them for exact MFU math). counters:
    the record keys of the vector the engine's step programs return, in
    its order (its family's `counters`; each has a COUNTER_SERIES)."""

    # cakelint guards discipline: the event bus is an optional plane
    OPTIONAL_PLANES = ("_events",)

    def __init__(self, *, impl: str = "dense", capacity: int = 512,
                 log_path: Optional[str] = None,
                 key_prefix: tuple = (),
                 peak_flops: Optional[float] = None,
                 hbm_bps: Optional[float] = None,
                 accountant: Optional[JitAccountant] = None,
                 events=None, counters: Sequence[str] = ()):
        self.impl = impl
        self._counters = tuple(counters)
        unknown = [k for k in self._counters if k not in COUNTER_SERIES]
        if unknown:
            raise ValueError(f"no series for the counters {unknown} "
                             "(obs/steps.COUNTER_SERIES)")
        self._lock = threading.Lock()
        self._ring: deque = deque(maxlen=max(1, int(capacity)))
        self._next = 1
        self._log = JsonlAppender(log_path) if log_path else None
        self._acct = accountant or ACCOUNTANT
        # obs/startup.seconds() as the first new signature since the
        # last record found them (a `compiled` record's `jit_s`)
        self._jit_before: Optional[tuple] = None
        self._prefix = tuple(key_prefix)
        self._peak = peak_flops
        self._bps = hbm_bps
        self._peaks_known = (peak_flops is not None
                             and hbm_bps is not None)
        # obs/events.EventBus (None = disabled plane, one attribute
        # test per publish): new jit signatures publish a "recompile"
        # event, so a shape-leak recompilation storm shows up on the
        # event timeline, not only as a rising counter
        self._events = events
        # the open step's phase table (engine thread only): seconds by
        # phase since the last record(), its gap_s once it dispatched,
        # and the end of the newest fetch
        self._phases: Dict[str, float] = {}
        self._gap: Optional[float] = None
        self._fetch_t1: Optional[float] = None
        # the open span's name, the open step's parts, and what waits
        # for the next record that is not chained: why the last chain
        # ended, the rows admitted since
        self._open: Optional[str] = None
        self._parts: Dict[str, float] = {}
        self._detok_ids = 0
        # deltas left for the stream writer, handed to a callback, and
        # signals to the writer, for the next record (counts of what
        # was sent: an idle loop's discard_open keeps them); whether
        # deltas wait for a signal; the signal (the API server sets it)
        self._stream = [0, 0, 0]
        self._stream_waits = False
        self.stream_wake: Optional[Callable[[], None]] = None
        self._break: Optional[str] = None
        self._admitted = 0
        # the engine thread's clock: where the open span started (inside
        # `emit`, where the last row's seams ended), where the open
        # step's loop_s starts, its seconds off the CPU by phase, and
        # the collections counted up to the record before
        self._mark = self._mark_cpu = 0.0
        self._emit_acc = [0.0] * len(EMIT_SEAMS)
        self._loop_t0: Optional[float] = None
        self._loop_cpu0 = 0.0
        self._offcpu: Dict[str, float] = {}
        import jax
        self._annotation = jax.profiler.TraceAnnotation
        GC_WATCH.open(self._annotation)
        # close() or, for a recorder nobody closed, its collection
        self._gc_release = weakref.finalize(self, GC_WATCH.close)
        self._gc_release.atexit = False
        self._gc_mark = GC_WATCH.mark()

    @property
    def next_step(self) -> int:
        """The number the record being put together will carry."""
        return self._next

    # -- step phases ----------------------------------------------------------

    def span(self, name: str) -> _Span:
        """Context manager around one phase of the engine loop (one of
        PHASES). Its seconds land in the `phases` of the NEXT record —
        the step being put together — and a capture shows it as
        `cake/<name>` with that record's step number."""
        if name not in PHASES:
            raise ValueError(f"unknown step phase {name!r}: the "
                             f"vocabulary is {PHASES}")
        return _Span(self, name)

    def part(self, name: str) -> _Part:
        """Context manager around one part of the OPEN span (one of
        PARTS, named without its span): `with span("schedule"): with
        part("plan"): ...`. Its seconds land in the `parts` of the next
        record under "schedule.plan" and stay in the span's `phases`
        entry, where they always were."""
        key = f"{self._open}.{name}"
        if self._open is None or key not in PARTS:
            raise ValueError(
                f"no part {name!r} of the open span {self._open!r}: the "
                f"vocabulary is {PARTS}")
        return _Part(self, key)

    def add_part(self, key: str, seconds: float) -> None:
        """Seconds of a part (its full name) measured by the caller:
        for one that runs a row a token, where an annotation each would
        cost more than the part. Counted inside its span only, like the
        span's own seconds."""
        span = _PART_SPAN.get(key)
        if span is None:
            raise ValueError(f"unknown part {key!r}: the vocabulary is "
                             f"{PARTS}")
        if span == self._open:
            self._parts[key] = self._parts.get(key, 0.0) + seconds

    def add_emit(self, t_in: float, t_trace: float, t_report: float,
                 t_detok: float, t_stream: float, t_end: float) -> None:
        """A row's token through serve/engine._emit, as the clock reads
        at its seams: its entry, then the end of `trace`, `report`,
        `detok`, `stream` and `retire` (a part that did not run ends
        where the one before it did). One read a seam: each part is
        the difference of two neighbours, and `emit.rows` runs from
        where the row before ended (or the span started) to the entry.
        Counted inside the `emit` span only, like add_part; summed in
        place (a row a token: no dict, no loop) and folded into the
        parts where the span closes."""
        if self._open != "emit":
            return
        acc = self._emit_acc
        acc[0] += t_in - self._mark
        acc[1] += t_trace - t_in
        acc[2] += t_report - t_trace
        acc[3] += t_detok - t_report
        acc[4] += t_stream - t_detok
        acc[5] += t_end - t_stream
        self._mark = t_end

    def add_detok_ids(self, ids: int) -> None:
        """Ids the detokenisation of a row's token handed to the
        tokenizer's decode (`detok_ids` of the next record). Counted
        inside the `emit` span only, like the part's seconds."""
        if self._open == "emit":
            self._detok_ids += ids

    def add_stream(self, handed: bool) -> None:
        """A delta went to its request's stream callback: `handed` when
        the callback only queued it for the stream writer, which then
        needs a signal. Inside an `emit` span the signal waits for the
        span's end, one for all its rows, and the delta counts in the
        next record; outside one (a speculative round, an adopted first
        token, a recovered row's flush) it goes at once."""
        if self._open == "emit":
            self._stream[0 if handed else 1] += 1
            self._stream_waits |= handed
        elif handed and self.stream_wake is not None:
            self.stream_wake()

    def chain_broke(self, cause: str) -> None:
        """A stretch of in-flight steps stopped chaining and has been
        fetched to its end (serve/engine._drive_burst): `cause` (one of
        BREAKS) rides the next record that is not chained."""
        if cause not in BREAKS:
            raise ValueError(f"unknown chain break {cause!r}: the "
                             f"vocabulary is {BREAKS}")
        self._break = cause

    def admitted(self, rows: int = 1) -> None:
        """Count rows admitted at this boundary (`rows_admitted` of the
        next record that is not chained)."""
        self._admitted += rows

    def discard_open(self, now: Optional[float] = None) -> None:
        """Drop the open step's phases, parts, gap and the reading for
        `jit_s`: what ran belongs to no step (the engine's warm-up; the
        idle loop). The next
        record's loop_s starts here (`now`: a clock read the caller
        already has)."""
        self._phases, self._parts, self._offcpu = {}, {}, {}
        self._detok_ids = 0
        self._gap = self._fetch_t1 = self._jit_before = None
        self._loop_t0 = time.perf_counter() if now is None else now
        self._loop_cpu0 = time.thread_time()

    def _close_span(self, name: str, t0: float, t1: float,
                    cpu: float) -> None:
        if name == "wait":
            # nothing to run: what led up to the wait belongs to no
            # step, and the next step starts no chain's successor
            self.discard_open(t1)
            if self._next > 1:
                self._break = "idle"
            return
        if name == "fetch":
            self._fetch_t1 = t1
        elif name == "emit":
            # what followed the last row, or a span that emitted none,
            # is its rows' own work too; then the rows' seams
            acc, parts = self._emit_acc, self._parts
            acc[0] += max(0.0, t1 - self._mark)
            for i, key in enumerate(EMIT_SEAMS):
                if acc[i] > 0.0:
                    parts[key] = parts.get(key, 0.0) + acc[i]
                    acc[i] = 0.0
            if self._stream_waits:
                self._stream_waits = False
                self._stream[2] += 1
                if self.stream_wake is not None:
                    self.stream_wake()
        elif (name == "dispatch" and name not in self._phases
                and self._fetch_t1 is not None):
            # the open step's first dispatch, after the last step's fetch
            self._gap = max(0.0, t0 - self._fetch_t1)
        self._phases[name] = self._phases.get(name, 0.0) + (t1 - t0)
        # signed: where the thread's CPU clock ticks coarsely a span
        # reads its wall, or its wall less a tick, and only sums are right
        self._offcpu[name] = self._offcpu.get(name, 0.0) + (t1 - t0 - cpu)

    def open_phase(self, name: str) -> Optional[float]:
        """Seconds the open step has spent in `name` so far (None if it
        never entered it)."""
        return self._phases.get(name)

    def rebind(self, *, impl: Optional[str] = None,
               key_prefix: Optional[tuple] = None) -> None:
        """Re-namespace this recorder after a live engine config switch
        (serve/engine.reconfigure): the ring, the --step-log appender
        and the accountant survive — only the impl tag and the
        signature prefix move, so the new config's compiled programs
        can never alias the old config's in the seen-set."""
        if impl is not None:
            self.impl = impl
        if key_prefix is not None:
            self._prefix = tuple(key_prefix)

    # -- jit/cost accounting ------------------------------------------------

    def jit_step(self, fn_name: str, key: tuple, cost_cb) -> _JitStep:
        """Account one dispatch of `fn_name` under signature `key`
        (caller-chosen: the shapes/statics that select the compiled
        program). cost_cb() -> CostInfo|None runs once per new key —
        typically `lambda: lower_cost(fn, args, kwargs)` — and only
        where a peak exists to divide by: on a device kind with no
        table entry (the CPU lane) the extra lowering buys nothing."""
        if not any(self._peaks()):
            cost_cb = _no_cost
        new, cost, before = self._acct.begin(
            fn_name, self._prefix + (fn_name,) + tuple(key), cost_cb)
        if new:
            if self._jit_before is None:
                self._jit_before = before
            if self._events is not None:
                self._events.publish("recompile", fn=fn_name,
                                     impl=self.impl)
        return _JitStep(new, cost)

    def _peaks(self) -> Tuple[Optional[float], Optional[float]]:
        """(peak FLOP/s, HBM bytes/s) — the pinned overrides, else the
        table entries for this process's device kind (None = not in the
        table, so no utilization)."""
        if not self._peaks_known:
            import jax
            kind = jax.devices()[0].device_kind
            if self._peak is None:
                self._peak = peak_flops_for(kind)
            if self._bps is None:
                self._bps = hbm_bps_for(kind)
            self._peaks_known = True
        return self._peak, self._bps

    # -- recording ----------------------------------------------------------

    def record(self, kind: str, *, rows: int = 0, tokens: int = 0,
               dispatch_s: Optional[float] = None,
               device_s: Optional[float] = None,
               wall_s: Optional[float] = None,
               cost: Optional[CostInfo] = None,
               compiled: bool = False,
               pages_free: Optional[int] = None,
               pages_total: Optional[int] = None,
               rows_decode: Optional[int] = None,
               rows_prefill: Optional[int] = None,
               rows_idle: Optional[int] = None,
               tokens_real: Optional[int] = None,
               tokens_computed: Optional[int] = None,
               attn_q_tiles: Optional[int] = None,
               attn_q_tiles_window: Optional[int] = None,
               attn_pages: Optional[int] = None,
               attn_pages_table: Optional[int] = None,
               window_pages: Optional[int] = None,
               window_folds: Optional[int] = None,
               mla_decode_pages: Optional[int] = None,
               mla_decode_folds: Optional[int] = None,
               mixed_attn_pages: Optional[int] = None,
               mixed_attn_pages_table: Optional[int] = None,
               mixed_attn_folds: Optional[int] = None,
               rids: Optional[Sequence[int]] = None,
               impl: Optional[str] = None,
               moe: Optional[Sequence[float]] = None,
               chained: Optional[bool] = None,
               fetch_wait_s: Optional[float] = None) -> StepRecord:
        """Append one step record; derives MFU / HBM utilization from
        `cost` and the step's device seconds. Any subset of the three
        timings may be given; missing ones fall back to the others.
        rows_decode/rows_prefill/rows_idle carry a mixed step's
        occupancy split and feed the cake_mixed_step_rows_total
        counters; tokens_real/tokens_computed its tokens and the
        positions its layers ran over (cake_mixed_tokens_total,
        cake_mixed_tokens_computed_total); attn_q_tiles /
        attn_q_tiles_window the query tiles its attention kernel folds
        and those of its rows' whole windows
        (cake_mixed_attn_q_tiles_total, ..._window_total); attn_pages /
        attn_pages_table the pages a decode step's attention kernel
        streams and the entries of its page table
        (cake_decode_attn_pages_total, ..._table_total); window_pages /
        window_folds the pages a tile of the latent window kernel walks
        in a mixed step and the softmax updates they take
        (cake_mla_window_pages_total, cake_mla_window_folds_total);
        mla_decode_pages / mla_decode_folds the pages the step's
        single-token rows walk through the latent page-walking kernel
        and the softmax updates they take (cake_mla_decode_pages_total,
        cake_mla_decode_folds_total);
        mixed_attn_pages / mixed_attn_pages_table / mixed_attn_folds the
        pages the mixed attention kernel walks a layer over a mixed
        step's calls, their tables' entries and the softmax updates
        (cake_mixed_attn_pages_total, ..._table_total, ..._folds_total).
        rids: the requests whose rows rode this dispatch (the
        per-request explain's step linkage). impl: the attention
        this step actually ran, where the engine resolved it per step
        kind (default: the recorder's engine-wide flavor). moe: the
        step program's sparse-expert counters (StepRecord.moe).
        chained: a decode or mixed step that was dispatched while the
        step before it was in flight (cake_decode_steps_chained_total,
        cake_mixed_steps_chained_total). Its
        gap_s is 0.0, the device had work queued, whatever the spans
        say: its dispatch span lies in the record before its own.
        fetch_wait_s: a chained step's own fetch; under LATE_FETCH_S
        the step is `late` (cake_chained_steps_late_total). A step that
        is not chained takes what waited for it: `chain_break`
        (cake_chain_breaks_total{cause}) and `rows_admitted`."""
        wall = wall_s if wall_s is not None else (
            (dispatch_s or 0.0) + (device_s or 0.0))
        disp = dispatch_s if dispatch_s is not None else wall
        dev = device_s if device_s is not None else wall
        mfu = hbm = None
        if cost is not None and dev > 0:
            peak, bps = self._peaks()
            if cost.flops > 0 and peak:
                mfu = min(1.0, cost.flops / (peak * dev))
            if cost.bytes_accessed > 0 and bps:
                hbm = min(1.0, cost.bytes_accessed / (bps * dev))
        # the loop's clock turns over where the `record` span around
        # this call started (its seconds go to the record after this
        # one, with the rest of what follows), else here
        if self._open == "record":
            now, cpu = self._mark, self._mark_cpu
        else:
            now, cpu = time.perf_counter(), time.thread_time()
        offcpu, self._offcpu = self._offcpu, {}
        loop_s = None
        if self._loop_t0 is not None:
            loop_s = now - self._loop_t0
            # off the CPU outside every span: the loop's wall less its
            # thread's CPU seconds, less what the spans hold of that
            offcpu["none"] = (loop_s - (cpu - self._loop_cpu0)
                              - sum(offcpu.values()))
        self._loop_t0, self._loop_cpu0 = now, cpu
        (gc_s0, gc_n0), self._gc_mark = self._gc_mark, GC_WATCH.mark()
        gc_s, gc_n = self._gc_mark[0] - gc_s0, self._gc_mark[1] - gc_n0
        gc_max_s = GC_WATCH.longest(gc_n)
        phases, self._phases = self._phases, {}
        parts, self._parts = self._parts, {}
        detok_ids, self._detok_ids = self._detok_ids, 0
        (chunks, direct, wakes), self._stream = self._stream, [0, 0, 0]
        gap, self._gap = (0.0 if chained else self._gap), None
        cause = admitted = late = None
        if chained:
            if fetch_wait_s is not None:
                # decided from the wait as the record holds it
                fetch_wait_s = round(fetch_wait_s, 6)
                late = fetch_wait_s < LATE_FETCH_S
        else:
            fetch_wait_s = None
            cause, self._break = self._break, None
            admitted, self._admitted = self._admitted, 0
        if "fetch" not in phases:
            # the device was never drained: the next step has no gap
            self._fetch_t1 = None
        jit_s = None
        if compiled and self._jit_before is not None:
            # read only here. A chained step's dispatch precedes the
            # record of the step before it, so the reading waits for
            # the record that says `compiled`
            before, self._jit_before = self._jit_before, None
            jit_s = {key: after - b for (key, _), after, b in zip(
                _startup.SECONDS, _startup.seconds(), before)}
        with self._lock:
            rec = StepRecord(
                step=self._next, ts=time.time(), kind=kind,
                impl=impl or self.impl, rows=int(rows),
                tokens=int(tokens),
                dispatch_s=float(disp), device_s=float(dev),
                wall_s=float(wall), mfu=mfu, hbm_util=hbm,
                pages_free=pages_free, pages_total=pages_total,
                compiled=bool(compiled),
                rows_decode=rows_decode, rows_prefill=rows_prefill,
                rows_idle=rows_idle,
                tokens_real=tokens_real, tokens_computed=tokens_computed,
                attn_q_tiles=attn_q_tiles,
                attn_q_tiles_window=attn_q_tiles_window,
                attn_pages=attn_pages, attn_pages_table=attn_pages_table,
                window_pages=window_pages, window_folds=window_folds,
                mla_decode_pages=mla_decode_pages,
                mla_decode_folds=mla_decode_folds,
                mixed_attn_pages=mixed_attn_pages,
                mixed_attn_pages_table=mixed_attn_pages_table,
                mixed_attn_folds=mixed_attn_folds,
                rids=(tuple(int(r) for r in rids)
                      if rids is not None else None),
                phases=phases or None, gap_s=gap, chained=chained,
                moe=(dict(zip(self._counters, map(float, moe),
                              strict=True))
                     if moe is not None else None),
                chain_break=cause, rows_admitted=admitted,
                parts=parts or None, detok_ids=detok_ids or None,
                stream_chunks=chunks or None, stream_direct=direct or None,
                stream_wakes=wakes or None,
                fetch_wait_s=fetch_wait_s, late=late,
                loop_s=loop_s, offcpu=offcpu or None,
                gc_s=gc_s, gc_n=gc_n, gc_max_s=gc_max_s, jit_s=jit_s)
            self._next += 1
            self._ring.append(rec)
        _STEPS_TOTAL.labels(kind=kind).inc()
        if chained:
            (_MIXED_CHAINED if kind == "mixed" else _DECODE_CHAINED).inc()
            if late:
                _CHAINED_LATE.inc()
        elif cause is not None:
            _CHAIN_BREAKS.labels(cause=cause).inc()
        _STEP_DISPATCH.labels(kind=kind).observe(disp)
        if chunks:
            _STREAM_CHUNKS.labels(path="writer").inc(chunks)
            _STREAM_WAKES.inc(wakes)
        if direct:
            _STREAM_CHUNKS.labels(path="handler").inc(direct)
        for k, v in (("decode", rows_decode), ("prefill", rows_prefill),
                     ("idle", rows_idle)):
            if v:
                _MIXED_ROWS.labels(kind=k).inc(v)
        if tokens_computed is not None:
            _MIXED_TOKENS.inc(tokens_real)
            _MIXED_TOKENS_COMPUTED.inc(tokens_computed)
        if attn_q_tiles is not None:
            _MIXED_ATTN_TILES.inc(attn_q_tiles)
            _MIXED_ATTN_TILES_WINDOW.inc(attn_q_tiles_window)
        if attn_pages is not None:
            _DECODE_ATTN_PAGES.inc(attn_pages)
            _DECODE_ATTN_PAGES_TABLE.inc(attn_pages_table)
        if window_pages is not None:
            _WINDOW_PAGES.inc(window_pages)
            _WINDOW_FOLDS.inc(window_folds)
        if mla_decode_pages is not None:
            _MLA_DECODE_PAGES.inc(mla_decode_pages)
            _MLA_DECODE_FOLDS.inc(mla_decode_folds)
        if mixed_attn_pages is not None:
            _MIXED_ATTN_PAGES.inc(mixed_attn_pages)
            _MIXED_ATTN_PAGES_TABLE.inc(mixed_attn_pages_table)
            _MIXED_ATTN_FOLDS.inc(mixed_attn_folds)
        if moe is not None:
            for key, v in rec.moe.items():
                COUNTER_SERIES[key].inc(v)
        if mfu is not None:
            _STEP_MFU.labels(kind=kind).set(_sig(mfu))
        if hbm is not None:
            _STEP_HBM.labels(kind=kind).set(_sig(hbm))
        if self._log is not None:
            self._log.append(rec.to_dict())
        return rec

    # -- export -------------------------------------------------------------

    def dump(self, limit: Optional[int] = None) -> List[Dict]:
        """Records newest first (the GET /api/v1/steps body)."""
        with self._lock:
            recs = list(reversed(self._ring))
        if limit is not None:
            recs = recs[:max(0, int(limit))]
        return [r.to_dict() for r in recs]

    def records_for(self, rid: int) -> List[Dict]:
        """Ring records whose dispatched batch contained `rid`, oldest
        first — the per-request explain's step stream (bounded by the
        ring capacity, like every other dump)."""
        with self._lock:
            recs = [r for r in self._ring
                    if r.rids is not None and rid in r.rids]
        return [r.to_dict() for r in recs]

    def utilization(self, *,
                    include_prefill: bool = False) -> Dict[str, float]:
        """Wall-time-weighted mean MFU / HBM utilization over the
        ring's decode-side records (decode / decode_scan / spec;
        prefill excluded — its utilization profile is a different
        question). include_prefill=True widens the aggregate to
        prefill records too (the autotuner's signal: a mixed record
        folds its chunk's prefill FLOPs in, so a dense engine's
        prefill records must count beside it). Records whose dispatch
        compiled a new signature are excluded — their wall is XLA
        compile, not decode. A field is ABSENT when no remaining
        record carried it (no cost info, or a device kind with no
        peak in the table) — never a 0.0 stand-in."""
        kinds = _DECODE_KINDS + ("prefill",) if include_prefill \
            else _DECODE_KINDS
        with self._lock:
            recs = [r for r in self._ring
                    if r.kind in kinds and not r.compiled]
        out: Dict[str, float] = {}
        for field in ("mfu", "hbm_util"):
            num = den = 0.0
            for r in recs:
                v = getattr(r, field)
                if v is not None and r.wall_s > 0:
                    num += v * r.wall_s
                    den += r.wall_s
            if den > 0:
                out[field] = _sig(num / den)
        return out

    def summary(self) -> Dict:
        """Aggregate view for /api/v1/steps and tools: per-kind counts,
        tokens, mean dispatch seconds, compile counts, plus the
        decode-side utilization means."""
        with self._lock:
            recs = list(self._ring)
            recorded = self._next - 1
        kinds: Dict[str, Dict] = {}
        for r in recs:
            k = kinds.setdefault(r.kind, {
                "count": 0, "tokens": 0, "compiles": 0,
                "dispatch_s_sum": 0.0})
            k["count"] += 1
            k["tokens"] += r.tokens
            k["compiles"] += 1 if r.compiled else 0
            k["dispatch_s_sum"] += r.dispatch_s
        for k in kinds.values():
            k["mean_dispatch_s"] = round(
                k.pop("dispatch_s_sum") / k["count"], 6)
        return {
            "recorded_steps": recorded,
            "ring": len(recs),
            "impl": self.impl,
            "kinds": kinds,
            **self.utilization(),
        }

    def close(self) -> None:
        self._gc_release()      # once, however often close() is called
        if self._log is not None:
            self._log.close()


# -- on-demand profiler capture ----------------------------------------------


class ProfileBusyError(RuntimeError):
    """A capture is already running (the single-flight guard). The API
    layer maps this to HTTP 409."""


class ProfileCapture:
    """Single-flight jax.profiler capture from a live process.

    jax.profiler supports one active trace per process; a second
    concurrent capture would raise from deep inside the profiler (or
    corrupt the first artifact), so the guard rejects it up front with
    ProfileBusyError instead."""

    MAX_SECONDS = 120.0

    def __init__(self):
        self._lock = threading.Lock()

    @property
    def busy(self) -> bool:
        # advisory only (the real gate is the non-blocking acquire)
        return self._lock.locked()

    def capture(self, seconds: float, out_dir: Optional[str] = None,
                perfetto: bool = False) -> Dict:
        try:
            seconds = float(seconds)
        except (TypeError, ValueError):
            raise ValueError("seconds must be a number")
        if not (0 < seconds <= self.MAX_SECONDS):
            raise ValueError(
                f"seconds must be in (0, {self.MAX_SECONDS:.0f}]")
        if not self._lock.acquire(blocking=False):
            raise ProfileBusyError(
                "a profiler capture is already in progress")
        try:
            from cake_tpu.utils.profiling import capture_trace
            return capture_trace(seconds, out_dir, perfetto=perfetto)
        finally:
            self._lock.release()


PROFILER = ProfileCapture()
