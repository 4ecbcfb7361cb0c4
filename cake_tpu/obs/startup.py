"""What is read of start-up, and the seconds that go into making programs.

Two instruments, both on the registry (obs/metrics.py) and both
observers: neither adds a wait, a sync or a lock to what it times.

  * **The start-up clock's readers.** The clock itself
    (`cake_tpu/startup.py`: named phases from the process's start to
    the first healthy answer, `unnamed` for what lies between them)
    imports nothing, so that it runs before the first `import jax`.
    Here: `healthy()`, which the first `/api/v1/health` answered ok
    calls, closes the clock and sets
    `cake_startup_phase_seconds{phase}` and
    `cake_startup_healthy_seconds` and writes the log's one `startup:`
    line; `report()` is the `startup` block of `/api/v1/health`.

  * **The programs' making** (`listen()`): JAX's own events
    (`jax.monitoring`), whoever makes the program: a step function,
    the weights' draw, a table update, an eager op.
    `/jax/core/compile/jaxpr_trace_duration` (the Python traced to a
    jaxpr), `.../jaxpr_to_mlir_module_duration` (the jaxpr lowered to
    StableHLO) and `.../backend_compile_duration` (jax's
    `compile_or_get_cached`: the cache key, then the cache's read and
    the executable's deserialisation on a hit, or XLA's compile and
    the cache's write on a miss) feed `cake_jit_trace_seconds_total`,
    `cake_jit_lower_seconds_total`, `cake_jit_backend_seconds_total`;
    the accountant's `cost_analysis` (`costing()`, obs/steps.py) feeds
    `cake_jit_cost_analysis_seconds_total`. JAX's spans nest: a jitted
    function traced inside another's trace reports its own seconds
    and the outer's hold them again, a kernel traced while its caller
    is lowered likewise, and the accountant's lowering IS the trace
    and the lowering that the dispatch then finds cached. So each of
    the four counts its spans LESS the spans inside them (`_Spans`: jax
    says when a span opens, too), and the four add up to wall seconds.
    `cake_jit_cache_load_seconds_total`
    (`/jax/compilation_cache/cache_retrieval_time_sec`) lies inside
    the backend's seconds and is not a fifth addend.
    A listener runs when a program is made and never else.
"""

from __future__ import annotations

import contextlib
import json
import logging
import threading
import time
from typing import Dict, List, Optional, Tuple

from cake_tpu.obs import metrics as _m
from cake_tpu.startup import STARTUP

log = logging.getLogger(__name__)

_PHASE_SECONDS = _m.gauge(
    "cake_startup_phase_seconds",
    "Seconds of each named start-up phase between the process's start "
    "and its first healthy answer (`unnamed`: what lay between phases)",
    labelnames=("phase",))
_HEALTHY_SECONDS = _m.gauge(
    "cake_startup_healthy_seconds",
    "Seconds from the process's start (as the OS gives it) to the "
    "first /api/v1/health answered ok; 0 until then")

_TRACE_S = _m.counter(
    "cake_jit_trace_seconds_total",
    "Seconds of Python traced to jaxprs, every program of the process "
    "(jax's jaxpr_trace_duration, less the spans inside each)")
_LOWER_S = _m.counter(
    "cake_jit_lower_seconds_total",
    "Seconds of jaxprs lowered to StableHLO modules "
    "(jax's jaxpr_to_mlir_module_duration, less the spans inside each)")
_BACKEND_S = _m.counter(
    "cake_jit_backend_seconds_total",
    "Seconds in jax's compile_or_get_cached: the cache key, then the "
    "persistent cache's read and the executable's deserialisation on "
    "a hit, or XLA's compile and the cache's write on a miss "
    "(backend_compile_duration)")
_CACHE_LOAD_S = _m.counter(
    "cake_jit_cache_load_seconds_total",
    "Seconds the persistent compile cache took to read and "
    "deserialise executables on hits (cache_retrieval_time_sec; "
    "inside cake_jit_backend_seconds_total, not beside it)")
_COST_S = _m.counter(
    "cake_jit_cost_analysis_seconds_total",
    "Seconds the step accountant's cost callback (obs/steps.lower_cost) "
    "took before new signatures' first dispatches, less the trace and "
    "lowering inside it, which the dispatch finds cached")
_CACHE_HITS = _m.counter(
    "cake_jit_cache_hits_total",
    "Programs whose executable came from the persistent compile cache")
_CACHE_MISSES = _m.counter(
    "cake_jit_cache_misses_total",
    "Programs compiled and written to the persistent compile cache (a "
    "rise on a restart over a full cache names a program whose key "
    "changes from run to run)")

# the order of a step record's `jit_s` (obs/steps.py) and of `seconds()`
SECONDS = (("trace", _TRACE_S), ("lower", _LOWER_S),
           ("backend", _BACKEND_S), ("cache_load", _CACHE_LOAD_S),
           ("cost_analysis", _COST_S))

_TRACE, _LOWER, _BACKEND = 0, 1, 2
_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": _TRACE,
    "/jax/core/compile/jaxpr_to_mlir_module_duration": _LOWER,
    "/jax/core/compile/backend_compile_duration": _BACKEND,
}
_PART_COUNTERS = (_TRACE_S, _LOWER_S, _BACKEND_S)
_CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
_CACHE_ASKED = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS = "/jax/compilation_cache/cache_misses"

TABLE_NAMES = 512    # distinct program names kept; the rest go to "other"
TABLE_TOP = 32       # rows of the table a reader is shown
_DEPTH = 512         # open spans a thread may hold: far past any nesting


def seconds() -> Tuple[float, ...]:
    """The five second-counters now, in SECONDS' order."""
    return tuple(c.value for _, c in SECONDS)


class _Spans(threading.local):
    """One thread's open spans, innermost last: each entry is the
    seconds of the spans that have ended inside it so far. JAX says
    when a span opens (`record_scalar` from its `__enter__`) and, with
    its duration, when it ends, so a span's own seconds are its
    duration less its entry."""

    def __init__(self):
        self.open: List[float] = []
        self.cache_hit: Optional[bool] = None

    def enter(self) -> None:
        if len(self.open) >= _DEPTH:   # ends lost (an interpreter's exit)
            del self.open[:]
        self.open.append(0.0)

    def own(self, secs: float) -> float:
        """The innermost open span ends after `secs`: its seconds less
        those of the spans that ended inside it."""
        inside = self.open.pop() if self.open else 0.0
        if self.open:
            self.open[-1] += secs
        return max(0.0, secs - inside)


class Programs:
    """What `listen()` feeds: the counters above and a bounded table
    {program name: [trace_s, lower_s, backend_s, cache_hit, made]}.
    No lock of its own: the counters are the registry's; a table row is
    a list whose `+=` could lose one addend if two threads made
    programs of one name at the same instant (start-up makes them on
    the main thread, serving on the engine's)."""

    def __init__(self):
        self._spans = _Spans()
        self.table: Dict[str, list] = {}
        self.asked = 0     # programs that asked the persistent cache

    def _row(self, fun_name) -> list:
        name = str(fun_name or "?")
        if name.startswith("jit(") and name.endswith(")"):
            name = name[4:-1]    # `jit(f)` is the module of the function f
        row = self.table.get(name)
        if row is None:
            if len(self.table) >= TABLE_NAMES:
                name = "other"
            row = self.table.setdefault(name, [0.0, 0.0, 0.0, None, 0])
        return row

    def on_duration(self, event: str, secs: float, **kw) -> None:
        part = _EVENTS.get(event)
        if part is None:
            if event == _CACHE_LOAD_EVENT:
                _CACHE_LOAD_S.inc(max(0.0, secs))
            return
        spans = self._spans
        own = spans.own(secs)
        _PART_COUNTERS[part].inc(own)
        row = self._row(kw.get("fun_name"))
        row[part] += own
        if part == _BACKEND:
            row[3], spans.cache_hit = spans.cache_hit, None
            row[4] += 1

    def on_scalar(self, event: str, value, **kw) -> None:
        # jax's LogElapsedTimeContextManager.__enter__: a span opens
        if event in _EVENTS:
            self._spans.enter()

    def on_event(self, event: str, **kw) -> None:
        if event == _CACHE_HIT:
            _CACHE_HITS.inc()
            self._spans.cache_hit = True
        elif event == _CACHE_MISS:
            _CACHE_MISSES.inc()
            self._spans.cache_hit = False
        elif event == _CACHE_ASKED:
            self.asked += 1

    @contextlib.contextmanager
    def costing(self):
        """The accountant's cost callback as one more span of the
        thread: `cake_jit_cost_analysis_seconds_total` takes its
        seconds less the trace and the lowering JAX reported inside."""
        self._spans.enter()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            _COST_S.inc(self._spans.own(time.perf_counter() - t0))

    def snapshot(self) -> dict:
        rows = sorted(self.table.items(),
                      key=lambda kv: -(kv[1][0] + kv[1][1] + kv[1][2]))
        return {
            "seconds": {k: round(c.value, 6) for k, c in SECONDS},
            "cache": {"asked": self.asked,
                      "hits": int(_CACHE_HITS.value),
                      "misses": int(_CACHE_MISSES.value)},
            "made": sum(r[4] for _, r in rows),
            "names": len(rows),
            "top": [[name, round(r[0], 6), round(r[1], 6),
                     round(r[2], 6), r[3], r[4]]
                    for name, r in rows[:TABLE_TOP]],
        }


PROGRAMS = Programs()
_listening = False


def listen() -> None:
    """Register PROGRAMS with jax.monitoring, once a process (jax
    keeps its listeners for good). Beside enable_compile_cache() in
    cli.main."""
    global _listening
    if _listening:
        return
    import jax

    jax.monitoring.register_scalar_listener(PROGRAMS.on_scalar)
    jax.monitoring.register_event_duration_secs_listener(
        PROGRAMS.on_duration)
    jax.monitoring.register_event_listener(PROGRAMS.on_event)
    _listening = True


# -- the start-up clock's readers ---------------------------------------------


def healthy() -> None:
    """A /api/v1/health answered ok. The first one closes the clock
    (cake_tpu/startup.py), sets the gauges and writes the log's one
    line; every later call returns at the clock's first test."""
    if not STARTUP.healthy():
        return
    snap = STARTUP.snapshot()
    _HEALTHY_SECONDS.set(snap["healthy_s"])
    for name, _, secs in snap["phases"]:
        _PHASE_SECONDS.labels(phase=name).set(secs)
    _PHASE_SECONDS.labels(phase="unnamed").set(snap["unnamed_s"])
    prog = PROGRAMS.snapshot()
    log.info("startup: %s", json.dumps({
        "healthy_s": round(snap["healthy_s"], 3),
        "phases": {p[0]: round(p[2], 3) for p in snap["phases"]},
        "unnamed_s": round(snap["unnamed_s"], 3),
        "programs": {k: round(v, 3) for k, v in prog["seconds"].items()},
        "cache": prog["cache"], "made": prog["made"]}))


def report() -> Optional[dict]:
    """The `startup` block of /api/v1/health: None where the clock
    never ran (a library caller, a test)."""
    if not STARTUP.ran:
        return None
    return dict(STARTUP.snapshot(), programs=PROGRAMS.snapshot())
