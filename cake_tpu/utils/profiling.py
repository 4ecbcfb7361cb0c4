"""Tracing / profiling / memory observability.

Reference surface (SURVEY.md §5):
  * `--sd-tracing` installs a Chrome-trace subscriber writing
    `trace-*.json` (sd/sd.rs:350-356) — here `trace(dir)` wraps
    `jax.profiler.trace`, producing a TensorBoard/Perfetto profile of
    both host Python and on-device XLA execution (strictly more detail
    than the reference's host-side spans). The engine loop's own spans
    are `obs/steps.StepTelemetry.span` (`cake/<phase>` annotations).
  * worker ops/s + read/write throughput logged every 5 ops
    (worker.rs:19, 254-283) — here the step flight recorder
    (obs/steps.py, `GET /api/v1/steps`).
  * memory reporting at context creation / model load / inference start
    (cake/mod.rs:65-71, memory-stats + human_bytes) — here
    `log_memory(tag)` over `Device.memory_stats()` (real HBM numbers on
    TPU, not host RSS).
"""

from __future__ import annotations

import contextlib
import logging
import os
import tempfile
import time
from typing import Dict, List, Optional

import jax

log = logging.getLogger(__name__)


@contextlib.contextmanager
def trace(log_dir: Optional[str]):
    """Profile everything inside the block to `log_dir` (None = no-op).

    View with TensorBoard's profile plugin or upload the generated
    `*.trace.json.gz` (perfetto trace) to ui.perfetto.dev — the TPU-era
    equivalent of the reference's chrome://tracing JSON.
    """
    if not log_dir:
        yield
        return
    with jax.profiler.trace(log_dir, create_perfetto_trace=True):
        log.info("profiling to %s", log_dir)
        yield
    log.info("profile written to %s", log_dir)


def capture_trace(seconds: float, out_dir: Optional[str] = None,
                  perfetto: bool = False) -> dict:
    """Capture a jax.profiler trace of the NEXT `seconds` of live
    execution (the POST /api/v1/profile backend, obs/steps.py): unlike
    `trace(dir)` — which wraps a code block the caller controls — this
    profiles whatever the process is doing right now (a serving engine
    mid-decode), then returns where the artifacts landed.

    The capture is as light as the profiler allows, because it runs
    inside the serving process: the Python tracer is off (the engine
    loop's `cake/<phase>` annotations and the device planes do not need
    it, and it hooks every Python call of every thread), and the
    Perfetto conversion — which re-reads and re-writes the whole trace
    as JSON under the GIL at stop — runs only when `perfetto` asks.

    out_dir: capture directory (created if missing); None makes a fresh
    temp dir per capture. Returns {"dir", "xplane", "perfetto_trace",
    "seconds"}: xplane is the newest ``*.xplane.pb`` under dir,
    perfetto_trace the ``perfetto_trace.json.gz`` beside it (upload to
    ui.perfetto.dev) or None when not asked for."""
    d = out_dir or tempfile.mkdtemp(prefix="cake-profile-")
    os.makedirs(d, exist_ok=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    t0 = time.perf_counter()
    jax.profiler.start_trace(d, create_perfetto_trace=bool(perfetto),
                             profiler_options=opts)
    try:
        time.sleep(seconds)
    finally:
        jax.profiler.stop_trace()
    captured = time.perf_counter() - t0
    xplane = _newest(d, ".xplane.pb")
    trace_json = _newest(d, "perfetto_trace.json.gz") if perfetto else None
    log.info("profiler capture: %.2fs -> %s", captured, xplane or d)
    return {"dir": d, "xplane": xplane, "perfetto_trace": trace_json,
            "seconds": round(captured, 3)}


def _newest(directory: str, suffix: str) -> Optional[str]:
    newest, newest_mtime = None, -1.0
    for root, _dirs, files in os.walk(directory):
        for name in files:
            if name.endswith(suffix):
                p = os.path.join(root, name)
                try:
                    m = os.path.getmtime(p)
                except OSError:
                    continue
                if m > newest_mtime:
                    newest, newest_mtime = p, m
    return newest


def human_bytes(n: float) -> str:
    """1536 -> '1.5 KiB' (reference human_bytes crate semantics)."""
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if abs(n) < 1024.0 or unit == "TiB":
            return f"{n:.1f} {unit}" if unit != "B" else f"{int(n)} B"
        n /= 1024.0
    return f"{n:.1f} TiB"


def device_memory_stats() -> List[Dict[str, object]]:
    """Per-device memory usage. Empty fields on backends without stats."""
    out = []
    for d in jax.local_devices():
        stats = {}
        try:
            stats = d.memory_stats() or {}
        except Exception:  # noqa: BLE001 — CPU backend has no stats
            pass
        out.append({
            "device": f"{d.platform}:{d.id}",
            "bytes_in_use": stats.get("bytes_in_use"),
            "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
            "bytes_limit": stats.get("bytes_limit"),
        })
    return out


def log_memory(tag: str) -> None:
    """Log per-device memory at a lifecycle point (cake/mod.rs:65-71)."""
    for s in device_memory_stats():
        used, peak, limit = (s["bytes_in_use"], s["peak_bytes_in_use"],
                             s["bytes_limit"])
        if used is None:
            log.info("[%s] %s: memory stats unavailable", tag, s["device"])
        else:
            log.info(
                "[%s] %s: %s in use (peak %s / limit %s)", tag, s["device"],
                human_bytes(used), human_bytes(peak or 0),
                human_bytes(limit or 0),
            )
