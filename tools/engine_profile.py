#!/usr/bin/env python3
"""On-chip engine dispatch profiler, driven by the step flight recorder.

Times the pieces the aggregate engine number is made of, to attribute
throughput between device compute and host<->device dispatch latency
(the batch-1 tier's on-device `lax.scan` loop pays one round-trip, the
engine pays one per step/scan):

  - raw dispatch RTT: a trivial jitted op, timed per round-trip
  - per-kind step timing (prefill / decode / decode_scan) straight from
    the engine's own flight recorder (obs/steps.py) — no hand-timed
    monkeypatching of dispatch internals, so the numbers are exactly
    what GET /api/v1/steps would report for the same run
  - per-step MFU / HBM utilization and jit compile counts
  - decode token accounting: tokens from scans vs single steps

Usage:
    python tools/engine_profile.py [model] [slots] [gen_tokens] [quant]
    python tools/engine_profile.py 8b 16 64 int8 --json

With --json the report is ONE machine-readable JSON line on stdout
(human narration stays on stderr); without it, everything goes to
stderr as before.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from functools import partial
from pathlib import Path

# resolve the repo root from this file, not the caller's cwd — the old
# sys.path.insert(0, ".") hack broke the tool whenever it was launched
# from anywhere but the repo root
REPO_ROOT = Path(__file__).resolve().parents[1]
if str(REPO_ROOT) not in sys.path:
    sys.path.insert(0, str(REPO_ROOT))

import jax                                                  # noqa: E402
import jax.numpy as jnp                                     # noqa: E402

import bench                                                # noqa: E402
from cake_tpu.models.llama.generator import ByteTokenizer   # noqa: E402
from cake_tpu.obs import metrics as obs_metrics             # noqa: E402
from cake_tpu.ops.sampling import SamplingConfig            # noqa: E402
from cake_tpu.serve.engine import InferenceEngine           # noqa: E402


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def _measure_rtt(n_rtt: int = 20) -> tuple[float, float]:
    """(blocking RTT, async chained dispatch) of a trivial jitted op."""
    f = jax.jit(lambda x: x + 1)
    x = jnp.zeros((8,), jnp.int32)
    x = f(x)
    jax.block_until_ready(x)
    t0 = time.perf_counter()
    for _ in range(n_rtt):
        x = f(x)
        jax.block_until_ready(x)
    rtt = (time.perf_counter() - t0) / n_rtt
    t0 = time.perf_counter()
    for _ in range(n_rtt):
        x = f(x)
    jax.block_until_ready(x)
    async_rtt = (time.perf_counter() - t0) / n_rtt
    return rtt, async_rtt


def _jit_compile_counts() -> dict:
    """Current cake_jit_compiles_total{fn} values from the registry."""
    fam = obs_metrics.REGISTRY.get("cake_jit_compiles_total")
    if fam is None:
        return {}
    return {labels[0]: value
            for labels, value in fam.samples().items() if labels}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Engine dispatch profiler over the step flight "
                    "recorder")
    ap.add_argument("model", nargs="?", default="8b",
                    help="model size (8b|3b|1b|tiny; default 8b)")
    ap.add_argument("slots", nargs="?", type=int, default=16)
    ap.add_argument("gen_tokens", nargs="?", type=int, default=64)
    ap.add_argument("quant", nargs="?", default=None,
                    choices=("int8", "int4", "bf16"),
                    help="weight quant; default int8 for 8b, bf16 else")
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--max-seq", type=int, default=512)
    ap.add_argument("--decode-scan", type=int, default=8)
    ap.add_argument("--json", action="store_true",
                    help="emit one JSON summary line on stdout")
    args = ap.parse_args(argv)

    quant_s = args.quant or ("int8" if args.model == "8b" else "bf16")
    quant = False if quant_s == "bf16" else quant_s

    dev = jax.devices()[0]
    log(f"device: {dev.platform}/{dev.device_kind}")
    rtt, async_rtt = _measure_rtt()
    log(f"raw dispatch RTT (tiny jit, block each): {rtt * 1e3:.1f} ms")
    log(f"async chained dispatch (block once): {async_rtt * 1e3:.1f} "
        "ms/op")

    cfg = bench.make_config(args.model)
    init, desc = bench._init_fn(quant)
    log(f"weights: {desc}")
    params = jax.jit(partial(init, cfg))(jax.random.PRNGKey(0))
    jax.block_until_ready(params)

    engine = InferenceEngine(
        cfg, params, ByteTokenizer(cfg.vocab_size),
        max_slots=args.slots, max_seq_len=args.max_seq,
        sampling=SamplingConfig(temperature=0.0, repeat_penalty=1.0),
        decode_scan_steps=args.decode_scan,
        # the measured run must fit in the ring (one record per step)
        step_ring=max(4096, args.slots * args.gen_tokens + 64),
    )

    prompt = list(range(3, 3 + args.prompt_len))
    with engine:
        t0 = time.perf_counter()
        warm = engine.submit(prompt, max_new_tokens=32)
        assert warm.wait(timeout=900)
        log(f"warmup: {time.perf_counter() - t0:.1f}s")
        warm_steps = engine.flight.summary()["recorded_steps"]
        base = engine.stats.tokens_generated
        t0 = time.perf_counter()
        handles = [engine.submit(prompt, max_new_tokens=args.gen_tokens)
                   for _ in range(args.slots)]
        assert all(h.wait(timeout=900) for h in handles)
        wall = time.perf_counter() - t0
        toks = engine.stats.tokens_generated - base
        # measured window = everything the recorder saw after warmup;
        # utilization uses the same window (compile steps excluded), so
        # the JSON's mfu agrees with its own per-kind table
        recs = [r for r in engine.flight.dump()
                if r["step"] > warm_steps]
        summary = engine.flight.summary()
        util = engine.flight.utilization(since_step=warm_steps)

    by_kind: dict = {}
    for r in recs:
        by_kind.setdefault(r["kind"], []).append(r)
    for kind, rs in sorted(by_kind.items()):
        d = [r["dispatch_s"] for r in rs]
        tot = sum(d)
        log(f"{kind:12s}: {len(rs):4d} steps, total {tot:6.2f}s, "
            f"mean {tot / len(rs) * 1e3:7.1f} ms, "
            f"min {min(d) * 1e3:7.1f} ms, max {max(d) * 1e3:7.1f} ms, "
            f"{sum(r['tokens'] for r in rs)} tokens")
    scan_tokens = sum(r["tokens"] for r in by_kind.get("decode_scan", []))
    single_tokens = sum(r["tokens"] for r in by_kind.get("decode", []))
    log(f"tokens: {toks} ({scan_tokens} scanned, {single_tokens} single)")
    log(f"wall: {wall:.2f}s -> {toks / wall:.1f} tok/s incl. prefill")
    log("utilization: " + (", ".join(
        f"{k} {v:.4f}" for k, v in util.items()) or "not measured"))
    compiles = _jit_compile_counts()
    log(f"jit compiles: {compiles}")
    ttfts = sorted(h.ttft for h in handles)
    p50 = ttfts[len(ttfts) // 2]
    log(f"TTFT p50 {p50 * 1e3:.0f} ms")

    if args.json:
        print(json.dumps({
            "device_kind": dev.device_kind,
            "model": args.model,
            "quant": quant_s,
            "slots": args.slots,
            "gen_tokens": args.gen_tokens,
            "raw_rtt_ms": round(rtt * 1e3, 2),
            "async_rtt_ms": round(async_rtt * 1e3, 2),
            "tokens": toks,
            "tok_s_incl_prefill": round(toks / wall, 2),
            "ttft_p50_ms": round(p50 * 1e3, 1),
            "scan_tokens": scan_tokens,
            "single_tokens": single_tokens,
            "kinds": {
                kind: {
                    "steps": len(rs),
                    "mean_dispatch_ms": round(
                        sum(r["dispatch_s"] for r in rs) / len(rs) * 1e3,
                        2),
                    "tokens": sum(r["tokens"] for r in rs),
                } for kind, rs in sorted(by_kind.items())
            },
            **util,
            "jit_compiles": compiles,
            "flight_summary": summary,
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
