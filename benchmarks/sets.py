#!/usr/bin/env python3
"""Repeat one cell and report how widely its runs spread: the builder's
tool for setting a bound (not part of the driver's check).

    python benchmarks/sets.py --workload <name> --seconds 48 --runs 6 \\
        --sets 2 [--seed0 1000003] [--trace-first]

Each set runs `--runs` times with seeds seed0, seed0+1, ... (the same
seeds in every set), each run a fresh `run.py` process. Lines go to
`chiprun_out/bench/<workload>.jsonl`; the summary gives, per metric and
set, the median and the spread (distance between the first and third
quartile of `statistics.quantiles(values, n=4)` over the median).
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
OUT = os.path.join(ROOT, "chiprun_out", "bench")


def run_once(workload, seed, seconds, trace, extra):
    cmd = [sys.executable, os.path.join(HERE, "run.py"), "--workload",
           workload, "--seed", str(seed), "--seconds", str(seconds),
           "--trace", str(trace)] + extra
    t0 = time.monotonic()
    proc = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
    took = time.monotonic() - t0
    os.makedirs(OUT, exist_ok=True)
    with open(os.path.join(OUT, workload + ".stderr.log"), "a") as f:
        f.write(f"--- seed {seed} trace {trace} rc {proc.returncode}\n"
                + proc.stderr[-4000:] + "\n")
    line = None
    if proc.returncode == 0 and proc.stdout.strip():
        line = json.loads(proc.stdout.strip().splitlines()[-1])
    else:
        print(proc.stderr[-3000:], file=sys.stderr)
    return line, took, proc.returncode


def spread(values):
    if len(values) < 2:
        return None
    q = statistics.quantiles(values, n=4)
    med = statistics.median(values)
    return (q[2] - q[0]) / med if med else None


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--seed0", type=int, default=2147483659)
    ap.add_argument("--trace-first", action="store_true",
                    help="one traced run before the sets")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    extra = ["--rehearse"] if args.rehearse else []
    os.makedirs(OUT, exist_ok=True)
    log = open(os.path.join(OUT, args.workload + ".jsonl"), "a")
    bad = 0

    def note(kind, seed, line, took, rc):
        log.write(json.dumps({"kind": kind, "seed": seed, "took_s": took,
                              "rc": rc, "line": line}) + "\n")
        log.flush()
        print(f"[{kind}] seed {seed} rc {rc} {took:.0f}s "
              + (json.dumps({k: round(v["value"], 3) for k, v in
                             line["metrics"].items()}) + f" correct="
                 f"{line['correct']} failed={line['failed']}/"
                 f"{line['attempted']}" if line else "NO LINE"), flush=True)

    if args.trace_first:
        line, took, rc = run_once(args.workload, args.seed0, args.seconds,
                                  1, extra)
        note("trace", args.seed0, line, took, rc)
        bad += line is None or not line["correct"]
        run_dir = os.path.join(HERE, ".run", args.workload)
        for name in ("trace_reduced.json", "trace_reduced.json.describe.json",
                     "server.log"):
            src = os.path.join(run_dir, name)
            if os.path.exists(src):
                shutil.copy(src, os.path.join(
                    OUT, f"{args.workload}.{name}"))
        if line:
            print(json.dumps(line.get("breakdown"), indent=1))
            print(json.dumps(line["device"]))
    sets = []
    for s in range(args.sets):
        lines = []
        for r in range(args.runs):
            line, took, rc = run_once(args.workload, args.seed0 + r,
                                      args.seconds, 0, extra)
            note(f"set{s}", args.seed0 + r, line, took, rc)
            bad += line is None or not line["correct"]
            if line:
                lines.append(line)
        sets.append(lines)
    names = sorted({k for lines in sets for ln in lines for k in ln["metrics"]})
    for name in names:
        row = []
        for lines in sets:
            vals = [ln["metrics"][name]["value"] for ln in lines
                    if name in ln["metrics"]]
            # the first run of a call compiles: its setup_s stands apart
            if name == "setup_s" and lines is sets[0]:
                vals = vals[1:]
            if vals:
                sp = spread(vals)
                row.append(f"median {statistics.median(vals):.4f} spread "
                           + (f"{100 * sp:.2f}%" if sp is not None else "n/a")
                           + f" (n={len(vals)}, min {min(vals):.4f}, "
                           f"max {max(vals):.4f})")
        print(f"{name}: " + " | ".join(row))
    shutil.copy(os.path.join(HERE, ".run", args.workload, "server.log"),
                os.path.join(OUT, f"{args.workload}.server.log"))
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
