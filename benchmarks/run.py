#!/usr/bin/env python3
"""Run one cell of the benchmark once, over the served path.

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Starts `python -m cake_tpu.cli ... --api 127.0.0.1:PORT` as a child held
to the TPU, waits until it is healthy, warms every shape the window
will use, ramps the cell's traffic, measures for `--seconds`, stops the
server, and prints one JSON object as the last line of stdout:
`correct`, `attempted`, `failed`, `metrics`, `device` (and `breakdown`
with `--trace 1`). With `--trace 0` the metrics are the cell's
end-to-end metrics, with `--trace 1` its per-layer metrics.

No chip, fewer chips than the cell asks for, or no program to serve:
exit code 1 and no result line. `--rehearse` runs the cell's toy
configuration under JAX_PLATFORMS=cpu for whoever builds a cell; its
line says `"platform": "cpu"` and is never a measurement.

This process never imports JAX: the server child holds the chip, and
the trace is reduced by a short-lived process after the server has
exited.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import shutil
import subprocess
import sys
import threading
import time

T_START = time.monotonic()

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from harness import e2e, server as srv, spec, traffic as tfc  # noqa: E402
from harness.client import stream_chat  # noqa: E402

PROBE_TOLERANCE = 2e-2     # first-token top-5 logprobs, batched vs alone
PROBE_ALONE_OUT = 16       # tokens of the probe when served alone
TRACE_SECONDS = 3.0        # of the steady window, through /api/v1/profile
TRACE_AT_S = 2.0           # after the window opens
REDUCE_LIMIT_S = 900       # harness/trace_reduce.py over one capture


class RunFailure(Exception):
    """The run cannot give a result; exit non-zero with no result line."""


def say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="toy configuration on the CPU: proves the "
                         "harness, never the chip")
    return ap.parse_args(argv)


def one_request(server, item, what: str) -> dict:
    rec = stream_chat(server.port, item)
    if not rec["finished"]:
        raise RunFailure(f"{what} request failed: status {rec['status']}, "
                         f"{rec['error']}")
    return rec


def wave(server, items: list, what: str) -> list:
    out, threads = [None] * len(items), []

    def work(i):
        out[i] = stream_chat(server.port, items[i])

    for i in range(len(items)):
        t = threading.Thread(target=work, args=(i,), daemon=True)
        t.start()
        threads.append(t)
    for t in threads:
        t.join()
    bad = [r for r in out if r is None or not r["finished"]]
    if bad:
        raise RunFailure(f"{what}: {len(bad)} of {len(items)} requests "
                         f"failed: {bad[0] and bad[0]['error']}")
    return out


def template_overhead(server, mix) -> int:
    """Tokens the chat template adds around the user's words, read off
    the server's own count for one request. The request is as long as
    the shortest class less a margin, so it compiles no shape of its
    own on an engine that buckets prompts."""
    n_words = max(1, min(c["hi"] for c in mix.traffic["prompt_classes"]) - 16)
    item = {"class": "_", "out": 1, "prompt": n_words, "n": -100,
            "content": tfc.words(random.Random(1), n_words, mix.vocab_size)}
    rec = one_request(server, item, "template-calibration")
    trace = request_traces(server, [rec["rid"]]).get(rec["rid"])
    if not trace:
        raise RunFailure("no server trace for the calibration request")
    return int(trace["prompt_tokens"]) - n_words


def request_traces(server, rids=None) -> dict:
    recs = server.get_json("/api/v1/requests?limit=100000",
                           timeout=120.0).get("requests", [])
    by = {r["rid"]: r for r in recs}
    return by if rids is None else {r: by[r] for r in rids if r in by}


def warm_up(server, mix, clients: int) -> dict:
    """The probe alone, twice (it must repeat itself); one request per
    shape the window will use; then a wave as wide as the window's
    concurrency. Everything that compiles, compiles here."""
    t0 = time.monotonic()
    # alone, the probe is cut to its first PROBE_ALONE_OUT tokens: the
    # check compares the first token, and greedy decoding gives the same
    # first tokens whatever max_tokens is
    alone = dict(mix.probe_item())
    alone["out"] = min(alone["out"], PROBE_ALONE_OUT)
    probe_a = one_request(server, alone, "probe")
    say(f"  probe alone, first (loads or compiles the step programs): "
        f"{time.monotonic() - t0:.1f} s")
    probe_b = one_request(server, alone, "probe repeat")
    t_shapes = time.monotonic()
    for item in mix.warmup_items():
        one_request(server, item, "warm-up")
    say(f"  probe again {t_shapes - t0 - (probe_a['t_end'] - probe_a['t_send']):.1f} s, "
        f"one request per shape {time.monotonic() - t_shapes:.1f} s")
    n_wave = int(mix.traffic.get("warmup_wave", clients))
    if n_wave:
        base = mix.warmup_items() or [mix.probe_item()]
        items = []
        for i in range(n_wave):
            it = dict(base[i % len(base)])
            it["content"] = tfc.words(
                random.Random(tfc.PROBE_SEED + 1000 + i),
                it["prompt"] - mix.overhead, mix.vocab_size)
            it["out"] = int(mix.traffic.get("warmup_wave_out", 8))
            it.pop("top_logprobs", None)
            items.append(it)
        wave(server, items, "warm-up wave")
    return {"probe_a": probe_a, "probe_b": probe_b,
            "warmup_s": time.monotonic() - t0}


def profile_later(server, delay_s: float, seconds: float, out: dict):
    def work():
        time.sleep(delay_s)
        try:
            status, raw = server.request(
                "POST", "/api/v1/profile", {"seconds": seconds},
                timeout=seconds + 300)
            out["status"] = status
            out["body"] = json.loads(raw) if status == 200 else raw[:300]
        except Exception as e:  # noqa: BLE001 — reported by the caller
            out["error"] = f"{type(e).__name__}: {e}"
    t = threading.Thread(target=work, daemon=True)
    t.start()
    return t


def reduce_trace(profile_dir: str, run_dir: str) -> dict:
    """In a process of its own, now that the server has let go of the
    chip; held to the CPU so it cannot take it either."""
    out_path = os.path.join(run_dir, "trace_reduced.json")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    try:
        proc = subprocess.run(
            [sys.executable,
             os.path.join(spec.BENCH_DIR, "harness", "trace_reduce.py"),
             profile_dir, out_path],
            env=env, capture_output=True, text=True, timeout=REDUCE_LIMIT_S)
    except subprocess.TimeoutExpired:
        raise RunFailure("trace reduction (harness/trace_reduce.py over "
                         f"{profile_dir}) passed its limit of "
                         f"{REDUCE_LIMIT_S} s") from None
    if proc.returncode != 0:
        raise RunFailure("trace reduction failed: " + proc.stderr[-2000:])
    return spec.load_json(out_path)


def check_probe(warm: dict, records: list, amid_traffic) -> list:
    """Failures of the probe checks, as sentences."""
    problems = []
    a, b = warm["probe_a"], warm["probe_b"]
    if a["logprobs"] != b["logprobs"]:
        problems.append("the greedy probe, served alone twice, did not "
                        "repeat itself")
    amid = [r for r in records
            if r.get("probe") and r["first_top"] and amid_traffic(r)]
    if not amid:
        problems.append("no probe got its first token amid traffic")
    for r in amid:
        want, got = sorted(a["first_top"]), sorted(r["first_top"])
        if len(want) != 5 or len(got) != 5 or max(
                abs(x - y) for x, y in zip(want, got)) > PROBE_TOLERANCE:
            problems.append(
                "the probe's first-token top-5 logprobs amid traffic "
                f"{got} differ from those alone {want} by more than "
                f"{PROBE_TOLERANCE}")
            break
    return problems


def check_lengths(records, traces, classes) -> list:
    """Every finished request had the asked token count (the client
    checks that) and a prompt inside its class by the server's count."""
    problems = []
    for r in records:
        if not r["finished"] or r["rid"] not in traces:
            continue
        c = classes.get(r["class"])
        p = traces[r["rid"]]["prompt_tokens"]
        if c and not (c["lo"] <= p <= c["hi"]):
            problems.append(f"request {r['rid']} of class {r['class']} "
                            f"had {p} prompt tokens")
            break
    return problems


def check_impls(steps, want: dict) -> list:
    """cell.json's `expect_impl` {step kind: impl} against what the
    window's step records say ran."""
    problems = []
    seen = {}
    for s in steps:
        seen.setdefault(s["kind"], set()).add(s["impl"])
    for kind, impl in want.items():
        if seen.get(kind) != {impl}:
            problems.append(f"{kind} steps ran {sorted(seen.get(kind, []))}, "
                            f"wanted {impl}")
    return problems


def main(argv=None) -> int:
    args = parse_args(argv)
    cell = spec.Cell(args.workload)
    found = spec.discover_layer_metrics()
    conf = cell.cell
    model_config = cell.model_config
    server_args = dict(conf["server_args"])
    chips = cell.chips
    if args.rehearse:
        say("REHEARSAL: a toy configuration under JAX_PLATFORMS=cpu. "
            "This proves the harness, never the chip.")
        model_config = dict(model_config, **conf["rehearse"]["config"])
        server_args.update(conf["rehearse"].get("server_args", {}))
    run_dir = os.path.join(spec.BENCH_DIR, ".run", cell.name)
    os.makedirs(run_dir, exist_ok=True)
    profile_dir = os.path.join(run_dir, "profile")
    shutil.rmtree(profile_dir, ignore_errors=True)
    model_dir = srv.prepare_model_dir(run_dir, model_config)
    cmd = srv.server_command(model_dir, srv.free_port(), server_args,
                             cell.config_dir, run_dir, args.seed)
    say("server: " + " ".join(cmd))
    server = srv.Server(cmd, srv.server_env(args.rehearse, chips),
                        os.path.join(run_dir, "server.log"))
    loop = None
    try:
        healthy_s = server.wait_healthy()
        device = server.devices()
        want_platform = "cpu" if args.rehearse else "tpu"
        if device["platform"] != want_platform or device["count"] < chips:
            raise RunFailure(f"the cell needs {chips} {want_platform} "
                             f"device(s); the server has {device}")
        say(f"[{device['kind']} x{device['count']}] healthy in "
            f"{healthy_s:.1f} s")
        mix = tfc.Mix(cell.traffic, args.seed, model_config["vocab_size"])
        mix.overhead = template_overhead(server, mix)
        clients = int(cell.traffic.get("clients", 0))
        warm = warm_up(server, mix, clients)
        say(f"warm-up {warm['warmup_s']:.1f} s; chat template adds "
            f"{mix.overhead} tokens")

        ramp_s = float(cell.traffic.get("ramp_s", 0))
        loop = tfc.make_loop(server.port, mix, ramp_s + args.seconds + 5)
        loop.start()
        time.sleep(ramp_s)
        metrics_0 = server.metrics()
        wall_0, t0 = time.time(), time.monotonic()
        setup_s = t0 - T_START
        prof, prof_thread = {}, None
        if args.trace:
            prof_thread = profile_later(server, TRACE_AT_S,
                                        min(TRACE_SECONDS,
                                            max(0.5, args.seconds - 3)),
                                        prof)
        time.sleep(max(0.0, t0 + args.seconds - time.monotonic()))
        t1, wall_1 = time.monotonic(), time.time()
        metrics_1 = server.metrics()
        steps = server.get_json("/api/v1/steps?limit=100000",
                                timeout=120.0)["steps"]
        health = server.get_json("/api/v1/health")
        loop.stop()
        if prof_thread is not None:
            prof_thread.join(600)
        traces = request_traces(server)
        metrics_2 = server.metrics()
        records = sorted(loop.records, key=lambda r: r["t_send"])
        left = loop.alive()
        server.stop()
        if left:
            raise RunFailure(f"{left} client threads did not stop")
    except (RunFailure, srv.ServerFailure, spec.SpecError,
            e2e.MetricError) as e:
        say("--- server log tail ---\n" + server.log_tail())
        say(f"BENCHMARK RUN FAILED: {e}")
        return 1
    finally:
        if loop is not None:
            loop.stop_event.set()
        server.stop()

    try:
        window_steps = [s for s in steps if wall_0 <= s["ts"] < wall_1]
        run = {
            "cell": cell, "args": args, "model_config": model_config,
            "server_args": server_args, "device": device,
            "records": records, "turnarounds": loop.turnarounds,
            "t0": t0, "t1": t1, "wall_0": wall_0, "wall_1": wall_1,
            "seconds": t1 - t0, "steps": window_steps, "all_steps": steps,
            "traces": traces, "health": health,
            "metrics_0": metrics_0, "metrics_1": metrics_1,
            "metrics_2": metrics_2, "healthy_s": healthy_s,
            "warmup_s": warm["warmup_s"], "setup_s": setup_s,
            "trace": None, "profile": prof,
        }
        sent = [r for r in records if r["t_send"] < t1]
        attempted, failed = len(sent), sum(r["failed"] for r in sent)
        problems = [f"{failed} of {attempted} requests failed: "
                    + next(r["error"] for r in sent if r["failed"])] \
            if failed else []
        compiles = (srv.metric_sum(metrics_1, "cake_jit_compiles_total")
                    - srv.metric_sum(metrics_0, "cake_jit_compiles_total"))
        if compiles or any(s["compiled"] for s in window_steps):
            problems.append(f"{compiles:.0f} programs compiled inside "
                            "the window")
        problems += check_probe(
            warm, records,
            lambda r: r["token_t"] and r["token_t"][0] < t1)
        problems += check_lengths(records, traces,
                                  tfc.class_by_name(cell.traffic))
        if not args.rehearse:
            problems += check_impls(window_steps,
                                    conf.get("expect_impl", {}))
        for family in ("cake_engine_errors_total",
                       "cake_engine_recoveries_total"):
            if srv.metric_sum(metrics_2, family):
                problems.append(f"{family} is not 0")

        peak = srv.metric_max(metrics_2, "cake_device_hbm_peak_bytes")
        device = dict(device, memory_peak_bytes=int(peak) if peak else 0)
        line = {"correct": not problems, "attempted": attempted,
                "failed": failed, "metrics": {}, "device": device}
        if problems:
            line["problems"] = problems[:8]
            say("NOT CORRECT: " + "; ".join(problems[:8]))
        if args.trace:
            if prof.get("status") != 200:
                raise RunFailure(f"POST /api/v1/profile failed: {prof}")
            t_reduce = time.monotonic()
            run["trace"] = reduce_trace(profile_dir, run_dir)
            t_read = time.monotonic()
            if not run["trace"]["busy_s"] > 0 and not args.rehearse:
                raise RunFailure("the trace shows no operation on a device")
            device["busy_s"] = run["trace"]["busy_s"]
            device["window_s"] = run["trace"]["window_s"]
            line["breakdown"] = {
                "device_ops": run["trace"]["device_ops"],
                "idle_gaps": run["trace"]["idle_gaps"]}
            line["metrics"] = spec.read_layer_metrics(cell, run, found, say)
            shutil.rmtree(profile_dir, ignore_errors=True)
            # the capture's cost to the run: the reduction's process,
            # then the readers (one more read of it, shared)
            line["trace_reduce_s"] = time.monotonic() - t_reduce
            say(f"trace: reduced in {t_read - t_reduce:.1f} s, layer "
                f"metrics read in {time.monotonic() - t_read:.1f} s")
        else:
            values = e2e.end_to_end(cell.names("end_to_end"), records, t0,
                                    t1, cell.traffic, setup_s)
            units = {m["name"]: m["unit"] for m in cell.end_to_end}
            line["metrics"] = {k: {"value": v, "unit": units[k]}
                               for k, v in values.items()}
            # for whoever reads the log of a run that read far off: the
            # layer metrics that need no trace, on stderr only
            layers = spec.read_layer_metrics(cell, run, found)
            say("layers: " + json.dumps(
                {k: round(v["value"], 3) for k, v in layers.items()}))
        if args.rehearse:
            line["rehearsal"] = True
    except (RunFailure, spec.SpecError, e2e.MetricError) as e:
        say(f"BENCHMARK RUN FAILED: {e}")
        return 1
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
