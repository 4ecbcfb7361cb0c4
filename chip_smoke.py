#!/usr/bin/env python3
"""The quickest proof that cake-tpu still starts and serves on the chip.

Drives the serving main path once, through the entry point a user calls:

    python -m cake_tpu.cli --model examples/models/llama3_8b --quant int8 \\
        --api 127.0.0.1:PORT --kv-pages 192 --kv-page-size 128 \\
        --max-slots 16 --max-seq-len 2048

Llama-3-8B at its published widths and all 32 layers, int8 weights from
a seed (the model directory holds config.json only: no weights, no
tokenizer, no network), paged pool, mixed step, Pallas attention. The
script answers a few chat requests through the HTTP API and checks, by
the server's own introspection, that what ran is what was meant to run.

    python chip_smoke.py              one-chip path, on however many chips
    python chip_smoke.py --chips 4    stage 2 x tp 2 over
                                      examples/serving/topology.yml
                                      (dense-slot engine)
    python chip_smoke.py --rehearse   the same script at a toy config
                                      under JAX_PLATFORMS=cpu; proves the
                                      script, never the chip

A chip belongs to one process at a time, so this file stays off JAX
(stdlib only) and the server is its one child. Without --rehearse the
child is held to the TPU backend: on a machine with no TPU it fails at
start-up, and so does this script, with no result line. On success the
last line of stdout is one JSON object:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Timings printed on the way are information labelled with the device
kind, never compared with anything.
"""

from __future__ import annotations

import argparse
import http.client
import json
import os
import re
import socket
import subprocess
import sys
import threading
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(ROOT, "chiprun_out", "chip_smoke")

HEALTH_TIMEOUT_S = 600      # random-init 8B + first listen
REQUEST_TIMEOUT_S = 600     # the first request compiles every program
STOP_TIMEOUT_S = 90
NEW_TOKENS = 64
REHEARSAL_NEW_TOKENS = 8


class SmokeFailure(Exception):
    """A phase failed; the script exits non-zero with no result line."""


def say(msg: str) -> None:
    print(msg, flush=True)


# -- the one child process ----------------------------------------------------


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def rehearsal_model_dir(args) -> str:
    """A toy Llama config written at run time: two layers, or the 32
    the serving topology's two 16-layer stages need."""
    path = os.path.join(OUT_DIR, "rehearsal_model")
    os.makedirs(path, exist_ok=True)
    with open(os.path.join(path, "config.json"), "w") as f:
        json.dump({
            "model_type": "llama", "vocab_size": 512, "hidden_size": 64,
            "intermediate_size": 128,
            "num_hidden_layers": 32 if args.chips == 4 else 2,
            "num_attention_heads": 4, "num_key_value_heads": 2,
            "max_position_embeddings": 2048,
            "bos_token_id": 1, "eos_token_id": 2}, f)
    return path


def server_command(args, port: int) -> list:
    model = (rehearsal_model_dir(args) if args.rehearse
             else os.path.join("examples", "models", "llama3_8b"))
    cmd = [sys.executable, "-m", "cake_tpu.cli", "--model", model,
           "--quant", "int8", "--api", f"127.0.0.1:{port}",
           "--max-slots", "4" if args.rehearse else "16",
           "--max-seq-len", "1024" if args.rehearse else "2048"]
    if args.rehearse:
        cmd += ["--dtype", "f32"]      # bf16 is emulated on a CPU
    if args.chips == 4:
        # serving across devices from topology.yml; the sharded engine
        # is the dense-slot one (it refuses --kv-pages)
        cmd += ["--topology",
                os.path.join("examples", "serving", "topology.yml"),
                "--tp", "2"]
    else:
        cmd += ["--kv-pages", "96" if args.rehearse else "192",
                "--kv-page-size", "64" if args.rehearse else "128"]
    return cmd


def server_env(args) -> dict:
    env = dict(os.environ)
    if args.rehearse:
        env["JAX_PLATFORMS"] = "cpu"
        if args.chips == 4:
            env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " --xla_force_"
                                "host_platform_device_count=4").strip()
    else:
        # no quiet CPU: a backend list that names only the TPU makes
        # JAX fail at start-up when there is none
        env["JAX_PLATFORMS"] = "tpu"
    return env


# -- HTTP, stdlib only --------------------------------------------------------


def http_json(port: int, method: str, path: str, body=None,
              timeout: float = 30.0):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        data = json.dumps(body).encode() if body is not None else None
        conn.request(method, path, body=data,
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        raw = resp.read()
        return resp.status, raw
    finally:
        conn.close()


def get_json(port: int, path: str) -> dict:
    status, raw = http_json(port, "GET", path)
    if status != 200:
        raise SmokeFailure(f"GET {path} -> {status}: {raw[:300]!r}")
    return json.loads(raw)


def get_metrics(port: int) -> dict:
    """/metrics as {series-with-labels: value}."""
    status, raw = http_json(port, "GET", "/metrics")
    if status != 200:
        raise SmokeFailure(f"GET /metrics -> {status}")
    out = {}
    for line in raw.decode().splitlines():
        if line and not line.startswith("#"):
            name, _, value = line.rpartition(" ")
            out[name] = float(value)
    return out


def metric_sum(metrics: dict, family: str) -> float:
    return sum(v for k, v in metrics.items()
               if k == family or k.startswith(family + "{"))


def chat_completion(port: int, content: str, stream: bool,
                    new_tokens: int) -> dict:
    """One greedy chat completion. Returns the HTTP status, the text,
    and the per-token logprob list — with seeded random weights nearly
    every token decodes to no text under the byte tokenizer, so the
    logprobs are what tells two generations apart."""
    body = {"messages": [{"role": "user", "content": content}],
            "max_tokens": new_tokens, "temperature": 0.0,
            "logprobs": True, "stream": stream}
    t0 = time.monotonic()
    status, raw = http_json(port, "POST", "/api/v1/chat/completions",
                            body, timeout=REQUEST_TIMEOUT_S)
    raw = raw.decode()
    out = {"status": status, "seconds": time.monotonic() - t0,
           "text": "", "logprobs": [], "stream": stream}
    if status != 200:
        out["error"] = raw[:300]
        return out
    if not stream:
        choice = json.loads(raw)["choices"][0]
        out["text"] = choice["message"]["content"]
        out["logprobs"] = [e["logprob"]
                           for e in choice["logprobs"]["content"]]
        return out
    for line in raw.splitlines():
        if not line.startswith("data: ") or line == "data: [DONE]":
            continue
        event = json.loads(line[len("data: "):])
        if "error" in event:
            out["status"] = 500
            out["error"] = json.dumps(event["error"])[:300]
            return out
        choice = event["choices"][0]
        out["text"] += choice["delta"].get("content", "")
        out["logprobs"] += [e["logprob"] for e in
                            (choice.get("logprobs") or {}).get("content", [])]
    return out


def prompt_text(i: int) -> str:
    """>= 600 bytes of plain ASCII, different per request, so every
    long prompt walks the mixed step for two or more windows."""
    sentence = (f"Request {i}: describe, step by step, how a paged key "
                f"value cache maps positions {i} through {i + 127} of a "
                "sequence onto fixed-size pages, and why a table lookup "
                "beats a dense slab when many short streams share one "
                "accelerator. ")
    return (sentence * 4)[:700]


# -- phases -------------------------------------------------------------------


def wait_healthy(proc, port: int) -> float:
    t0 = time.monotonic()
    while time.monotonic() - t0 < HEALTH_TIMEOUT_S:
        if proc.poll() is not None:
            raise SmokeFailure(
                f"server exited with code {proc.returncode} before it "
                "was healthy")
        try:
            status, raw = http_json(port, "GET", "/api/v1/health",
                                    timeout=5.0)
        except OSError:
            time.sleep(0.5)
            continue
        if status == 200 and json.loads(raw).get("status") == "ok":
            return time.monotonic() - t0
        time.sleep(0.5)
    raise SmokeFailure(f"server not healthy within {HEALTH_TIMEOUT_S}s")


def check_devices(port: int, args) -> dict:
    devices = get_json(port, "/api/v1/cluster")["devices"]
    want = "cpu" if args.rehearse else "tpu"
    say("devices: " + ", ".join(
        f"{d['id']}:{d['platform']}/{d['kind']}" for d in devices))
    bad = [d for d in devices if d["platform"] != want]
    if not devices or bad:
        raise SmokeFailure(
            f"every device must have platform {want!r}: {devices}")
    if args.chips == 4 and len(devices) != 4:
        raise SmokeFailure(
            f"--chips 4 needs four devices, found {len(devices)}")
    return {"platform": devices[0]["platform"],
            "kind": devices[0]["kind"], "count": len(devices)}


def run_requests(port: int, kind: str, new_tokens: int) -> dict:
    """One alone (compiles), four long at once plus a short one (two of
    them streamed), then the first again, alone."""
    failures = []

    def chat(content: str, stream: bool) -> dict:
        return chat_completion(port, content, stream, new_tokens)

    def must_answer(label: str, r: dict) -> None:
        say(f"  {label}: status {r['status']}, {len(r['logprobs'])} "
            f"tokens, {r['seconds']:.2f} s"
            + (" (stream)" if r["stream"] else ""))
        if r["status"] != 200 or not r["logprobs"]:
            failures.append(f"{label}: status {r['status']} "
                            f"{r.get('error', 'no tokens')}")

    say("request 1, alone (compiles every program it needs):")
    first = chat(prompt_text(1), stream=False)
    must_answer("first", first)
    after_first = get_metrics(port)

    say("wave: four long prompts at once and a short one:")
    jobs = [(f"long-{i}", prompt_text(i), i % 2 == 1)
            for i in range(2, 6)] + [("short", "Say hello.", False)]
    results = {}

    def no_answer(stream: bool, error: str) -> dict:
        return {"status": 0, "seconds": 0.0, "text": "", "logprobs": [],
                "stream": stream, "error": error}

    def worker(label, content, stream):
        try:
            results[label] = chat(content, stream)
        except Exception as e:  # noqa: BLE001 — reported below as a failure
            results[label] = no_answer(stream, f"{type(e).__name__}: {e}")

    threads = [threading.Thread(target=worker, args=j) for j in jobs]
    for t in threads:
        t.start()
    for t in threads:
        t.join(REQUEST_TIMEOUT_S + 30)
    for label, _content, stream in jobs:
        must_answer(label, results.get(label)
                    or no_answer(stream, "no answer in time"))
    after_wave = get_metrics(port)

    say("request 1 again, alone (greedy: must repeat itself):")
    again = chat(prompt_text(1), stream=False)
    must_answer("repeat", again)
    if (again["text"], again["logprobs"]) != (first["text"],
                                              first["logprobs"]):
        diff = next((i for i, (a, b) in enumerate(
            zip(first["logprobs"], again["logprobs"])) if a != b),
            min(len(first["logprobs"]), len(again["logprobs"])))
        failures.append(
            "the repeated greedy prompt answered differently (first "
            f"difference at token {diff}): a kernel read an unwritten "
            "or wrong page, or a step is not deterministic")
    after_repeat = get_metrics(port)

    compiles = [metric_sum(m, "cake_jit_compiles_total")
                for m in (after_first, after_wave, after_repeat)]
    say(f"jit compiles: {compiles[0]:.0f} after request 1, "
        f"{compiles[1]:.0f} after the wave, {compiles[2]:.0f} after "
        "the repeat")
    if compiles[2] != compiles[1]:
        failures.append(
            f"cake_jit_compiles_total still rising after the first "
            f"wave: {compiles[1]:.0f} -> {compiles[2]:.0f}")
    say(f"[{kind}] first (compiling) request: {first['seconds']:.1f} s")
    if failures:
        raise SmokeFailure("; ".join(failures))
    return after_repeat


def check_steps(port: int, args) -> None:
    """What the flight recorder and /api/v1/health say actually ran."""
    steps = get_json(port, "/api/v1/steps")
    health = get_json(port, "/api/v1/health")
    config = health.get("engine_config", {})
    say(f"engine_config: {json.dumps(config)}")
    by_kind = {}
    for rec in steps["steps"]:
        by_kind.setdefault(rec["kind"], set()).add(rec["impl"])
    say("steps recorded: " + json.dumps(
        {k: sorted(v) for k, v in sorted(by_kind.items())})
        + f" ({steps['summary']['recorded_steps']} in all)")
    failures = []
    paged = args.chips != 4
    decode_kinds = [k for k in ("decode", "decode_scan") if k in by_kind]
    for kind in (("mixed",) if paged else ("prefill",)):
        if kind not in by_kind:
            failures.append(f"no {kind!r} step was recorded")
    if not decode_kinds:
        failures.append("no decode step was recorded")
    if paged:
        # the attention each step kind ACTUALLY ran, by the resolved
        # name: Pallas on the chip (the CPU rehearsal resolves to the
        # fold, and must say so)
        want = "paged-fold" if args.rehearse else "paged-pallas"
        impls = config.get("attn_impl", {})
        for kind in ["mixed"] + decode_kinds:
            which = "mixed" if kind == "mixed" else "decode"
            seen = by_kind.get(kind, set())
            if seen != {want} or "paged-" + impls.get(which, "?") != want:
                failures.append(
                    f"{kind} steps ran {sorted(seen)} (health says "
                    f"{impls.get(which)}), wanted {want}")
    recovery = health.get("recovery", {})
    for key in ("recoveries", "poisoned"):
        if recovery.get(key) != 0:
            failures.append(f"recovery.{key} = {recovery.get(key)}")
    if failures:
        raise SmokeFailure("; ".join(failures))


def check_metrics(metrics: dict, args, kind: str) -> None:
    failures = []
    for family in ("cake_engine_errors_total",
                   "cake_poison_requests_total",
                   "cake_engine_recoveries_total",
                   "cake_engine_reset_failures_total"):
        if metric_sum(metrics, family) != 0:
            failures.append(f"{family} = {metric_sum(metrics, family)}")
    hbm = {k: v for k, v in metrics.items()
           if k.startswith("cake_device_hbm_bytes_in_use{")}
    for name, v in sorted(hbm.items()):
        say(f"[{kind}] {name} = {v / 2**30:.2f} GiB")
    if not args.rehearse and not hbm:
        failures.append("no per-device HBM gauge on /metrics")
    if args.chips == 4 and hbm and max(hbm.values()) > 2 * min(hbm.values()):
        failures.append(
            "per-device HBM in use differs by more than 2x: "
            + ", ".join(f"{v / 2**30:.2f}" for v in hbm.values())
            + " GiB (a tree built on one device before it was sharded?)")
    if failures:
        raise SmokeFailure("; ".join(failures))


def report_latency(port: int, kind: str) -> None:
    """Time to first token and tokens/s of the warm requests, from the
    server's own request traces (information only)."""
    traces = get_json(port, "/api/v1/requests?limit=16").get("requests", [])
    for t in sorted(traces, key=lambda t: t["rid"])[1:]:
        decode_s = (t["e2e_s"] or 0) - (t["ttft_s"] or 0)
        rate = ((t["output_tokens"] - 1) / decode_s
                if decode_s > 0 and t["output_tokens"] > 1 else None)
        say(f"[{kind}] rid {t['rid']}: {t['prompt_tokens']} prompt "
            f"tokens, {t['output_tokens']} out, ttft {t['ttft_s']} s, "
            + (f"{rate:.1f} tokens/s after it" if rate else "rate n/a"))


def stop_server(proc, port: int) -> None:
    status, raw = http_json(port, "POST", "/api/v1/drain",
                            {"timeout_s": 30})
    if status != 200:
        raise SmokeFailure(f"POST /api/v1/drain -> {status}: {raw[:300]!r}")
    try:
        proc.wait(STOP_TIMEOUT_S)
    except subprocess.TimeoutExpired:
        raise SmokeFailure(
            f"server still running {STOP_TIMEOUT_S}s after the drain")
    if proc.returncode != 0:
        raise SmokeFailure(f"server exited with code {proc.returncode}")


def cache_entries(path) -> int:
    return len(os.listdir(path)) if path and os.path.isdir(path) else 0


def compile_cache_dir(log_path: str):
    """The directory the server says it caches in (cli.main logs it)."""
    with open(log_path, errors="replace") as f:
        m = re.search(r"compile cache: (\S+)", f.read())
    return m.group(1) if m else None


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = the stage 2 x tp 2 topology form")
    ap.add_argument("--rehearse", action="store_true",
                    help="toy config on the CPU: checks this script only")
    args = ap.parse_args()
    if args.rehearse:
        say("REHEARSAL: a toy config under JAX_PLATFORMS=cpu. This run "
            "proves the script, not the chip.")

    os.makedirs(OUT_DIR, exist_ok=True)
    log_path = os.path.join(OUT_DIR, "server.log")
    port = free_port()
    cmd = server_command(args, port)
    say("server: " + " ".join(cmd))
    t_start = time.monotonic()
    with open(log_path, "w") as log:
        proc = subprocess.Popen(cmd, cwd=ROOT, env=server_env(args),
                                stdout=log, stderr=subprocess.STDOUT)
    try:
        healthy_s = wait_healthy(proc, port)
        cache_dir = compile_cache_dir(log_path)
        device = check_devices(port, args)
        kind = device["kind"]
        say(f"[{kind}] start to healthy: {healthy_s:.1f} s")
        # entries present once the server is up, before any request
        # compiled a step program
        entries_before = cache_entries(cache_dir)
        metrics = run_requests(
            port, kind,
            REHEARSAL_NEW_TOKENS if args.rehearse else NEW_TOKENS)
        check_steps(port, args)
        check_metrics(metrics, args, kind)
        report_latency(port, kind)
        stop_server(proc, port)
        entries_after = cache_entries(cache_dir)
        say(f"[{kind}] compile cache {cache_dir}: {entries_before} "
            f"entries when healthy, {entries_after} at exit")
        if cache_dir is None or entries_after <= 0:
            raise SmokeFailure(
                f"no compile-cache entries under {cache_dir}")
        say(f"[{kind}] whole run: {time.monotonic() - t_start:.1f} s")
    except SmokeFailure as e:
        with open(log_path, errors="replace") as f:
            tail = f.read()[-6000:]
        print(f"--- server log tail ({log_path}) ---\n{tail}",
              file=sys.stderr)
        print(f"CHIP SMOKE FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    finally:
        if proc.poll() is None:
            proc.terminate()
            try:
                proc.wait(20)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait()
    result = {"ok": True, "device": device}
    if args.rehearse:
        say("REHEARSAL passed (no chip was involved).")
        result["rehearsal"] = True
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
