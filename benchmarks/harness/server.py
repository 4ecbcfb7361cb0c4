"""The system under test: one `python -m cake_tpu.cli ... --api` child.

The parent stays off JAX (a chip belongs to one process), talks to the
child over HTTP with the standard library, and stops it when done. The
child is held to `JAX_PLATFORMS=tpu`, so a machine with no TPU fails at
start-up; only `--rehearse` runs it on the CPU.
"""

from __future__ import annotations

import http.client
import json
import os
import shutil
import socket
import subprocess
import sys
import time

from .spec import ROOT, SpecError

HEALTH_TIMEOUT_S = 900
STOP_TIMEOUT_S = 60


class ServerFailure(Exception):
    """The child did not start, answer or stop; no result line."""


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def write_tokenizer(model_dir: str, vocab_size: int) -> None:
    """A word-level `tokenizer.json`: id i is the word `w<i>`, split on
    whitespace. With seeded weights the generated ids are spread over
    the whole vocabulary; the program's byte fallback turns only ids
    3..258 into text and streams no chunk for the rest, so a client
    would see neither a first token nor the gaps. Here every id is a
    word, as nearly every id of a real vocabulary is, a prompt of n
    words is n tokens plus the chat template's fixed few, and every
    generated token arrives as one chunk."""
    vocab = {"<unk>": 0}
    for i in range(1, vocab_size):
        vocab[f"w{i}"] = i
    doc = {"version": "1.0", "truncation": None, "padding": None,
           "added_tokens": [], "normalizer": None,
           "pre_tokenizer": {"type": "WhitespaceSplit"},
           "post_processor": None, "decoder": None,
           "model": {"type": "WordLevel", "vocab": vocab,
                     "unk_token": "<unk>"}}
    with open(os.path.join(model_dir, "tokenizer.json"), "w") as f:
        json.dump(doc, f)


def prepare_model_dir(run_dir: str, model_config: dict) -> str:
    """`<run_dir>/model`: the configuration as it is run, plus the
    generated tokenizer. No weights: the program draws them from its
    seed in the type they are served in."""
    model_dir = os.path.join(run_dir, "model")
    shutil.rmtree(model_dir, ignore_errors=True)
    os.makedirs(model_dir)
    with open(os.path.join(model_dir, "config.json"), "w") as f:
        json.dump(model_config, f, indent=1)
    write_tokenizer(model_dir, int(model_config["vocab_size"]))
    return model_dir


def server_command(model_dir: str, port: int, server_args: dict,
                   config_dir: str, run_dir: str, seed: int) -> list:
    """cell.json's `server_args` map option names to values: true is a
    bare flag, and a value starting with `@config/` is a file beside
    cell.json (a topology)."""
    cmd = [sys.executable, "-m", "cake_tpu.cli", "--model", model_dir,
           "--api", f"127.0.0.1:{port}", "--seed", str(seed),
           "--profile-dir", os.path.join(run_dir, "profile")]
    for key, value in server_args.items():
        if key in ("model", "api", "seed", "profile-dir"):
            raise SpecError(f"server_args may not set --{key}")
        if value is True:
            cmd.append(f"--{key}")
            continue
        if isinstance(value, str) and value.startswith("@config/"):
            value = os.path.join(config_dir, value[len("@config/"):])
        cmd += [f"--{key}", str(value)]
    return cmd


def server_env(rehearse: bool, chips: int) -> dict:
    env = dict(os.environ)
    env.pop("BENCH_RUN", None)
    if rehearse:
        env["JAX_PLATFORMS"] = "cpu"
        if chips > 1:
            env["XLA_FLAGS"] = (
                env.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count={chips}").strip()
    else:
        # a backend list that names only the TPU makes JAX fail at
        # start-up when there is none: no quiet CPU
        env["JAX_PLATFORMS"] = "tpu"
    return env


class Server:
    """The child and the few HTTP calls the harness makes to it."""

    def __init__(self, cmd: list, env: dict, log_path: str):
        self.port = int(cmd[cmd.index("--api") + 1].rsplit(":", 1)[1])
        self.cmd = cmd
        self.log_path = log_path
        self.t_spawn = time.monotonic()
        with open(log_path, "w") as log:
            self.proc = subprocess.Popen(cmd, cwd=ROOT, env=env, stdout=log,
                                         stderr=subprocess.STDOUT)

    # -- HTTP ----------------------------------------------------------

    def request(self, method: str, path: str, body=None,
                timeout: float = 60.0):
        conn = http.client.HTTPConnection("127.0.0.1", self.port,
                                          timeout=timeout)
        try:
            data = json.dumps(body).encode() if body is not None else None
            conn.request(method, path, body=data,
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            return resp.status, resp.read()
        finally:
            conn.close()

    def get_json(self, path: str, timeout: float = 60.0) -> dict:
        status, raw = self.request("GET", path, timeout=timeout)
        if status != 200:
            raise ServerFailure(f"GET {path} -> {status}: {raw[:300]!r}")
        return json.loads(raw)

    def metrics(self) -> dict:
        """/metrics as {series-with-labels: value}."""
        status, raw = self.request("GET", "/metrics")
        if status != 200:
            raise ServerFailure(f"GET /metrics -> {status}")
        return parse_metrics(raw.decode())

    # -- life cycle ----------------------------------------------------

    def wait_healthy(self) -> float:
        """Seconds from spawn to the first `status: ok`."""
        while time.monotonic() - self.t_spawn < HEALTH_TIMEOUT_S:
            if self.proc.poll() is not None:
                raise ServerFailure(
                    f"server exited with code {self.proc.returncode} "
                    "before it was healthy")
            try:
                status, raw = self.request("GET", "/api/v1/health",
                                           timeout=5.0)
            except OSError:
                time.sleep(0.25)
                continue
            if status == 200 and json.loads(raw).get("status") == "ok":
                return time.monotonic() - self.t_spawn
            time.sleep(0.25)
        raise ServerFailure(f"server not healthy in {HEALTH_TIMEOUT_S}s")

    def devices(self) -> dict:
        devs = self.get_json("/api/v1/cluster")["devices"]
        if not devs:
            raise ServerFailure("the server reports no device")
        kinds = {(d["platform"], d["kind"]) for d in devs}
        if len(kinds) != 1:
            raise ServerFailure(f"mixed devices: {sorted(kinds)}")
        return {"platform": devs[0]["platform"], "kind": devs[0]["kind"],
                "count": len(devs)}

    def stop(self) -> None:
        """Drain, wait, and make sure: nothing is left running."""
        if self.proc.poll() is None:
            try:
                self.request("POST", "/api/v1/drain", {"timeout_s": 5},
                             timeout=10.0)
                self.proc.wait(STOP_TIMEOUT_S)
            except (OSError, subprocess.TimeoutExpired):
                pass
        if self.proc.poll() is None:
            self.proc.terminate()
            try:
                self.proc.wait(20)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()

    def log_tail(self, n: int = 6000) -> str:
        try:
            with open(self.log_path, errors="replace") as f:
                return f.read()[-n:]
        except OSError:
            return ""


def parse_metrics(text: str) -> dict:
    out = {}
    for line in text.splitlines():
        if line and not line.startswith("#"):
            name, _, value = line.rpartition(" ")
            try:
                out[name] = float(value)
            except ValueError:
                pass
    return out


def metric_sum(metrics: dict, family: str) -> float:
    return sum(v for k, v in metrics.items()
               if k == family or k.startswith(family + "{"))


def metric_max(metrics: dict, family: str):
    vals = [v for k, v in metrics.items()
            if k == family or k.startswith(family + "{")]
    return max(vals) if vals else None
