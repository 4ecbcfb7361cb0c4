"""chip_smoke.py, the script the driver runs on the chip, checked here on
the CPU: it must refuse to pass without a TPU, and its --rehearse form
must drive the real entry point (python -m cake_tpu.cli ... --kv-pages)
end to end at a toy config. Three subprocess runs, ~15 s in all — which
is why this file sorts last in the suite (the tier-1 lane is cut at a
wall-clock cap, and seconds spent early push later files past it)."""

import json
import os
import shutil
import subprocess
import sys

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(argv, cwd=REPO, script=SMOKE, **env):
    return subprocess.run(
        [sys.executable, script, *argv], cwd=cwd,
        env={**os.environ, "JAX_PLATFORMS": "cpu", **env},
        capture_output=True, text=True, timeout=300)


def test_without_a_tpu_it_fails_and_prints_no_result():
    """No --rehearse: the server child is held to the TPU backend, so on
    this CPU-only box it dies at start-up and the smoke exits non-zero
    with no result line — it never passes on a quiet CPU."""
    proc = _run([])
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "CHIP SMOKE FAILED" in proc.stderr


def test_alone_in_a_directory_it_fails(tmp_path):
    """A directory that holds chip_smoke.py and nothing else of the repo
    has no program to drive: non-zero, no result (even rehearsing)."""
    script = shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    proc = _run(["--rehearse"], cwd=str(tmp_path), script=str(script))
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


def test_rehearsal_passes_and_caches_where_the_env_says(tmp_path):
    """--rehearse says it is one, serves every request through the
    paged mixed + decode steps, reports the fold (the honest name on a
    CPU) and ends with the result line; the server's compile cache
    lands under JAX_COMPILATION_CACHE_DIR and gains entries."""
    cache = tmp_path / "cache"
    proc = _run(["--rehearse"], JAX_COMPILATION_CACHE_DIR=str(cache))
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    assert lines[0].startswith("REHEARSAL")
    result = json.loads(lines[-1])
    assert result["ok"] is True and result["rehearsal"] is True
    assert result["device"]["platform"] == "cpu"
    out = proc.stdout
    assert '"attn_impl": {"decode": "fold", "mixed": "fold"}' in out
    assert '"decode": ["paged-fold"], "mixed": ["paged-fold"]' in out
    assert f"compile cache {cache}" in out
    assert len(os.listdir(cache)) > 0
