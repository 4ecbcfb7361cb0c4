"""Native C++ runtime components vs their Python fallbacks.

The native library builds from csrc/ with the system g++ on first use; if
that fails these tests fail loudly (the build environment guarantees a
toolchain — silent fallback would mask a regression).
"""

import numpy as np
import pytest

from cake_tpu.native import is_available
from cake_tpu.native.scheduler import PyScheduler, make_scheduler


def test_native_library_builds():
    assert is_available(), "native library failed to build"


def test_the_schedulers_calls_keep_the_interpreter_lock(tmp_path):
    """One library, two handles (PR 54): the scheduler's calls, a row a
    token on the engine thread and microseconds under the library's own
    mutex, go through a PyDLL; the file mapping and prefetch of the
    safetensors reader let the lock go (a CDLL)."""
    import ctypes

    from cake_tpu import native
    from cake_tpu.native.safetensors import StFile

    sched = make_scheduler(max_slots=2)
    assert type(sched._lib) is ctypes.PyDLL
    assert sched._lib is native.get_scheduler_library()
    path, _tensors = _write_fixture(tmp_path)
    f = StFile(path)
    f.close()
    assert type(f._lib) is ctypes.CDLL
    assert f._lib is native.get_library()
    assert sched._lib._name == f._lib._name
    for name in ("submit", "cancel", "plan", "report", "queue_depth",
                 "active", "completed"):
        # declared on the handle that calls them, and on no other
        assert getattr(sched._lib, f"cake_sched_{name}").argtypes
        assert getattr(f._lib, f"cake_sched_{name}").argtypes is None


# -- safetensors reader ------------------------------------------------------

def _write_fixture(tmp_path):
    from cake_tpu.utils.loading import save_safetensors
    import ml_dtypes
    rng = np.random.default_rng(0)
    tensors = {
        "model.layers.0.w": rng.normal(size=(16, 32)).astype(np.float32),
        "model.layers.1.w": rng.normal(size=(8,)).astype(np.float16),
        "embed": rng.normal(size=(4, 4)).astype(ml_dtypes.bfloat16),
        "ids": np.arange(7, dtype=np.int64),
    }
    path = str(tmp_path / "model.safetensors")
    save_safetensors(path, tensors)
    return path, tensors


def test_native_safetensors_reader(tmp_path):
    from cake_tpu.native.safetensors import StFile

    path, expected = _write_fixture(tmp_path)
    f = StFile(path)
    assert sorted(f.names()) == sorted(expected)
    got = f.tensors()
    for name, ref in expected.items():
        np.testing.assert_array_equal(np.asarray(got[name]), ref)
        assert got[name].dtype == ref.dtype
    # subset selection
    sub = f.tensors(names=["embed"])
    assert list(sub) == ["embed"]
    f.close()


def test_native_reader_matches_python_loader(tmp_path):
    from cake_tpu.native.safetensors import read_file
    from cake_tpu.utils.loading import _st_load_file

    path, _ = _write_fixture(tmp_path)
    native, keepalive = read_file(path)
    pure = _st_load_file(path)
    assert sorted(native) == sorted(pure)
    for name in pure:
        np.testing.assert_array_equal(np.asarray(native[name]),
                                      np.asarray(pure[name]))


def test_native_view_outlives_handle(tmp_path):
    """Views must keep the mmap alive after all explicit refs are dropped."""
    import gc
    from cake_tpu.native.safetensors import read_file

    path, expected = _write_fixture(tmp_path)
    tensors, handle = read_file(path)
    arr = tensors["model.layers.0.w"]
    del tensors, handle
    gc.collect()
    np.testing.assert_array_equal(np.asarray(arr),
                                  expected["model.layers.0.w"])


def test_native_reader_rejects_garbage(tmp_path):
    from cake_tpu.native.safetensors import StFile

    bad = tmp_path / "bad.safetensors"
    bad.write_bytes(b"\xff" * 64)
    with pytest.raises(OSError):
        StFile(str(bad))


# -- continuous-batching scheduler -------------------------------------------

def _drive_scenario(sched):
    """4 slots, 6 requests; returns the ordered event log."""
    log = []
    for rid in range(1, 7):
        assert sched.submit(rid, prompt_len=8, max_new_tokens=2 + rid % 2)
    assert not sched.submit(3, 8, 4), "duplicate id must be rejected"
    assert sched.queue_depth == 6

    for it in range(12):
        prefill, decode = sched.plan()
        log.append(("plan", sorted(prefill), sorted(decode)))
        for rid, slot in prefill + decode:
            fin = sched.report(rid, 1, eos=False)
            if fin:
                log.append(("finished", rid))
        if sched.active == 0 and sched.queue_depth == 0:
            break
    assert sched.completed == 6
    assert sched.active == 0
    return log


def test_scheduler_python_fallback():
    _drive_scenario(PyScheduler(max_slots=4))


def test_scheduler_native():
    sched = make_scheduler(max_slots=4)
    assert type(sched).__name__ == "NativeScheduler"
    _drive_scenario(sched)


def test_scheduler_native_matches_python():
    """Identical FCFS scenario must produce the identical event log."""
    log_py = _drive_scenario(PyScheduler(max_slots=4))
    log_native = _drive_scenario(make_scheduler(max_slots=4))
    assert log_py == log_native


def test_scheduler_cancel():
    s = make_scheduler(max_slots=2)
    assert s.submit(1, 4, 10) and s.submit(2, 4, 10) and s.submit(3, 4, 10)
    prefill, _ = s.plan()
    assert sorted(p[0] for p in prefill) == [1, 2]
    assert s.cancel(3)          # still queued
    assert s.cancel(1)          # active: slot freed
    assert s.active == 1
    prefill, decode = s.plan()  # nothing queued; 2 decodes
    assert prefill == [] and [d[0] for d in decode] == [2]
    assert not s.cancel(99)
