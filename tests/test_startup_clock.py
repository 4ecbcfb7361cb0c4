"""cake_tpu/startup.py and obs/startup.py: the start-up clock's phases,
what is read of them, and the programs' making through jax.monitoring.
Counts and order only, never a time's size."""

import json
import os
import subprocess
import sys
import threading
import urllib.request

import pytest

import jax
import jax.numpy as jnp

from cake_tpu.args import Args
from cake_tpu.context import Context
from cake_tpu.obs import metrics as m
from cake_tpu.obs import startup
from cake_tpu.obs import steps as obs_steps
from cake_tpu.startup import STARTUP, StartupClock, process_start

NEW_FAMILIES = (
    "cake_startup_phase_seconds", "cake_startup_healthy_seconds",
    "cake_jit_trace_seconds_total", "cake_jit_lower_seconds_total",
    "cake_jit_backend_seconds_total", "cake_jit_cache_load_seconds_total",
    "cake_jit_cost_analysis_seconds_total", "cake_jit_cache_hits_total",
    "cake_jit_cache_misses_total")


@pytest.fixture
def clock():
    c = StartupClock()
    c.start()
    return c


@pytest.fixture
def running():
    """The module's own clock, as cli.main leaves it for a serving
    process; closed again whatever the test did."""
    STARTUP.start()
    yield STARTUP
    STARTUP.stop()
    STARTUP.healthy_at = None


# -- the clock ----------------------------------------------------------------


def test_phases_come_out_in_order_apart_and_add_up(clock):
    names = ["args", "weights", "engine"]
    for name in names:
        with clock.phase(name):
            pass
    clock.healthy_at = clock._phases[-1][2] + 0.5
    snap = clock.snapshot()
    # start() files what lay before it, from the zero
    assert [p[0] for p in snap["phases"]] == ["boot"] + names
    assert snap["phases"][0][1] == 0.0
    assert all(len(p) == 3 for p in snap["phases"])
    ends = [p[1] + p[2] for p in snap["phases"]]
    starts = [p[1] for p in snap["phases"]]
    assert all(e <= s + 1e-6 for e, s in zip(ends, starts[1:]))
    assert starts[0] >= 0.0
    total = sum(p[2] for p in snap["phases"]) + snap["unnamed_s"]
    assert total == pytest.approx(snap["healthy_s"], abs=1e-4)
    assert snap["unnamed_s"] >= 0.5 - 1e-6


@pytest.mark.parametrize("how", ["nested", "nested_in_itself", "reopened",
                                 "boot_opened"])
def test_a_phase_that_breaks_the_rules_is_refused_by_name(clock, how):
    with clock.phase("weights"):
        if how == "nested":
            with pytest.raises(ValueError, match="'engine'.*'weights'"):
                with clock.phase("engine"):
                    pass
        if how == "nested_in_itself":
            with pytest.raises(ValueError, match="'weights'.*'weights'"):
                with clock.phase("weights"):
                    pass
    if how == "reopened":
        with pytest.raises(ValueError, match="'weights' opened twice"):
            with clock.phase("weights"):
                pass
    if how == "boot_opened":     # start() filed it
        with pytest.raises(ValueError, match="'boot' opened twice"):
            with clock.phase("boot"):
                pass
    assert [p[0] for p in clock._phases] == ["boot", "weights"]
    with clock.phase("engine"):      # a refusal leaves the clock usable
        pass
    assert clock._phases[-1][0] == "engine"


def test_a_clock_that_does_not_run_files_nothing_and_refuses_nothing():
    c = StartupClock()
    for _ in range(2):     # a library caller builds two engines
        with c.phase("engine"):
            with c.phase("weights"):
                pass
    assert not c.healthy()
    assert c._phases == [] and c.healthy_at is None and not c.ran
    # nor does one that was stopped: cli.main without --api
    c.start()
    c.stop()
    for _ in range(2):
        with c.phase("engine"):
            pass
    assert [p[0] for p in c._phases] == ["boot"] and not c.ran


def test_the_zero_is_the_processs_start_and_no_later_than_the_import():
    import time
    now = time.perf_counter()
    zero, origin = process_start(now)
    assert zero <= now and origin in ("proc", "import")
    if os.path.exists("/proc/self/stat"):
        assert origin == "proc"


def test_the_first_healthy_mark_closes_the_clock_once(running, caplog):
    with running.phase("weights"):
        pass
    with caplog.at_level("INFO", logger="cake_tpu.obs.startup"):
        threads = [threading.Thread(target=startup.healthy)
                   for _ in range(8)]
        [t.start() for t in threads]
        [t.join(10) for t in threads]
    lines = [r for r in caplog.records if r.getMessage().startswith(
        "startup: ")]
    assert len(lines) == 1
    said = json.loads(lines[0].getMessage()[len("startup: "):])
    assert list(said["phases"]) == ["boot", "weights"]
    assert {"healthy_s", "unnamed_s", "programs", "cache"} <= set(said)
    assert not running.running and running.ran
    with running.phase("weights"):     # closed: a no-op, not a refusal
        pass
    assert len(running._phases) == 2


def test_the_clock_is_had_before_the_first_import_of_jax():
    """cli.py imports the clock at its top; were jax among what that
    pulls in, its import would be `boot`'s and not `import_jax`'s."""
    proc = subprocess.run(
        [sys.executable, "-c",
         "import sys, cake_tpu.cli; "
         "assert cake_tpu.cli.STARTUP is sys.modules["
         "'cake_tpu.startup'].STARTUP; "
         "sys.exit('jax' in sys.modules or 'cake_tpu.obs' in sys.modules)"],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]


# -- the programs' making -----------------------------------------------------


@pytest.fixture
def listening(tmp_path):
    """PROGRAMS on jax's events with the persistent cache in a
    directory of the test's own; the settings go back as they were."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes",
            "jax_enable_compilation_cache")
    was = {k: getattr(jax.config, k) for k in keys}
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()
    startup.listen()
    yield startup.PROGRAMS
    for k, v in was.items():
        jax.config.update(k, v)
    cc.reset_cache()


def _counts():
    return {"hits": startup._CACHE_HITS.value,
            "misses": startup._CACHE_MISSES.value,
            "asked": startup.PROGRAMS.asked}


def test_one_miss_then_one_hit_and_seconds_only_where_a_program_is_made(
        listening):
    @jax.jit
    def startup_clock_probe(x):
        return jnp.tanh(x @ x).sum() * 3.0

    x = jnp.ones((8, 8), jnp.float32)
    x.block_until_ready()     # the eager ops' own programs are made
    c0, s0 = _counts(), startup.seconds()
    startup_clock_probe(x)
    c1, s1 = _counts(), startup.seconds()
    trace, lower, backend = (s1[i] - s0[i] for i in range(3))
    assert trace > 0 and lower > 0 and backend > 0
    assert (c1["misses"] - c0["misses"], c1["hits"] - c0["hits"]) == (1, 0)
    row = listening.table["startup_clock_probe"]
    assert row[0] > 0 and row[1] > 0 and row[2] > 0
    assert row[3] is False and row[4] == 1
    # the second call of one signature makes nothing
    startup_clock_probe(x)
    assert startup.seconds() == s1 and _counts() == c1
    # a process that lost its programs finds the executable again
    jax.clear_caches()
    startup_clock_probe(x)
    c2 = _counts()
    assert (c2["misses"] - c1["misses"], c2["hits"] - c1["hits"]) == (0, 1)
    assert c2["asked"] - c0["asked"] == 2
    assert c2["hits"] + c2["misses"] - c0["hits"] - c0["misses"] == 2
    assert row[3] is True and row[4] == 2
    assert startup.seconds()[3] > s1[3]      # the cache's load
    snap = listening.snapshot()
    assert len(snap["top"]) <= startup.TABLE_TOP
    assert snap["made"] >= 2


def test_a_span_counts_its_seconds_less_the_spans_inside_it():
    spans = startup._Spans()
    spans.enter()                       # a parent
    spans.enter()                       # a child, with a child of its own
    spans.enter()
    assert spans.own(0.25) == pytest.approx(0.25)
    assert spans.own(1.0) == pytest.approx(0.75)
    spans.enter()                       # the parent's second child
    assert spans.own(0.5) == pytest.approx(0.5)
    assert spans.own(5.0) == pytest.approx(3.5)
    assert spans.open == []
    # an end whose opening was never heard counts whole
    assert spans.own(1.0) == pytest.approx(1.0)
    # far more children than any bound on what is kept
    spans.enter()
    for _ in range(20000):
        spans.enter()
        spans.own(0.001)
    assert spans.own(30.0) == pytest.approx(10.0)


def test_a_jitted_function_traced_inside_another_is_counted_once(listening):
    import time

    @jax.jit
    def startup_clock_inner(x):
        return jnp.tanh(x) + 1.0

    @jax.jit
    def startup_clock_outer(x):
        for _ in range(8):
            x = startup_clock_inner(x * 1.5)
        return x

    x = jnp.ones((4,), jnp.float32)
    x.block_until_ready()
    def made_s():     # the four that add up: not the cache's load
        s = startup.seconds()
        return s[0] + s[1] + s[2] + s[4]

    s0, t0 = made_s(), time.perf_counter()
    startup_clock_outer(x)
    wall = time.perf_counter() - t0
    made = made_s() - s0
    assert 0 < made <= wall
    assert listening.table["startup_clock_inner"][0] > 0
    assert listening._spans.open == []


def test_the_accountants_callback_is_timed_apart_from_what_jax_reports(
        listening):
    @jax.jit
    def startup_clock_costed(x):
        return (x * 2.0).sum()

    x = jnp.ones((4,), jnp.float32)
    acct = obs_steps.JitAccountant()
    cost0 = startup._COST_S.value
    new, cost, before = acct.begin(
        "costed", ("k",),
        lambda: obs_steps.lower_cost(startup_clock_costed, (x,)))
    assert new and cost is not None and len(before) == len(startup.SECONDS)
    assert startup._COST_S.value > cost0
    # the lowering inside the callback went to its own counters
    after = startup.seconds()
    assert after[0] > before[0] and after[1] > before[1]
    assert acct.begin("costed", ("k",), lambda: 1 / 0) == (
        False, cost, None)


# -- a compiled step's record -------------------------------------------------


def test_a_compiled_record_carries_jit_s_and_the_next_does_not():
    from cake_tpu.models.llama.config import LlamaConfig
    from cake_tpu.models.llama.generator import ByteTokenizer
    from cake_tpu.models.llama.params import init_params
    from cake_tpu.ops.sampling import SamplingConfig
    from cake_tpu.serve.engine import InferenceEngine

    startup.listen()
    cfg = LlamaConfig.tiny(num_hidden_layers=2)
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    eng = InferenceEngine(
        cfg, params, ByteTokenizer(cfg.vocab_size), max_slots=3,
        max_seq_len=192, sampling=SamplingConfig(temperature=0.0),
        # sizes no other test's engine has: the accountant is the
        # process's, and a signature it has seen makes no program
        cache_dtype=jnp.float32, kv_pages=24, kv_page_size=16)
    with eng:
        h = eng.submit(list(range(3, 3 + 20)), max_new_tokens=12)
        assert h.wait(180)
    recs = list(reversed(eng.flight.dump()))
    compiled = [r for r in recs if r["compiled"]]
    assert compiled, "a fresh engine's first steps make programs"
    for r in compiled:
        assert list(r["jit_s"]) == [k for k, _ in startup.SECONDS]
        assert all(v >= 0.0 for v in r["jit_s"].values())
    steady = [r for r in recs if not r["compiled"]]
    assert steady and all("jit_s" not in r for r in steady)
    assert eng.flight._jit_before is None


# -- where it is read ---------------------------------------------------------


def test_metrics_show_every_new_family_and_the_lint_passes(running):
    import importlib.util
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "lint_metrics", os.path.join(root, "tools", "lint_metrics.py"))
    lm = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(lm)
    with running.phase("weights"):
        pass
    startup.healthy()
    text = m.REGISTRY.render()
    for family in NEW_FAMILIES:
        assert f"# TYPE {family} " in text, family
    assert 'cake_startup_phase_seconds{phase="weights"}' in text
    assert 'cake_startup_phase_seconds{phase="unnamed"}' in text
    assert "cake_jit_compile_seconds" not in text
    assert lm.lint(text) == []
    with open(os.path.join(root, "README.md")) as f:
        assert lm.lint_readme_coverage(text, f.read()) == []


@pytest.fixture
def served(running):
    from cake_tpu.api.server import start
    from cake_tpu.master import Master
    from cake_tpu.models.llama.config import LlamaConfig
    from cake_tpu.models.llama.generator import (
        ByteTokenizer, LlamaGenerator,
    )
    from cake_tpu.models.llama.params import init_params
    from cake_tpu.ops.sampling import SamplingConfig

    cfg = LlamaConfig.tiny(num_hidden_layers=2)
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    gen = LlamaGenerator(cfg, params, ByteTokenizer(cfg.vocab_size),
                         max_seq_len=128,
                         sampling=SamplingConfig(temperature=0.0),
                         cache_dtype=jnp.float32)
    httpd = start(Master(Args(sample_len=4), text_generator=gen),
                  address="127.0.0.1:0", block=False)
    host, port = httpd.server_address[:2]
    yield f"http://{host}:{port}"
    httpd.shutdown()


def _get(url):
    with urllib.request.urlopen(url, timeout=60) as resp:
        return json.loads(resp.read())


def test_health_carries_startup_and_the_lite_form_does_not(served, running):
    assert running.running
    first = _get(served + "/api/v1/health")
    assert not running.running, "the first healthy answer is the mark"
    names = [p[0] for p in first["startup"]["phases"]]
    assert names == ["boot", "engine", "server"]
    doc = _get(served + "/api/v1/health")["startup"]
    assert doc["healthy_s"] > 0 and doc["zero"] in ("proc", "import")
    assert [p[0] for p in doc["phases"]] == names
    assert all(len(p) == 3 for p in doc["phases"])
    total = sum(p[2] for p in doc["phases"]) + doc["unnamed_s"]
    assert total == pytest.approx(doc["healthy_s"], abs=1e-3)
    assert {"seconds", "cache", "made", "top"} <= set(doc["programs"])
    assert "startup" not in _get(served + "/api/v1/health?lite=1")
    assert m.REGISTRY.get("cake_startup_healthy_seconds").value == \
        pytest.approx(doc["healthy_s"], abs=1e-3)


TOPOLOGY_2WAY = """\
worker0:
  host: 10.0.0.1:10128
  layers:
    - model.layers.0-1
worker1:
  host: 10.0.0.2:10128
  layers:
    - model.layers.2-3
"""


@pytest.mark.parametrize("branch", ["one_device", "two_device_mesh"])
def test_both_branches_of_load_text_model_file_a_weights_phase(
        branch, running, tmp_path):
    kw = dict(model="", max_seq_len=128, batch_size=1, sample_len=4,
              temperature=0.0, repeat_penalty=1.0, flash_attention=False)
    if branch == "two_device_mesh":
        topo = tmp_path / "topology.yml"
        topo.write_text(TOPOLOGY_2WAY)
        kw["topology"] = str(topo)
    gen = Context.from_args(Args(**kw).validate()).load_text_model()
    assert (gen.parallel is not None) == (branch == "two_device_mesh")
    names = [p[0] for p in running.snapshot()["phases"]]
    want = (["config", "weights", "generator"] if branch == "one_device"
            else ["config", "mesh", "weights", "place", "generator"])
    assert names == ["boot"] + want
