"""Multi-host runtime: init gating, slice-aware mesh layout, introspection."""

import numpy as np
import pytest

import jax

import cake_tpu.parallel.distributed as dist
from cake_tpu.parallel.distributed import (
    assign_hosts_to_stages, cluster_info, initialize, is_coordinator,
    make_multihost_mesh,
)


def test_initialize_noop_single_host():
    assert initialize(env={}) is False


def test_initialize_requires_signal():
    # explicit env without coordinator and no pod markers -> no-op
    assert initialize(env={"HOSTNAME": "x"}) is False


def test_initialize_single_entry_hostnames_after_backend_init():
    """A single-entry TPU_WORKER_HOSTNAMES (TPU VM images export it)
    is not a multi-worker signal: initialize() must
    no-op even after the XLA backend is live, where attempting
    jax.distributed.initialize raises RuntimeError (regression: the CLI
    path failed when called from a warm process)."""
    jax.devices()  # ensure the backend is initialised
    assert initialize(env={"TPU_WORKER_HOSTNAMES": "localhost"}) is False


def test_initialize_multi_worker_failfast_after_backend_init():
    # a genuine multi-worker signal must NOT silently downgrade
    jax.devices()
    with pytest.raises(RuntimeError):
        initialize(env={"TPU_WORKER_HOSTNAMES": "host0,host1"})


def test_is_coordinator_single_process():
    assert is_coordinator() is True


def test_cluster_info():
    info = cluster_info()
    assert info["process_count"] == 1
    assert info["device_count"] == len(jax.devices())
    assert info["slices"] == [0]


def test_single_slice_mesh_matches_make_mesh():
    m = make_multihost_mesh(dp=2, stage=2, tp=2)
    assert m.axis_names == ("dp", "stage", "tp")
    assert m.devices.shape == (2, 2, 2)


def test_multislice_dp_outermost(monkeypatch):
    """With 2 simulated slices and dcn_axis='dp', each dp half must sit
    entirely in one slice (cross-slice traffic confined to dp)."""
    devs = jax.devices()
    fake = {id(d): i // 4 for i, d in enumerate(devs)}  # 2 slices of 4
    monkeypatch.setattr(dist, "_slice_ids",
                        lambda ds: [fake[id(d)] for d in ds])
    m = make_multihost_mesh(dp=2, stage=2, tp=2, dcn_axis="dp")
    arr = m.devices
    for i in range(2):  # dp coordinate i = slice i
        got = {fake[id(d)] for d in arr[i].flat}
        assert got == {i}


def test_multislice_stage_outermost(monkeypatch):
    """dcn_axis='stage': pipeline stages split across slices, every other
    axis stays intra-slice (the reference's machine-per-layer-range shape)."""
    devs = jax.devices()
    fake = {id(d): i // 4 for i, d in enumerate(devs)}
    monkeypatch.setattr(dist, "_slice_ids",
                        lambda ds: [fake[id(d)] for d in ds])
    m = make_multihost_mesh(dp=1, stage=4, tp=2, dcn_axis="stage")
    arr = m.devices  # [1, 4, 2]
    for s in range(4):
        got = {fake[id(d)] for d in arr[:, s].flat}
        assert len(got) == 1, f"stage {s} spans slices {got}"
    # stages 0,1 on slice 0; stages 2,3 on slice 1
    assert {fake[id(d)] for d in arr[:, :2].flat} == {0}
    assert {fake[id(d)] for d in arr[:, 2:].flat} == {1}


def test_multislice_indivisible_raises(monkeypatch):
    devs = jax.devices()
    fake = {id(d): i // 4 for i, d in enumerate(devs)}
    monkeypatch.setattr(dist, "_slice_ids",
                        lambda ds: [fake[id(d)] for d in ds])
    with pytest.raises(ValueError, match="divisible"):
        make_multihost_mesh(dp=1, stage=1, tp=8, dcn_axis="stage")


def test_assign_hosts_to_stages():
    topo = {"a": None, "b": None, "c": None}
    assert assign_hosts_to_stages(topo, 2) == {"a": 0, "b": 1, "c": 0}


def test_plan_build_mesh_uses_multihost_path(tiny_config):
    from cake_tpu.parallel.plan import ParallelPlan
    plan = ParallelPlan.from_topology(tiny_config, None)
    m = plan.build_mesh()
    assert m.axis_names == ("dp", "stage", "tp")


def test_multihost_pipeline_executes(monkeypatch, tiny_config):
    """A pipeline sharded over a simulated 2-slice mesh (stage over DCN)
    still compiles and runs — the layout change must be transparent to
    shard_map."""
    import jax.numpy as jnp
    from cake_tpu.models.llama.cache import KVCache
    from cake_tpu.models.llama.model import RopeTables
    from cake_tpu.models.llama.params import init_params
    from cake_tpu.parallel.pipeline import (
        make_pipeline_forward, place_for_pipeline,
    )

    devs = jax.devices()
    fake = {id(d): i // 4 for i, d in enumerate(devs)}
    monkeypatch.setattr(dist, "_slice_ids",
                        lambda ds: [fake[id(d)] for d in ds])
    cfg = tiny_config
    params = init_params(cfg, jax.random.PRNGKey(0))
    mesh = make_multihost_mesh(dp=1, stage=2, tp=1, dcn_axis="stage",
                               devices=devs[:2] + devs[4:6])
    rope = RopeTables.create(cfg, 64)
    cache = KVCache.create(cfg, 4, 64)
    params_s, cache = place_for_pipeline(params, cache, mesh)
    pf = make_pipeline_forward(mesh, cfg, num_microbatches=2)
    toks = jnp.ones((4, 8), jnp.int32)
    logits, cache = pf(params_s, toks, cache, jnp.int32(0), rope,
                       is_prefill=True)
    assert logits.shape == (4, cfg.vocab_size)
    assert bool(jnp.isfinite(logits).all())
