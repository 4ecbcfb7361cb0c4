"""Multi-host / multi-slice distributed runtime.

The reference's distribution story is master/worker processes over TCP
(SURVEY.md §2.7): the master dials each worker listed in topology.yml and
request/responses hidden states per hop. The TPU-native story is one SPMD
program launched on every host of a pod (or several pod slices):

  * `initialize()` — `jax.distributed.initialize` wrapper. On TPU pods all
    coordinates are auto-detected; elsewhere they come from
    CAKE_COORDINATOR / CAKE_NUM_PROCESSES / CAKE_PROCESS_ID (the moral
    equivalent of the reference's --address/--name flags, lib.rs:21-88).
  * `make_multihost_mesh()` — a ("dp","stage","tp") mesh whose slowest
    varying axis crosses the DCN (inter-slice) boundary, so cross-slice
    traffic is confined to ONE axis: "dp" (gradient-free inference
    replicas; cross-slice collectives only at admission) or "stage"
    (pipeline hop per decode step crosses DCN once — how the reference's
    multi-machine layer split maps onto multi-slice TPU).
  * `is_coordinator()` / `coordinator_only()` — process-0 gating; the REST
    API binds on the coordinator, matching "the master serves the API"
    (api/mod.rs:23-48) without a separate master process.

Host→stage placement parity: the reference's topology.yml names workers by
host (topology.rs:14-21). Here `assign_hosts_to_stages` maps topology
nodes onto slice ids so a node's block range lands on the slice that
"is" that worker.
"""

from __future__ import annotations

import logging
import os
from typing import Dict, List, Optional, Sequence

import numpy as np

import jax
from jax.sharding import Mesh

from cake_tpu.parallel.mesh import AXES

log = logging.getLogger(__name__)


def initialize(coordinator: Optional[str] = None,
               num_processes: Optional[int] = None,
               process_id: Optional[int] = None,
               env: Optional[Dict[str, str]] = None) -> bool:
    """Initialise JAX's distributed runtime for multi-host execution.

    Returns True if distributed init ran, False for single-process runs.
    Explicit args beat CAKE_* env vars beat auto-detection. Safe to call
    unconditionally: with no coordinator configured and a single process,
    it is a no-op.
    """
    env = dict(os.environ if env is None else env)
    coordinator = coordinator or env.get("CAKE_COORDINATOR") or None
    if num_processes is None and env.get("CAKE_NUM_PROCESSES"):
        num_processes = int(env["CAKE_NUM_PROCESSES"])
    if process_id is None and env.get("CAKE_PROCESS_ID"):
        process_id = int(env["CAKE_PROCESS_ID"])

    on_pod = bool(env.get("TPU_WORKER_HOSTNAMES") or env.get("MEGASCALE_COORDINATOR_ADDRESS"))
    if coordinator is None and not on_pod:
        return False  # single host, nothing to do

    kwargs = {}
    if coordinator:
        kwargs["coordinator_address"] = coordinator
    if num_processes is not None:
        kwargs["num_processes"] = num_processes
    if process_id is not None:
        kwargs["process_id"] = process_id
    multi_worker = (
        bool(env.get("MEGASCALE_COORDINATOR_ADDRESS"))
        or len([h for h in env.get("TPU_WORKER_HOSTNAMES", "").split(",")
                if h.strip()]) > 1
    )
    if not kwargs and not multi_worker:
        # Single-worker pod-ish env (e.g. a TPU VM image exports
        # TPU_WORKER_HOSTNAMES with one entry): there are no peers to
        # coordinate with, and attempting auto-init after the XLA backend
        # is live (library use, REPL, tests) raises RuntimeError.
        return False
    # Explicit config or a genuine multi-worker signal: let failures
    # propagate — silently downgrading one worker to single-process
    # would hang its peers in their first collective.
    jax.distributed.initialize(**kwargs)
    log.info("distributed: process %d/%d, %d local / %d global devices",
             jax.process_index(), jax.process_count(),
             jax.local_device_count(), jax.device_count())
    return True


def is_coordinator() -> bool:
    """True on the process that owns coordination (serves the REST API)."""
    return jax.process_index() == 0


def coordinator_only(fn):
    """Decorator: run fn only on the coordinator; others return None."""
    def wrapper(*a, **kw):
        if is_coordinator():
            return fn(*a, **kw)
        return None
    return wrapper


def _slice_ids(devices: Sequence) -> List[int]:
    """Slice index per device; falls back to process index (one slice per
    host) when the backend doesn't expose slice topology — either by
    returning None, or on the CPU backend, which reports slice_index 0
    everywhere even across processes (there, the process boundary IS the
    DCN/Gloo boundary). Real TPU pods keep their reported slice ids: a
    multi-host single-slice pod (e.g. v5p-16) is genuinely one
    ICI-connected slice and must not be split by process."""
    sids = [getattr(d, "slice_index", None) for d in devices]
    is_cpu = bool(devices) and getattr(devices[0], "platform", "") == "cpu"
    procs = {d.process_index for d in devices}
    if any(s is None for s in sids) or (is_cpu and len(set(sids)) == 1
                                        and len(procs) > 1):
        return [d.process_index for d in devices]
    return list(sids)


def make_multihost_mesh(dp: int = 1, stage: int = 1, tp: int = 1,
                        dcn_axis: str = "dp",
                        devices: Optional[Sequence] = None) -> Mesh:
    """("dp","stage","tp") mesh aware of slice (DCN) boundaries.

    The `dcn_axis` dimension is factored as (num_slices x per-slice) with
    the slice factor slowest-varying, so neighbouring coordinates along
    every other axis always live in the same slice and their collectives
    ride ICI. With one slice this degrades to `make_mesh` exactly.
    """
    if dcn_axis not in AXES:
        raise ValueError(f"dcn_axis must be one of {AXES}")
    devices = list(devices) if devices is not None else jax.devices()
    need = dp * stage * tp
    if need > len(devices):
        raise ValueError(
            f"mesh dp={dp} x stage={stage} x tp={tp} = {need} devices, "
            f"but only {len(devices)} available")

    sids = _slice_ids(devices)
    num_slices = len(set(sids))
    if num_slices == 1:
        arr = np.array(devices[:need]).reshape(dp, stage, tp)
        return Mesh(arr, AXES)

    sizes = {"dp": dp, "stage": stage, "tp": tp}
    if sizes[dcn_axis] % num_slices != 0:
        raise ValueError(
            f"dcn axis '{dcn_axis}'={sizes[dcn_axis]} must be divisible by "
            f"num_slices={num_slices}")
    per_slice_need = need // num_slices

    # group devices by slice, order groups by slice id
    by_slice: Dict[int, List] = {}
    for d, sid in zip(devices, sids):
        by_slice.setdefault(sid, []).append(d)
    groups = [by_slice[s] for s in sorted(by_slice)]
    if any(len(g) < per_slice_need for g in groups):
        raise ValueError(
            f"every slice needs {per_slice_need} devices for this mesh; "
            f"got {[len(g) for g in groups]}")

    # build [num_slices, per_slice_dcn, other axes...] then move the slice
    # factor into the dcn axis's slow position
    inner = {a: sizes[a] for a in AXES}
    inner[dcn_axis] = sizes[dcn_axis] // num_slices
    stacked = np.stack([
        np.array(g[:per_slice_need]).reshape(
            inner["dp"], inner["stage"], inner["tp"])
        for g in groups
    ])  # [S, dp_i, stage_i, tp_i]
    axis_pos = AXES.index(dcn_axis)
    # move S next to (before) the dcn axis and merge
    stacked = np.moveaxis(stacked, 0, axis_pos)
    arr = stacked.reshape(dp, stage, tp)
    return Mesh(arr, AXES)


def assign_hosts_to_stages(topology, num_slices: int) -> Dict[str, int]:
    """Map topology node names -> slice ids, preserving file order
    (reference: worker name -> host, topology.rs:14-21). With more nodes
    than slices, nodes wrap round-robin (several stages per slice)."""
    names = list(topology.keys())
    return {name: i % num_slices for i, name in enumerate(names)}


def cluster_info() -> dict:
    """Introspection snapshot (reference WorkerInfo, proto/message.rs:42-58,
    surfaced at /api/v1/cluster)."""
    devs = jax.devices()
    return {
        "process_index": jax.process_index(),
        "process_count": jax.process_count(),
        "device_count": len(devs),
        "local_device_count": jax.local_device_count(),
        "slices": sorted(set(_slice_ids(devs))),
        "platform": devs[0].platform if devs else None,
        "device_kind": devs[0].device_kind if devs else None,
    }
