#!/usr/bin/env python3
"""Time `cake_retention_step` alone on the chip, and say what bounds it.

    chiprun -- python tools/retention_step_bench.py [--out chiprun_out/retention_step_bench.json]
    JAX_PLATFORMS=cpu python tools/retention_step_bench.py --rehearse   # tiny, interpreted

`brumby14b.longreply16-closed`'s widths (10 layers, 16 rows, 8 K/V heads
of 5 query heads, a state of [9, 128, 1024] float32 a row, layer and K/V
head), N calls inside ONE program each (layer = i % L over the stacks,
which are the loop's carry and donated), the best of 5 runs a case:
`kernel_all` (every row steps), `kernel_one_stays` (15 of 16: a mixed
step's rows beside its window) and `kernel_some_fresh` (2 of 16 from
zeros). (Whether the copies or the vector unit bound the kernel was
asked once, with the same copies and no arithmetic: 2,149 us against
2,148, the copies; ops/retention.py's docstring, PERF.md section 6,
PR 63.)

A case's `roofline_pct` is its stepping rows' S and z, as the served
path lays them out (D = 9,216), read once and written once at the
device's bandwidth (obs/steps.py's table) over its time. Before the
timing, ONE call of the kernel and of `step_fold` on the same inputs (3
layers, 4 rows), compared on the device: the stepping rows' S, z and y
(`S_err`, `z_err`, `y_err`, worst entry over the fold's largest), a
staying row's and every other layer's bits, a staying row's `y`. Prints
one JSON line. Not imported by the package; no cell of the benchmark
runs it.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SHAPE = (10, 16, 8, 5, 128)         # L, B, KV, R, hd
REHEARSAL = (3, 4, 2, 2, 16)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny widths, for a run with no chip")
    ap.add_argument("--out", help="also write the line to this file")
    a = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax import lax

    from cake_tpu.ops import kda, retention

    L, B, G, R, hd = REHEARSAL if a.rehearse else SHAPE
    kind = jax.devices()[0].device_kind
    interpret = jax.default_backend() != "tpu"
    out = {"device": kind, "calls": a.calls, "ring_depth": kda.RING_DEPTH,
           "shape": [L, B, *retention.state_shape(G, hd, hd)]}
    D = retention.state_width(hd)
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    q = jax.random.normal(ks[1], (B, G, R, hd), jnp.float32)
    k = jax.random.normal(ks[2], (B, G, hd), jnp.float32)
    v = jax.random.normal(ks[3], (B, G, hd), jnp.float32)
    lg = -jnp.abs(jax.random.normal(ks[4], (B, G), jnp.float32)) * 0.01

    def stacks(layers, rows=B):
        return (jax.random.normal(
                    ks[0], (layers, rows) + retention.state_shape(G, hd, hd),
                    jnp.float32) * 0.2,
                jnp.abs(jax.random.normal(ks[5], (layers, rows, G, D),
                                          jnp.float32)) * 50.0)

    def kernel(S, z, j, code):
        return jax.jit(functools.partial(
            retention._step_pallas.__wrapped__, interpret=interpret))(
                S, z, jnp.asarray(j, jnp.int32), code, q, k, v, lg)

    every = jnp.full((B,), retention.STEP, jnp.int32)
    one_stays = every.at[B // 2].set(retention.STAY)
    fresh = every.at[B // 4:B // 4 + max(1, B // 8)].set(retention.FRESH)

    # one call of each on the same inputs, compared where they lie
    rows = min(B, 4)
    some = jnp.asarray([retention.STEP, retention.STAY, retention.FRESH,
                        retention.STEP][:rows], jnp.int32)
    cut = tuple(x[:rows] for x in (q, k, v, lg))
    S0, z0 = stacks(3, rows)
    want = jax.jit(lambda S, z: retention.step_fold(S, z, 1, some, *cut))(
        S0, z0)
    got = jax.jit(lambda S, z: retention._step_pallas.__wrapped__(
        S, z, jnp.int32(1), some, *cut, interpret=interpret))(S0, z0)
    steps, others = some != retention.STAY, jnp.arange(3) != 1

    def err(x, y):
        return float(jnp.max(jnp.abs(x - y)) / jnp.max(jnp.abs(y)))

    out["check"] = {
        "S_err": err(got[0][1][steps], want[0][1][steps]),
        "z_err": err(got[1][1][steps], want[1][1][steps]),
        "y_err": err(got[2][steps], want[2][steps]),
        "stay_bits": bool(jnp.all(got[0][1][~steps] == S0[1][~steps])
                          & jnp.all(got[1][1][~steps] == z0[1][~steps])),
        "others_bits": bool(jnp.all(got[0][others] == S0[others])
                            & jnp.all(got[1][others] == z0[others])),
        "y_stay_zero": bool(jnp.all(got[2][~steps] == 0))}
    del S0, z0, want, got

    def timed(code):
        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def run(S, z):
            def body(i, carry):
                S, z, acc = carry
                S, z, y = kernel(S, z, i % L, code)
                return S, z, acc + y
            return lax.fori_loop(0, a.calls, body,
                                 (S, z, jnp.zeros((B, G, R, hd), jnp.float32)))

        S, z = stacks(L)
        best = None
        for _ in range(5):
            t0 = time.perf_counter()
            S, z, acc = run(S, z)
            acc.block_until_ready()
            dt = (time.perf_counter() - t0) / a.calls
            best = dt if best is None else min(best, dt)
        del S, z
        read = {"us_a_call": round(best * 1e6, 1)}
        stepping = int(jnp.sum(code == retention.STEP)) * 2 + int(
            jnp.sum(code == retention.FRESH))
        from cake_tpu.obs.steps import hbm_bps_for
        bandwidth = hbm_bps_for(kind)
        if bandwidth:           # (a CPU has no row in the table)
            nbytes = stepping * G * D * (hd + 1) * 4
            read["roofline_pct"] = round(100.0 * nbytes / bandwidth / best, 1)
        return read

    out["kernel_all"] = timed(every)
    out["kernel_one_stays"] = timed(one_stays)
    out["kernel_some_fresh"] = timed(fresh)
    line = json.dumps(out)
    print(line, flush=True)
    if a.out:
        os.makedirs(os.path.dirname(os.path.abspath(a.out)), exist_ok=True)
        with open(a.out, "w") as f:
            f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
