#!/usr/bin/env python3
"""Time `cake_ssm_step` alone on the chip against the XLA forms it replaced.

    chiprun -- python tools/ssm_step_bench.py [--out chiprun_out/ssm_step_bench.json]
    JAX_PLATFORMS=cpu python tools/ssm_step_bench.py --rehearse   # tiny, interpreted

Two shapes, N calls inside ONE program each (layer = i % L over the
stack, which is the loop's carry and donated: a loop of dispatches would
read the host, PERF.md section 6, PR 34), the best of 5 runs a case:

  * `granite`, `granite4h.sessions-closed`'s widths (36 Mamba layers,
    64 rows, 64 heads of 64 x 128 float32, ONE group):
    `xla_served`, nemotron_h.ssm_step_fold as the step programs held it
    before PR 57 (two fusions over the state at one group);
    `xla_eight_groups`, the same with the one group handed over as
    eight equal ones (the compiler then makes ONE fusion: the yardstick,
    not a design); `kernel_all`, ops/ssm.step with every row stepping;
    `kernel_one_stays` (63 of 64: a mixed step's rows beside its
    window); `kernel_some_fresh` (4 of 64 from zeros); and
    `kernel_no_lane_sum`, the kernel with y's sum along the lanes (the
    matrix unit's) taken out;
  * `nemotron`, `nemotron3s.agent-closed`'s (10 blocks, 32 rows, 128
    heads of 64 x 128, eight groups): `xla_served` (one fusion there)
    and `kernel_all`: the bar a kernel has to pass before Nemotron's
    trunk takes it.

A case's `roofline_pct` is its stepping rows' state read once and
written once at the device's bandwidth (obs/steps.py's table: 819 GB/s
on a v5e; no such key on a CPU) over its time. Before the timing, ONE call of
the kernel and of the fold on the same inputs at each shape's widths (3
layers), compared on the device: the stepping rows' state and `y`
(`S_err`, `y_err`: 0.0 is bit-equal), a staying row's and every other
layer's bits, a staying row's `y`. Prints one JSON line. Not imported
by the package; no cell of the benchmark runs it.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SHAPES = {  # name -> (L, B, H, P, N, G)
    "granite": (36, 64, 64, 64, 128, 1),
    "nemotron": (10, 32, 128, 64, 128, 8),
}
REHEARSAL = {"granite": (3, 4, 8, 8, 128, 1), "nemotron": (3, 2, 8, 8, 128, 4)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--calls", type=int, default=36)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny widths, for a run with no chip")
    ap.add_argument("--out", help="also write the line to this file")
    a = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax import lax

    from cake_tpu.models.moe.nemotron_h import ssm_step_fold
    from cake_tpu.obs.steps import hbm_bps_for
    from cake_tpu.ops import kda, ssm

    N_CALLS = a.calls
    kind = jax.devices()[0].device_kind
    bandwidth = hbm_bps_for(kind)
    out = {"device": kind, "calls": N_CALLS, "ring_depth": kda.RING_DEPTH}

    def kernel_without_lane_sum(*args):
        """ops/ssm.step traced anew with the products themselves in
        their sum's place (the jitted wrapper caches on shapes)."""
        old, ssm._lane_sum = ssm._lane_sum, lambda v: v[:, :ssm.LANES]
        try:
            return jax.jit(functools.partial(
                ssm._step_pallas.__wrapped__,
                interpret=jax.default_backend() != "tpu"))(*args)
        finally:
            ssm._lane_sum = old

    def bench(name):
        L, B, H, P, N, G = (REHEARSAL if a.rehearse else SHAPES)[name]
        ks = jax.random.split(jax.random.PRNGKey(0), 7)
        x = jax.random.normal(ks[1], (B, H, P)).astype(jnp.bfloat16)
        Bm = jax.random.normal(ks[2], (B, G, N)).astype(jnp.bfloat16)
        Cm = jax.random.normal(ks[3], (B, G, N)).astype(jnp.bfloat16)
        dt = jax.nn.softplus(jax.random.normal(ks[4], (B, H)))
        a_ = -dt * jnp.exp(jax.random.normal(ks[5], (H,)))[None, :]
        D = jax.random.normal(ks[6], (H,))

        def stack(layers):
            return jax.random.normal(ks[0], (layers, B, H, P, N),
                                     jnp.float32) * 0.2

        def kernel(state, j, code):
            return ssm.step(state, j, code, x, Bm, Cm, dt, a_, D)

        def no_lane_sum(state, j, code):
            return kernel_without_lane_sum(
                state, jnp.asarray(j, jnp.int32), code, x, Bm, Cm, dt, a_, D)

        def fold(state, j, code, groups=G):
            wide = (B, groups, N)
            return ssm_step_fold(state, j, code, x, jnp.broadcast_to(Bm, wide),
                                 jnp.broadcast_to(Cm, wide), dt, a_, D)

        every = jnp.full((B,), ssm.STEP, jnp.int32)
        one_stays = every.at[B // 2].set(ssm.STAY)
        fresh = every.at[B // 4:B // 4 + max(1, B // 16)].set(ssm.FRESH)
        some = fresh.at[B // 2].set(ssm.STAY).at[B - 1].set(ssm.STAY)
        read = {"shape": [L, B, H, P, N], "groups": G,
                "block_heads": kda.block_heads(H, P * N * 4)}

        # one call of each on the same inputs, compared where they lie
        at = 1

        def one(call):
            return jax.jit(lambda s, c: call(s, jnp.int32(at), c),
                           donate_argnums=(0,))

        ref_S, ref_y = one(fold)(stack(3), some)
        S, y = one(kernel)(stack(3), some)
        before, steps = stack(3), some != ssm.STAY
        others = jnp.arange(3) != at
        read["check"] = {
            "S_err": float(jnp.max(jnp.abs(S[at] - ref_S[at]))),
            "y_err": float(jnp.max(jnp.abs(y - ref_y)[steps])),
            "stay_bits": bool(jnp.all(S[at][~steps] == before[at][~steps])),
            "others_bits": bool(jnp.all(S[others] == before[others])),
            "y_stay_zero": bool(jnp.all(y[~steps] == 0))}
        del S, y, ref_S, ref_y, before

        def timed(call, code):
            def run(state, code):
                def body(i, carry):
                    state, acc = carry
                    state, y = call(state, i % L, code)
                    return state, acc + y
                return lax.fori_loop(
                    0, N_CALLS, body,
                    (state, jnp.zeros((B, H, P), jnp.float32)))

            run = jax.jit(run, donate_argnums=(0,))
            state, acc = run(stack(L), code)
            jax.block_until_ready(acc)
            best = float("inf")
            for _ in range(5):
                t = time.perf_counter()
                state, acc = run(state, code)
                jax.block_until_ready(acc)
                best = min(best, time.perf_counter() - t)
            del state
            got = {"us_a_call": round(best / N_CALLS * 1e6, 1)}
            if bandwidth:
                need = int(jnp.sum(code != ssm.STAY)) * 2 * H * P * N * 4
                got["roofline_pct"] = round(
                    100 * need / bandwidth / (best / N_CALLS), 1)
            return got

        read["xla_served"] = timed(fold, every)
        read["kernel_all"] = timed(kernel, every)
        if name == "granite":
            read["xla_eight_groups"] = timed(
                functools.partial(fold, groups=8), every)
            read["kernel_one_stays"] = timed(kernel, one_stays)
            read["kernel_some_fresh"] = timed(kernel, fresh)
            read["kernel_no_lane_sum"] = timed(no_lane_sum, every)
        return read

    for name in SHAPES:
        out[name] = bench(name)
    line = json.dumps(out)
    print(line)
    if a.out:
        os.makedirs(os.path.dirname(os.path.abspath(a.out)), exist_ok=True)
        with open(a.out, "w") as f:
            f.write(line + "\n")
    return 0 if all(out[name]["check"][k] for name in SHAPES for k in
                    ("stay_bits", "others_bits", "y_stay_zero")) else 1


if __name__ == "__main__":
    sys.exit(main())
