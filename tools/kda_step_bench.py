#!/usr/bin/env python3
"""Time `cake_kda_step` alone on the chip against the XLA form it replaced.

    chiprun -- python tools/kda_step_bench.py [--out chiprun_out/kda_step_bench.json]
    JAX_PLATFORMS=cpu python tools/kda_step_bench.py --rehearse   # tiny, interpreted

Ling's cell's widths (10 KDA layers, 32 rows, 32 heads of 128 x 128
float32: `ling3.longreply-closed`), N calls inside ONE program (layer =
i % L over the stack, which is the loop's carry and donated: a loop of
dispatches would read the host, PERF.md section 6, PR 34), the best of 5
runs a case:

  * `fold`: bailing_hybrid.kda_step_fold, every row stepping;
  * `kernel`: ops/kda.step with every row stepping, with 24 of 32
    (6 staying, 2 fresh among them) and with none.

A case's `roofline_pct` is its stepping rows' state read once and
written once at the device's bandwidth (obs/steps.py's table: 819 GB/s
on a v5e; no such key on a CPU) over its time. Before the timing, ONE call of
each on the same inputs, compared on the device: the stepping rows'
state and `o` (`S_err`, `o_err`: 0.0 is bit-equal), a staying row's and
every other layer's bits, a staying row's `o`. Prints one JSON line.
Not imported by the package; no cell of the benchmark runs it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--calls", type=int, default=30)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny widths, for a run with no chip")
    ap.add_argument("--out", help="also write the line to this file")
    a = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax import lax

    from cake_tpu.models.moe.bailing_hybrid import kda_step_fold
    from cake_tpu.obs.steps import hbm_bps_for
    from cake_tpu.ops import kda

    L, B, H, dk, dv = (3, 8, 4, 8, 128) if a.rehearse else (10, 32, 32, 128, 128)
    N = a.calls
    ks = jax.random.split(jax.random.PRNGKey(0), 6)

    def fresh_stack():
        return jax.random.normal(ks[0], (L, B, H, dk, dv), jnp.float32) * 0.2

    q = jax.random.normal(ks[1], (B, H, dk)) * dk ** -0.5
    k = jax.random.normal(ks[2], (B, H, dk))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[3], (B, H, dv)).astype(jnp.bfloat16)
    g = -5 * jax.random.uniform(ks[4], (B, H, dk)) ** 4
    beta = jax.random.uniform(ks[5], (B, H))

    def kernel(state, j, code):
        return kda.step(state, j, code, q, k, v, g, beta)

    def fold(state, j, code):
        return kda_step_fold(state, j, code, q, k, v, g, beta)

    every = jnp.full((B,), kda.STEP, jnp.int32)
    # of 32 rows: 20 step, 6 stay, 2 are fresh, 4 step
    stay, fresh = max(1, 3 * B // 16), max(1, B // 16)
    some = every.at[5 * B // 8:5 * B // 8 + stay].set(kda.STAY)
    some = some.at[5 * B // 8 + stay:5 * B // 8 + stay + fresh].set(kda.FRESH)
    none = jnp.full((B,), kda.STAY, jnp.int32)
    kind = jax.devices()[0].device_kind
    bandwidth = hbm_bps_for(kind)
    out = {"device": kind,
           "shape": [L, B, H, dk, dv], "calls": N,
           "block_heads": kda.block_heads(H, dk * dv * 4),
           "ring_depth": kda.RING_DEPTH}

    # one call of each on the same inputs, compared where they lie
    at = L // 3

    def one(call):
        return jax.jit(lambda s, c: call(s, jnp.int32(at), c),
                       donate_argnums=(0,))

    ref_S, ref_o = one(fold)(fresh_stack(), some)
    S, o = one(kernel)(fresh_stack(), some)
    before, steps = fresh_stack(), some != kda.STAY
    others = jnp.arange(L) != at
    out["check"] = {
        "S_err": float(jnp.max(jnp.abs(S[at] - ref_S[at]))),
        "o_err": float(jnp.max(jnp.abs(o - ref_o)[steps])),
        "stay_bits": bool(jnp.all(S[at][~steps] == before[at][~steps])),
        "others_bits": bool(jnp.all(S[others] == before[others])),
        "o_stay_zero": bool(jnp.all(o[~steps] == 0))}
    del S, o, ref_S, ref_o, before

    def timed(call, code):
        def run(state, code):
            def body(i, carry):
                state, acc = carry
                state, o = call(state, i % L, code)
                return state, acc + o
            return lax.fori_loop(0, N, body,
                                 (state, jnp.zeros((B, H, dv), jnp.float32)))

        run = jax.jit(run, donate_argnums=(0,))
        state, acc = run(fresh_stack(), code)
        jax.block_until_ready(acc)
        best = float("inf")
        for _ in range(5):
            t = time.perf_counter()
            state, acc = run(state, code)
            jax.block_until_ready(acc)
            best = min(best, time.perf_counter() - t)
        del state
        read = {"us_a_call": round(best / N * 1e6, 1)}
        if bandwidth:
            need = int(jnp.sum(code != kda.STAY)) * 2 * H * dk * dv * 4
            read["roofline_pct"] = round(
                100 * need / bandwidth / (best / N), 1)
        return read

    out["fold_all"] = timed(fold, every)
    out["kernel_all"] = timed(kernel, every)
    out["kernel_some"] = timed(kernel, some)
    out["kernel_none"] = timed(kernel, none)
    line = json.dumps(out)
    print(line)
    if a.out:
        os.makedirs(os.path.dirname(os.path.abspath(a.out)), exist_ok=True)
        with open(a.out, "w") as f:
            f.write(line + "\n")
    check = out["check"]
    return 0 if (check["stay_bits"] and check["others_bits"]
                 and check["o_stay_zero"]) else 1


if __name__ == "__main__":
    sys.exit(main())
