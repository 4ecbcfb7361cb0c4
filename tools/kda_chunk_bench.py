#!/usr/bin/env python3
"""Time `cake_kda_chunk` alone on the chip against XLA's chunked form.

    chiprun -- python tools/kda_chunk_bench.py [--out chiprun_out/kda_chunk_bench.json]
    JAX_PLATFORMS=cpu python tools/kda_chunk_bench.py --rehearse   # tiny, interpreted

Ling's cell's widths (a window of 512 tokens, 32 heads of 128 x 128
float32, one layer: `ling3.longreply-closed`), N calls inside ONE
program (the state is the loop's carry: a call starts from what the
last one left; a loop of dispatches would read the host, PERF.md section
6, PR 34), the best of 5 runs a case:

  * `fold_sliced`: bailing_hybrid.kda_chunked on the window's tokens cut
    out of one of two sets by `i % 2` (a dynamic slice, as the served
    path's window slice is: with ONE set XLA would lift everything that
    does not depend on the state out of the loop);
  * `kernel_sliced`: ops/kda.chunked the same way (the slices are copies
    in front of the kernel: what the served path pays);
  * `kernel`: ops/kda.chunked on one set as it lies (the kernel alone);
  * `kernel_no_arith` (with --no-arith): the same grid, blocks and state
    traffic around a body that only copies v to o: what the bytes cost.

A case's `roofline_pct` is the chunked form's least bytes as the
benchmark counts them (benchmarks/harness/kda_roofline.py: q, k, v in
and o out at 2 bytes, the decay and beta in float32) at the device's
bandwidth (obs/steps.py's table: 819 GB/s on a v5e; no such key on a
CPU) over its time. Before the timing, ONE call of each on the same
inputs, compared on the device (`S_err`, `o_err`: the largest absolute
difference). Prints one JSON line; exits 1 where either passes 2e-5.
Not imported by the package; no cell of the benchmark runs it.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

TOLERANCE = 2e-5


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--no-arith", action="store_true",
                    help="also time the kernel's body with no arithmetic")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny widths, for a run with no chip")
    ap.add_argument("--out", help="also write the line to this file")
    a = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax import lax

    from cake_tpu.models.moe.bailing_hybrid import kda_chunked
    from cake_tpu.obs.steps import hbm_bps_for
    from cake_tpu.ops import kda
    from cake_tpu.ops import ragged_paged_attention as rpa

    C, H, dk, dv = (40, 4, 8, 16) if a.rehearse else (512, 32, 128, 128)
    N = a.calls
    ks = jax.random.split(jax.random.PRNGKey(0), 6)

    def fresh_state():
        return jax.random.normal(ks[0], (H, dk, dv), jnp.float32) * 0.2

    # two sets of a window's tokens: [2, C, H, d]
    q = jax.random.normal(ks[1], (2, C, H, dk)) * dk ** -0.5
    k = jax.random.normal(ks[2], (2, C, H, dk))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[3], (2, C, H, dv)).astype(jnp.bfloat16)
    g = -5 * jax.random.uniform(ks[4], (2, C, H, dk)) ** 4
    beta = jax.random.uniform(ks[5], (2, C, H))
    sets = (q, k, v, g, beta)

    interpret = not rpa._on_tpu()
    no_arith = functools.partial(kda._chunk_pallas, heads=kda.chunk_heads(H),
                                 interpret=interpret, arith=False)
    kind = jax.devices()[0].device_kind
    bandwidth = hbm_bps_for(kind)
    need = C * H * ((2 * dk + 2 * dv) * 2 + (dk + 1) * 4)
    out = {"device": kind, "shape": [C, H, dk, dv], "calls": N,
           "chunk": kda.CHUNK, "block": kda.CHUNK_BLOCK,
           "heads": kda.chunk_heads(H)}

    one = [x[1] for x in sets]
    ref_S, ref_o = jax.jit(kda_chunked)(fresh_state(), *one)
    S, o = jax.jit(kda.chunked)(fresh_state(), *one)
    out["check"] = {"S_err": float(jnp.max(jnp.abs(S - ref_S))),
                    "o_err": float(jnp.max(jnp.abs(o - ref_o)))}
    del S, o, ref_S, ref_o

    def timed(call, sliced):
        # sliced: [2, C, H, d] operands cut by i % 2 inside the loop;
        # else one set handed over as it lies
        operands = sets if sliced else one

        def run(S, *operands):
            def body(i, carry):
                S, acc = carry
                xs = ([lax.dynamic_index_in_dim(x, i % 2, 0, keepdims=False)
                       for x in operands] if sliced else operands)
                S, o = call(S, *xs)
                return S, acc + o
            return lax.fori_loop(0, N, body,
                                 (S, jnp.zeros((C, H, dv), jnp.float32)))

        run = jax.jit(run, donate_argnums=(0,))
        S, acc = run(fresh_state(), *operands)
        jax.block_until_ready(acc)
        best = float("inf")
        for _ in range(5):
            t = time.perf_counter()
            S, acc = run(S, *operands)
            jax.block_until_ready(acc)
            best = min(best, time.perf_counter() - t)
        read = {"us_a_call": round(best / N * 1e6, 1)}
        if bandwidth:
            read["roofline_pct"] = round(
                100 * need / bandwidth / (best / N), 1)
        return read

    out["fold_sliced"] = timed(kda_chunked, True)
    out["kernel_sliced"] = timed(kda.chunked, True)
    out["kernel"] = timed(kda.chunked, False)
    if a.no_arith:
        out["kernel_no_arith"] = timed(no_arith, False)
    line = json.dumps(out)
    print(line)
    if a.out:
        os.makedirs(os.path.dirname(os.path.abspath(a.out)), exist_ok=True)
        with open(a.out, "w") as f:
            f.write(line + "\n")
    return 0 if max(out["check"].values()) <= TOLERANCE else 1


if __name__ == "__main__":
    sys.exit(main())
