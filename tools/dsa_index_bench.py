#!/usr/bin/env python3
"""Time `cake_dsa_index` alone on the chip against the blocked `lax.map`.

    chiprun -- python tools/dsa_index_bench.py [--out chiprun_out/dsa_index_bench.json]
    JAX_PLATFORMS=cpu python tools/dsa_index_bench.py --rehearse   # tiny, interpreted

The three selecting cells' shapes (a window of 512 queries; 16 index
heads of 64 over a table of 33,280 keys in `keyevl2.longctx-closed`, 32
of 128 over 12,800 in `glm52.longdoc-closed`, 64 of 128 over 16,896 in
`dots3.longshort-closed`), one layer, bf16 queries and keys, a window
that ends at `last_pos` 4k / 8k / 16k / 32k (those the table holds), N
calls inside ONE program (a loop of dispatches would read the host,
PERF.md section 6, PR 34; `last_pos` passes through the loop's carry,
so nothing is lifted out of it, and a call's result is read at one row),
the best of 5 runs a case:

  * `kernel`: ops/mla_attention.index_scores_window (`cake_dsa_index`);
  * `xla`: the form the step programs ran until PR 68, kept here and in
    tests/test_dsa_index_kernel.py alone: a `lax.map` over key blocks
    of whole 128-key pages, a divisor of the table's pages up to a
    thousand keys (`key_block`, `glm_dsa._key_block` as it was: 640,
    640 and 768 keys), with a `lax.cond` a block, the blocks stacked
    [blocks, queries, block] and transposed to [queries, keys].

Beside the times, what the kernel needs by its own tiling
(`index_tiles`): `scored` keys of the table's `S` (`index_scored`),
`flops` (2 x queries x heads x width x scored) with `mxu_us`, those at
the device's bf16 peak, and `bytes` (the queries and weights once, the
scored keys once a query tile, the whole [queries, keys] float32 result
written once) with `hbm_us`, those at the device's bandwidth
(obs/steps.py's tables); `floor_pct` is the larger of the two over the
kernel's time. `--tq` / `--chunks` try another tile (queries a tile,
128-key chunks a key block) in place of `index_tiles`'s. Before the
timing, ONE call of each on the same inputs, compared on the device
(`max_err` over the scored columns, relative to the largest score;
zeros past them on both sides). Prints one JSON line; exits 1 where a
comparison fails. Not imported by the package; no cell of the benchmark
runs it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# cell: (index heads, their width, the table's keys)
CELLS = {"keyevl2": (16, 64, 33280), "glm52": (32, 128, 12800),
         "dots3": (64, 128, 16896)}
LAST_POS = (4095, 8191, 16383, 32767)


def key_block(S: int, page: int = 128) -> int:
    """Keys a block of the `lax.map` form held: whole pages, a divisor
    of the row's page count, about a thousand keys."""
    pages = S // page
    return page * max(d for d in range(1, pages + 1)
                      if pages % d == 0 and d * page <= 1024)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--calls", type=int, default=16)
    ap.add_argument("--cells", default=",".join(CELLS))
    ap.add_argument("--tq", type=int, help="queries a tile")
    ap.add_argument("--chunks", type=int, help="128-key chunks a key block")
    ap.add_argument("--no-xla", action="store_true",
                    help="time the kernel alone")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny widths, for a run with no chip")
    ap.add_argument("--out", help="also write the line to this file")
    a = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax import lax

    from cake_tpu.obs.steps import hbm_bps_for, peak_flops_for
    from cake_tpu.ops import mla_attention as mla

    if a.chunks:
        mla.index_key_block = lambda S: a.chunks * 128
    if a.tq:
        tiles = mla.index_tiles
        mla.index_tiles = lambda *shapes: (a.tq, tiles(*shapes)[1])
    C = 16 if a.rehearse else 512
    cells = ({"tiny": (2, 16, 288)} if a.rehearse
             else {c: CELLS[c] for c in a.cells.split(",")})
    ends = (63, 255) if a.rehearse else LAST_POS
    page = 8 if a.rehearse else 128
    N = a.calls
    kind = jax.devices()[0].device_kind
    bandwidth, peak = hbm_bps_for(kind), peak_flops_for(kind)
    out = {"device": kind, "window": C, "calls": N, "cases": []}

    def xla(qI, kI, w, last_pos):
        S = kI.shape[0]
        block = key_block(S, page)
        blocks = kI.reshape(S // block, block, kI.shape[-1])

        def one(args):
            i, kb = args
            return lax.cond(i * block <= last_pos,
                            lambda: mla._weighted_relu(qI, kb, w),
                            lambda: jnp.zeros((C, block), jnp.float32))

        stack = lax.map(one, (jnp.arange(S // block), blocks))
        return jnp.transpose(stack, (1, 0, 2)).reshape(C, S)

    def timed(score, qI, kI, w, last_pos):
        def run(qI, kI, w, last_pos):
            def body(_, seen):
                # seen >= 0: the compiler cannot know, so the call stays
                last = last_pos + jnp.minimum(seen, 0)
                row = score(qI, kI, w, last)[seen % C]
                return seen + (jnp.sum(row) > 0).astype(jnp.int32)
            return lax.fori_loop(0, N, body, jnp.int32(0))

        run = jax.jit(run)
        jax.block_until_ready(run(qI, kI, w, last_pos))
        best = float("inf")
        for _ in range(5):
            t = time.perf_counter()
            jax.block_until_ready(run(qI, kI, w, last_pos))
            best = min(best, time.perf_counter() - t)
        return best / N

    ok = True
    for cell, (J, d, S) in cells.items():
        keys = jax.random.split(jax.random.PRNGKey(S), 3)
        qI = jax.random.normal(keys[0], (C, J, d), jnp.bfloat16)
        kI = jax.random.normal(keys[1], (S, d), jnp.bfloat16)
        w = jax.random.normal(keys[2], (C, J), jnp.float32) * (J * d) ** -0.5
        tq, kb = mla.index_tiles(C, J, d, S)
        block = key_block(S, page)
        for end in (e for e in ends if e < S):
            last = jnp.int32(end)
            scored = int(mla.index_scored(last, S))
            got = jax.jit(mla.index_scores_window)(qI, kI, w, last)
            want = jax.jit(xla)(qI, kI, w, last)
            live = min(scored, (end // block + 1) * block)
            err = float(jnp.max(jnp.abs(got[:, :live] - want[:, :live]))
                        / jnp.max(jnp.abs(want)))
            same = (err < 1e-5 and not bool(jnp.any(got[:, scored:]))
                    and not bool(jnp.any(want[:, (end // block + 1) * block:])))
            ok = ok and same
            flops = 2 * C * J * d * scored
            need = (C * J * (d * 2 + 4) + C // tq * scored * d * 2
                    + C * S * 4)
            case = {"cell": cell, "heads": J, "width": d, "S": S,
                    "last_pos": end, "same": same, "max_err": err,
                    "tiles": [tq, kb], "xla_block": block, "scored": scored,
                    "flops": flops,
                    "bytes": need}
            kernel = timed(mla.index_scores_window, qI, kI, w, last)
            case["kernel_us"] = round(kernel * 1e6, 1)
            if bandwidth and peak:
                case["mxu_us"] = round(flops / peak * 1e6, 1)
                case["hbm_us"] = round(need / bandwidth * 1e6, 1)
                case["floor_pct"] = round(
                    100 * max(flops / peak, need / bandwidth) / kernel, 1)
            if not a.no_xla:
                case["xla_us"] = round(timed(xla, qI, kI, w, last) * 1e6, 1)
            out["cases"].append(case)
    line = json.dumps(out)
    print(line)
    if a.out:
        os.makedirs(os.path.dirname(os.path.abspath(a.out)), exist_ok=True)
        with open(a.out, "w") as f:
            f.write(line + "\n")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
