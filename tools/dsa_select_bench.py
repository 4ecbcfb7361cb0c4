#!/usr/bin/env python3
"""Time `cake_dsa_select` alone on the chip against `select_mask`.

    chiprun -- python tools/dsa_select_bench.py [--out chiprun_out/dsa_select_bench.json]
    JAX_PLATFORMS=cpu python tools/dsa_select_bench.py --rehearse   # tiny, interpreted

The three selecting cells' shapes (a window of 512 queries, top 2,048;
the table 33,280 keys wide in `keyevl2.longctx-closed`, 12,800 in
`glm52.longdoc-closed`, 16,896 in `dots3.longshort-closed`), one layer,
a full window that ends at `last_pos` 2k / 8k / 16k / 32k (those the
table holds), N calls inside ONE program (a loop of dispatches would
read the host, PERF.md section 6, PR 34; `last_pos` passes through the
loop's carry, so nothing is lifted out of it), the best of 5 runs a
case:

  * `kernel`: ops/mla_attention.select_window (`cake_dsa_select`);
  * `xla`: ops/mla_attention.select_mask on the visibility the kernel
    derives (`span <= min(positions, last_pos)`), built inside the
    loop as the step programs built it before PR 61.

Beside the times, what the kernel moves by its own tiling
(`select_tiles`): `walked` keys of the table's `S` (`select_walked`),
`bytes` (the walked scores read once, float32, and the whole mask
written once, int8) with `hbm_pct`, those bytes at the device's
bandwidth (obs/steps.py's table) over the time, and `vector_passes`
(loads of a [8, 128] vector of codes: 32 counting passes, one for the
keys above the k-th, the encoding and the tie pass over the walked
keys); for `select_mask`, `xla_bytes` and `xla_vector_passes` by the
same count of the function as written: its [C, S] codes read by 32
counting passes and by `above`, `tied` and the running count in and
out, over the table's whole width whatever `last_pos`. `--tq` /
`--chunks` try another tile (queries a tile, 128-key
chunks a block) in place of `select_tiles`'s. Before the timing, ONE
call of each on the same inputs (half the scores exactly 0.0, the
relu's floor: ties at the k-th value), compared bit for bit on the
device. Prints one JSON line; exits 1 where a mask differs. Not
imported by the package; no cell of the benchmark runs it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CELLS = {"keyevl2": 33280, "glm52": 12800, "dots3": 16896}
LAST_POS = (2047, 8191, 16383, 32767)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--calls", type=int, default=16)
    ap.add_argument("--cells", default=",".join(CELLS))
    ap.add_argument("--tq", type=int, help="queries a tile")
    ap.add_argument("--chunks", type=int, help="128-key chunks a block")
    ap.add_argument("--no-xla", action="store_true",
                    help="time the kernel alone")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny widths, for a run with no chip")
    ap.add_argument("--out", help="also write the line to this file")
    a = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax import lax

    from cake_tpu.obs.steps import hbm_bps_for
    from cake_tpu.ops import mla_attention as mla

    if a.tq or a.chunks:
        tiles = mla.select_tiles

        def select_tiles(C, S):
            tq, chunk, block = tiles(C, S)
            return (a.tq or tq, chunk,
                    a.chunks * chunk if a.chunks else block)

        mla.select_tiles = select_tiles
    C, K = (16, 24) if a.rehearse else (512, 2048)
    cells = ({"tiny": 256} if a.rehearse
             else {c: CELLS[c] for c in a.cells.split(",")})
    ends = (63, 255) if a.rehearse else LAST_POS
    N = a.calls
    kind = jax.devices()[0].device_kind
    bandwidth = hbm_bps_for(kind)
    out = {"device": kind, "window": C, "topk": K, "calls": N, "cases": []}

    def xla(scores, positions, last_pos, k):
        at = jnp.minimum(positions, last_pos)
        return mla.select_mask(
            scores, jnp.arange(scores.shape[1])[None, :] <= at[:, None], k)

    def timed(select, scores, last_pos):
        def run(scores, last_pos):
            def body(_, seen):
                # seen >= 0: the compiler cannot know, so the call stays
                last = last_pos + jnp.minimum(seen, 0)
                positions = last - C + 1 + jnp.arange(C)
                return seen + jnp.sum(select(scores, positions, last, K),
                                      dtype=jnp.int32) % 7
            return lax.fori_loop(0, N, body, jnp.int32(0))

        run = jax.jit(run)
        jax.block_until_ready(run(scores, last_pos))
        best = float("inf")
        for _ in range(5):
            t = time.perf_counter()
            jax.block_until_ready(run(scores, last_pos))
            best = min(best, time.perf_counter() - t)
        return best / N

    ok = True
    for cell, S in cells.items():
        scores = jax.random.normal(jax.random.PRNGKey(S), (C, S), jnp.float32)
        scores = jnp.where(jax.random.bernoulli(jax.random.PRNGKey(1), 0.5,
                                                (C, S)), scores, 0.0)
        tq, chunk, block = mla.select_tiles(C, S)
        for end in (e for e in ends if e < S):
            last = jnp.int32(end)
            positions = last - C + 1 + jnp.arange(C)
            same = bool(jnp.array_equal(
                jax.jit(mla.select_window, static_argnums=3)(
                    scores, positions, last, K),
                jax.jit(xla, static_argnums=3)(scores, positions, last, K)))
            ok = ok and same
            walked = int(mla.select_walked(last, C, S))
            need = C * walked * 4 + C * S
            case = {"cell": cell, "S": S, "last_pos": end, "same": same,
                    "tiles": [tq, chunk, block], "walked": walked,
                    "bytes": need, "vector_passes": 35 * C * walked // 1024}
            kernel = timed(mla.select_window, scores, last)
            case["kernel_us"] = round(kernel * 1e6, 1)
            if bandwidth:
                case["hbm_pct"] = round(100 * need / bandwidth / kernel, 1)
            if not a.no_xla:
                case["xla_us"] = round(timed(xla, scores, last) * 1e6, 1)
                case["xla_bytes"] = 36 * C * S * 4
                case["xla_vector_passes"] = 36 * C * S // 1024
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
