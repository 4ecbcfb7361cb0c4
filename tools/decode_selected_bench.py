#!/usr/bin/env python3
"""Time a selecting model's single-token rows alone on the chip, both
forms: the selected keys GATHERED out of the pools, and the row's own
pages WALKED under the selection's mask.

    chiprun -- python tools/decode_selected_bench.py [--out chiprun_out/decode_selected_bench.json]
    JAX_PLATFORMS=cpu python tools/decode_selected_bench.py --rehearse   # tiny, interpreted

Keye-VL-2.0's shape in `keyevl2.longctx-closed`: 8 rows, 32 query heads
over 4 K/V heads of 128, bfloat16 pages of 128 tokens, a table of 260
pages (33,280 keys), top 2,048; every row at the same length, 4k / 8k /
16k / 32k and the table's end; the index scores an input (float32, half
of them exactly 0.0, the relu's floor: ties at the k-th value). N calls
inside ONE program over a two-layer pool, `layer = i % 2` and the
positions through the loop's carry, so nothing is lifted out (a loop of
dispatches would read the host: PERF.md section 6, PR 34); the best of 5
runs a case:

  * `gather`: the form `models/moe/keye_vl2.py` served until PR 66,
    kept HERE as it was: `lax.top_k` over the table's width, the
    indices sorted ascending, the page look-up, two XLA gathers of
    [rows * 2,048, KV * hd] laid out as a pool of their own, and
    `cake_decode_attn` over that pool;
  * `mask`: `cake_dsa_select` at one tile of 8 queries (the rows'
    positions, `last_pos` their greatest) and `cake_decode_attn(
    selected=)` over the layer's own pools and the rows' own table;
  * `mask_select` / `mask_attend`: the mask form's two kernels, each
    alone; `walk`: `cake_decode_attn` with no selection (what the walk
    costs before the mask).

Before the timing, ONE call of each form on the same inputs: the
selected sets compared exactly, the outputs to bfloat16's rounding.
Prints one JSON line with the microseconds a call and `crossover_keys`
(where the mask form's time passes the gather form's, interpolated
between two lengths; null where it never does). Exits 1 where the two
forms differ. Not imported by the package; no cell of the benchmark
runs it.
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
    ap.add_argument("--calls", type=int, default=16)
    ap.add_argument("--lengths", help="keys a row, comma-separated")
    ap.add_argument("--forms", default="gather,mask,mask_select,walk,"
                    "mask_attend", help="what to time, comma-separated")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny shapes, for a run with no chip")
    ap.add_argument("--out", help="also write the line to this file")
    a = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from cake_tpu.models.llama import paged
    from cake_tpu.ops import mla_attention as mla

    if a.rehearse:
        B, H, KV, hd, P, max_pages, K = 4, 4, 2, 16, 8, 16, 24
        lengths, dtype, attn = (20, 60, 128), jnp.float32, "pallas"
    else:
        B, H, KV, hd, P, max_pages, K = 8, 32, 4, 128, 128, 260, 2048
        lengths, dtype, attn = (4096, 8192, 16384, 32768, 33280), \
            jnp.bfloat16, "pallas"
    if a.lengths:
        lengths = tuple(int(n) for n in a.lengths.split(","))
    S, N = max_pages * P, a.calls
    F32 = jnp.float32
    key = jax.random.PRNGKey(0)
    kq, kk, kv_, ks, kz = jax.random.split(key, 5)
    pool_shape = (2, 1 + B * max_pages, P, KV * hd)
    pool_k = jax.random.normal(kk, pool_shape, dtype)
    pool_v = jax.random.normal(kv_, pool_shape, dtype)
    q = jax.random.normal(kq, (B, H, hd), dtype)
    scores = jnp.where(jax.random.bernoulli(kz, 0.5, (B, S)),
                       jax.random.normal(ks, (B, S), F32), 0.0)
    span = jnp.arange(S)[None, :]

    def select_gather(scores, pos):
        """keye_vl2.select_keys' single-token half until PR 66."""
        rows = jnp.where(span <= pos[:, None], scores, -jnp.inf)
        _, idx = lax.top_k(rows, K)
        n_valid = jnp.minimum(pos + 1, K).astype(jnp.int32)
        idx = jnp.sort(jnp.where(jnp.arange(K)[None, :] < n_valid[:, None],
                                 idx, S - 1), axis=1)
        return idx.astype(jnp.int32), n_valid

    def attend_gather(q, pool_k, pool_v, layer, table, idx, n_valid):
        """keye_vl2.attend_rows until PR 66."""
        width = pool_k.shape[3]
        Kp = -(-K // P) * P
        rows = jnp.arange(B)[:, None]
        pages = jnp.maximum(table[rows, idx // P], 0)
        at = idx % P

        def gathered(pool):
            g = pool.at[layer, pages, at].get(mode="promise_in_bounds")
            g = jnp.pad(g, ((0, 0), (0, Kp - K), (0, 0)))
            return g.reshape(1, B * Kp // P, P, width)

        own = jnp.arange(B * Kp // P, dtype=jnp.int32).reshape(B, Kp // P)
        return paged.paged_attention(
            q[:, None], gathered(pool_k), gathered(pool_v), jnp.int32(0),
            own, n_valid - 1, impl=attn)[:, 0]

    def select_mask(scores, pos):
        return mla.select_window(scores, pos, jnp.max(pos), K)

    def attend_mask(q, pool_k, pool_v, layer, table, pos, mask):
        return paged.paged_attention(
            q[:, None], pool_k, pool_v, layer, table, pos, impl=attn,
            selected=mask.astype(F32).reshape(B, max_pages, P))[:, 0]

    # (the pools, the query and the scores are ARGUMENTS of every jitted
    # function: closed over, a program would carry 2 GB of constants)
    data = (q, pool_k, pool_v, scores)
    forms = {
        "gather": lambda layer, table, pos, q, pool_k, pool_v, scores:
            attend_gather(q, pool_k, pool_v, layer, table,
                          *select_gather(scores, pos)),
        "mask": lambda layer, table, pos, q, pool_k, pool_v, scores:
            attend_mask(q, pool_k, pool_v, layer, table, pos,
                        select_mask(scores, pos)),
        "mask_select": lambda layer, table, pos, q, pool_k, pool_v, scores:
            jnp.sum(select_mask(scores, pos), dtype=F32),
        "walk": lambda layer, table, pos, q, pool_k, pool_v, scores:
            paged.paged_attention(q[:, None], pool_k, pool_v, layer, table,
                                  pos, impl=attn)[:, 0],
        # the mask handed over ready: the kernel that walks under it alone
        "mask_attend": lambda layer, table, pos, q, pool_k, pool_v, scores,
            mask: attend_mask(q, pool_k, pool_v, layer, table, pos, mask),
    }

    def timed(form, table, pos, *fixed):
        def run(table, pos, *fixed):
            def body(i, acc):
                # never true, and the compiler cannot know: the call stays
                moved = pos - (acc > 1e30).astype(jnp.int32)
                return acc + jnp.sum(form(i % 2, table, moved, *fixed)
                                     .astype(F32))
            return lax.fori_loop(0, N, body, F32(0))

        run = jax.jit(run)
        jax.block_until_ready(run(table, pos, *fixed))
        best = float("inf")
        for _ in range(5):
            t = time.perf_counter()
            jax.block_until_ready(run(table, pos, *fixed))
            best = min(best, time.perf_counter() - t)
        return round(best / N * 1e6, 1)

    out = {"device": jax.devices()[0].device_kind, "rows": B, "heads": H,
           "kv_heads": KV, "head_dim": hd, "page": P, "table_keys": S,
           "topk": K, "calls": N, "cases": []}
    ok = True
    for n in lengths:
        pos = jnp.full((B,), n - 1, jnp.int32)
        live = -(-n // P)
        table = np.full((B, max_pages), -1, np.int32)
        for b in range(B):
            table[b, :live] = 1 + b * max_pages + np.arange(live)
        table = jnp.asarray(table)
        idx, n_valid = jax.jit(select_gather)(scores, pos)
        mask = jax.jit(select_mask)(scores, pos)
        sets = np.zeros((B, S), bool)
        for b in range(B):
            sets[b, np.asarray(idx[b, :int(n_valid[b])])] = True
        same_sets = bool(np.array_equal(sets, np.asarray(mask)))
        one = jnp.int32(1)
        got = jax.jit(forms["mask"])(one, table, pos, *data).astype(F32)
        want = jax.jit(forms["gather"])(one, table, pos, *data).astype(F32)
        err = float(jnp.max(jnp.abs(got - want)))
        case = {"keys": n, "pages": live, "same_sets": same_sets,
                "max_abs_diff": round(err, 6)}
        ok = ok and same_sets and err < (1e-4 if a.rehearse else 2e-2)
        for name in a.forms.split(","):
            more = (mask,) if name == "mask_attend" else ()
            case[name + "_us"] = timed(forms[name], table, pos, *data, *more)
        out["cases"].append(case)
        report(out, a.out)     # (a call cut short keeps its cases)
    # where the walk's time passes the gather's, between two lengths
    out["crossover_keys"] = None
    cases = [c for c in out["cases"] if "mask_us" in c and "gather_us" in c]
    for lo, hi in zip(cases, cases[1:]):
        d0, d1 = (c["mask_us"] - c["gather_us"] for c in (lo, hi))
        if d0 <= 0 < d1:
            out["crossover_keys"] = round(
                lo["keys"] + (hi["keys"] - lo["keys"]) * -d0 / (d1 - d0))
    if cases and cases[0]["mask_us"] > cases[0]["gather_us"]:
        out["crossover_keys"] = 0
    print(report(out, a.out))
    return 0 if ok else 1


def report(out: dict, path) -> str:
    line = json.dumps(out)
    if path:
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        with open(path, "w") as f:
            f.write(line + "\n")
    return line


if __name__ == "__main__":
    sys.exit(main())
