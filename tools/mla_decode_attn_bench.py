#!/usr/bin/env python3
"""Time `cake_mla_decode_attn` alone on the chip at the two cells' shapes.

    chiprun -- python tools/mla_decode_attn_bench.py [--out chiprun_out/mla_decode_attn_bench.json]
    chiprun -- python tools/mla_decode_attn_bench.py --tree _scratch/parent   # another tree's kernel
    JAX_PLATFORMS=cpu python tools/mla_decode_attn_bench.py --rehearse       # tiny, interpreted

One call a case, as a cell's step programs make it in every latent
layer (bfloat16 pool, one layer of a two-layer pool; rows, heads, row
and value widths, page and table width from the cell's own
`config.json` and `cell.json`), N calls inside ONE program (a loop of
dispatches would read the host, PERF.md section 6, PR 34; the layer
alternates and the result passes through the loop's carry, so nothing
is lifted out of it), the best of 5 runs, at F = 1 / 2 / 4 pages a
softmax update (`mla.decode_block` replaced; a tree without it is timed
as it is, F = 1):

  * `dsv2`: deepseek-v2-int8-share8, 30 of 32 rows decoding at contexts
    of 3,969-4,608 (33-36 live pages a row, as `dsv2.code-closed`'s
    `mla_keys_per_decode_row` 4,168), 128 heads, a table of 40 pages;
  * `ling3`: ling-3.0-flash-int8-share4, 32 rows, three in four at
    2,000-3,072 and the rest at 8,192-9,216 (the cell's two classes;
    its counter reads 4,385 keys a row), 32 heads, a table of 76.

Beside `us` a call: `us_a_page` (a call over the pages its rows walk:
at 128 heads the unit of 128 query rows x one page), `pages` / `folds`
(`mla.pages_walk`), `roofline_pct` (`benchmarks/harness/
mla_dense_roofline.py`'s need for the keys the rows attend, the greater
of its operations at the bf16 peak and its bytes at the HBM rate, over
the time: what `mla_decode_attn_roofline` reads in a cell), and `err`,
the result's greatest distance from F = 1's (the tests' bfloat16 limit,
2e-2; F = 1 itself against the XLA fold). Prints one JSON line; exits 1
where a case is off. Not imported by the package; no cell of the
benchmark runs it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIGS = os.path.join(ROOT, "benchmarks", "configs")
LIMIT = 2e-2


def cell_case(directory: str, idle: int, contexts) -> dict:
    """A cell's call: its files' sizes, and its rows' positions drawn
    from `contexts` [(share of the rows, lo, hi)]."""
    import numpy as np
    with open(os.path.join(CONFIGS, directory, "config.json")) as f:
        config = json.load(f)
    with open(os.path.join(CONFIGS, directory, "cell.json")) as f:
        args = json.load(f)["server_args"]
    rows, page = args["max-slots"], args["kv-page-size"]
    rng = np.random.default_rng(rows)
    pos = np.concatenate([
        rng.integers(lo, hi, round(share * (rows - idle)))
        for share, lo, hi in contexts])
    pos = np.concatenate([pos, np.full(rows - len(pos), -1)])
    width = config["kv_lora_rank"] + config["qk_rope_head_dim"]
    return dict(H=config["num_attention_heads"], r=config["kv_lora_rank"],
                W=-(-width // 128) * 128, page=page,
                table=args["max-seq-len"] // page, pos=rng.permutation(pos),
                config=config)


def cases(rehearse: bool) -> dict:
    if rehearse:
        import numpy as np
        return {"tiny": dict(H=4, r=16, W=24, page=8, table=8, config=None,
                             pos=np.asarray([20, 36, -1, 15, 63, 7]))}
    return {"dsv2": cell_case("deepseek-v2-int8-share8", 2,
                              [(1.0, 3969, 4608)]),
            "ling3": cell_case("ling-3.0-flash-int8-share4", 0,
                               [(0.75, 2000, 3072), (0.25, 8192, 9216)])}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--calls", type=int, default=32)
    ap.add_argument("--cases", help="comma-separated names (all)")
    ap.add_argument("--blocks", default="1,2,4",
                    help="pages a softmax update, comma-separated")
    ap.add_argument("--tree", help="import cake_tpu from this directory")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny shapes, for a run with no chip")
    ap.add_argument("--out", help="also write the line to this file")
    a = ap.parse_args(argv)
    sys.path.insert(0, os.path.abspath(a.tree) if a.tree else ROOT)
    sys.path.insert(0, os.path.join(ROOT, "benchmarks"))

    import numpy as np

    import jax
    import jax.numpy as jnp
    from jax import lax

    from cake_tpu.ops import mla_attention as mla
    from harness import mla_dense_roofline as roof
    from harness.peaks import PEAKS

    blocked = hasattr(mla, "decode_block")
    rule = getattr(mla, "decode_block", None)
    blocks = [int(f) for f in a.blocks.split(",")] if blocked else [1]
    chosen = cases(a.rehearse)
    if a.cases:
        chosen = {n: chosen[n] for n in a.cases.split(",")}
    N = a.calls
    kind = jax.devices()[0].device_kind
    peak = PEAKS.get(kind)      # none on a CPU: no roofline_pct there
    dtype = jnp.float32 if a.rehearse else jnp.bfloat16
    out = {"device": kind, "tree": a.tree or ".", "calls": N, "cases": []}
    ok = True
    try:
        for name, c in chosen.items():
            H, r, W, page, T = c["H"], c["r"], c["W"], c["page"], c["table"]
            B = len(c["pos"])
            pos = jnp.asarray(c["pos"], jnp.int32)
            # a row's pages are its own, in order
            table = jnp.arange(B * T, dtype=jnp.int32).reshape(B, T)
            keys = jax.random.split(jax.random.PRNGKey(B + T), 2)
            q = jax.random.normal(keys[0], (B, H, W),
                                  jnp.float32).astype(dtype)
            pool = jax.random.normal(keys[1], (2, B * T, page, W),
                                     jnp.float32).astype(dtype)
            scale = W ** -0.5
            attended = int(np.sum(np.maximum(c["pos"] + 1, 0)))
            first = None
            for F in blocks:
                if blocked:
                    mla.decode_block = lambda *s, F=F: F

                def call(q, pool, layer, table, pos):
                    return mla._pages_pallas.__wrapped__(
                        q, pool, layer, table, pos, r=r, scale=scale,
                        interpret=bool(a.rehearse))

                def run(q, pool, table, pos):
                    def body(i, seen):
                        # seen stays 0: the compiler cannot know, so the
                        # call stays
                        o = call(q, pool, i % 2, table,
                                 pos + jnp.minimum(seen, 0))
                        return seen + (jnp.abs(o[0, 0, 0]) > 1e30).astype(
                            jnp.int32)
                    return lax.fori_loop(0, N, body, jnp.int32(0))

                got = np.asarray(jax.jit(call)(q, pool, jnp.int32(1), table,
                                               pos).astype(jnp.float32))
                if first is None:
                    first = got
                    want = np.asarray(jax.jit(
                        lambda *x: mla._pages_fold(*x, r, scale))(
                            q, pool, jnp.int32(1), table, pos
                        ).astype(jnp.float32))
                else:
                    want = first
                walked = [mla.pages_walk(int(p), page, T, F) if blocked
                          else (min(max(int(p) // page + 1, 0), T),) * 2
                          for p in c["pos"]]
                case = {"case": name, "block": F, "rows": B, "heads": H,
                        "pages": sum(w[0] for w in walked),
                        "folds": sum(w[1] for w in walked),
                        "err": round(float(np.max(np.abs(got - want))), 5),
                        "finite": bool(np.isfinite(got).all())}
                ok = ok and case["finite"] and case["err"] <= (
                    1e-4 if a.rehearse else LIMIT)
                fn = jax.jit(run)
                jax.block_until_ready(fn(q, pool, table, pos))
                best = float("inf")
                for _ in range(5):
                    t = time.perf_counter()
                    jax.block_until_ready(fn(q, pool, table, pos))
                    best = min(best, time.perf_counter() - t)
                case["us"] = round(best / N * 1e6, 1)
                case["us_a_page"] = round(best / N * 1e6 / case["pages"], 4)
                if peak and c["config"]:
                    need = roof.least_s(c["config"], attended, attended, peak)
                    case["roofline_pct"] = round(100 * need / (best / N), 1)
                out["cases"].append(case)
    finally:
        if blocked:
            mla.decode_block = rule
    line = json.dumps(out)
    print(line)
    if a.out:
        os.makedirs(os.path.dirname(os.path.abspath(a.out)), exist_ok=True)
        with open(a.out, "w") as f:
            f.write(line + "\n")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
