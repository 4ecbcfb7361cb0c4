#!/usr/bin/env python3
"""Time `cake_mixed_attn` alone on the chip at the cells' call shapes.

    chiprun -- python tools/mixed_attn_bench.py [--out chiprun_out/mixed_attn_bench.json]
    chiprun -- python tools/mixed_attn_bench.py --tree _scratch/parent   # another tree's kernel
    JAX_PLATFORMS=cpu python tools/mixed_attn_bench.py --rehearse       # tiny, interpreted
    JAX_PLATFORMS=cpu python tools/mixed_attn_bench.py --compile-only   # Mosaic, no chip

One call a case, as a cell's step program makes it (bfloat16 pool,
pages of 128, one layer of a two-layer pool), N calls inside ONE
program (a loop of dispatches would read the host, PERF.md section 6,
PR 34; the layer alternates and the result passes through the loop's
carry, so nothing is lifted out of it), the best of 5 runs a case:

  * `keyevl2-<ctx>` / `-sel`: 4 entries of 128 queries, 32 heads over 4
    K/V heads, a window that ends at 2k / 8k / 16k / 32k of a 260-page
    table, without and with `selected=` (every key selected: the
    operand's cost, not a mask's);
  * `mistral7b-1w` / `-2w`, `olmoe7b-1w` / `-2w`: 16 rows of a 16-page
    table, 15 / 14 decode rows at ragged positions and 1 / 2 prefilling
    a window of 128;
  * `idle16`: Mistral's call with every row idle;
  * `kexaone-full` / `-band`: 8 entries of 64 queries, 64 heads over 8
    K/V heads, over the full layers' 76-page table at 4.5k of context
    and banded (128) over the ring of 6 entries;
  * `zaya1`: 32 rows, 8 heads over 2 K/V heads, 30 decode rows and two
    windows of 128.

Beside the time: `pages` / `folds` the kernel walks (`rpa.mixed_walk`
at `rpa.mixed_block`'s pages a fold; the table's cells for a tree whose
kernel steps through `rows x max_pages`), `mxu_pct`, the two products'
operations over the (query, key) pairs the folds COMPUTE at the device's
bfloat16 peak over the time, and `sha`, a digest of the real queries'
result bits (two trees that agree bit for bit print the same). `--block
F` puts F pages a fold in place of `mixed_block`'s, `--depth D` D ring
slots in place of `decode_ring_depth`'s. Before the timing ONE call is
compared with the fold reference on the real columns (2e-2, bfloat16).
Prints one JSON line; exits 1 where a case is off. Not imported by the
package; no cell of the benchmark runs it.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
P = 128
BF16_PEAK = {"TPU v5 lite": 197e12}


def _decode_rows(n, seed, lo, hi):
    import numpy as np
    at = np.random.default_rng(seed).integers(lo, hi, n)
    return [(int(p), 1) for p in at]


def cases(rehearse: bool):
    """name -> dict(H, KV, hd, C, table, rows [(first position, q_len)],
    window, selecting)."""
    if rehearse:
        return {
            "tiny-mixed": dict(H=4, KV=2, hd=16, C=8, table=5, page=8,
                               rows=[(19, 1), (0, 0), (8, 8), (3, 1)]),
            "tiny-sel": dict(H=4, KV=2, hd=16, C=8, table=8, page=8,
                             rows=[(40, 8), (48, 8)], selecting=True),
            "tiny-band": dict(H=4, KV=2, hd=16, C=8, table=3, page=8,
                              rows=[(40, 8), (48, 8)], window=8),
        }
    out = {}
    keye = dict(H=32, KV=4, hd=128, C=128, table=260)
    for ctx in (2048, 8192, 16384, 32768):
        rows = [(ctx - 512 + 128 * e, 128) for e in range(4)]
        out[f"keyevl2-{ctx // 1024}k"] = dict(keye, rows=rows)
        out[f"keyevl2-{ctx // 1024}k-sel"] = dict(keye, rows=rows,
                                                  selecting=True)
    for name, H, KV in (("mistral7b", 32, 8), ("olmoe7b", 16, 16)):
        for w in (1, 2):
            rows = _decode_rows(16 - w, 7, 150, 1900) + [(640, 128),
                                                         (256, 128)][:w]
            out[f"{name}-{w}w"] = dict(H=H, KV=KV, hd=128, C=128, table=16,
                                       rows=rows)
    out["idle16"] = dict(H=32, KV=8, hd=128, C=128, table=16,
                         rows=[(0, 0)] * 16)
    kex = dict(H=64, KV=8, hd=128, C=64)
    out["kexaone-full"] = dict(kex, table=76,
                               rows=[(4096 + 64 * e, 64) for e in range(8)])
    out["kexaone-band"] = dict(kex, table=6, window=128,
                               rows=[(4096 + 64 * e, 64) for e in range(8)])
    out["zaya1"] = dict(H=8, KV=2, hd=128, C=128, table=40,
                        rows=(_decode_rows(30, 11, 300, 4500)
                              + [(1024, 128), (2560, 128)]))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--calls", type=int, default=32)
    ap.add_argument("--cases", help="comma-separated names (all)")
    ap.add_argument("--tree", help="import cake_tpu from this directory")
    ap.add_argument("--block", type=int, help="pages a fold")
    ap.add_argument("--depth", type=int, help="ring slots")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny shapes, for a run with no chip")
    ap.add_argument("--compile-only", action="store_true",
                    help="compile each case for a described v5e; no run")
    ap.add_argument("--out", help="also write the line to this file")
    a = ap.parse_args(argv)
    sys.path.insert(0, os.path.abspath(a.tree) if a.tree else ROOT)

    import numpy as np

    import jax
    import jax.numpy as jnp
    from jax import lax

    from cake_tpu.models.llama.paged import paged_attention_mixed
    from cake_tpu.ops import ragged_paged_attention as rpa

    walks = hasattr(rpa, "mixed_block")
    if a.block:
        rpa.mixed_block = lambda *s, **kw: a.block
    if a.depth:
        rpa.decode_ring_depth = lambda nbytes: a.depth
    sharding = None
    if a.compile_only:
        sys.path.insert(0, os.path.join(ROOT, "tools"))
        import step_hlo
        sharding = step_hlo.describe_v5e()
        rpa._on_tpu = lambda: True
    chosen = cases(a.rehearse)
    if a.cases:
        chosen = {n: chosen[n] for n in a.cases.split(",")}
    N = a.calls
    kind = jax.devices()[0].device_kind
    dtype = jnp.float32 if a.rehearse else jnp.bfloat16
    out = {"device": "described v5e" if a.compile_only else kind,
           "tree": a.tree or ".", "calls": N, "cases": []}
    ok = True
    for name, c in chosen.items():
        H, KV, hd, C, T = c["H"], c["KV"], c["hd"], c["C"], c["table"]
        page = c.get("page", P)
        window, selecting = c.get("window"), c.get("selecting", False)
        rows = c["rows"]
        B = len(rows)
        pos = jnp.asarray([p for p, _ in rows], jnp.int32)
        qlen = jnp.asarray([n for _, n in rows], jnp.int32)
        # a row's pages are its own, in order; entries of one window
        # (Keye, K-EXAONE) share a table row
        shared = name.startswith(("keyevl2", "kexaone", "tiny-sel",
                                  "tiny-band"))
        table = np.arange(T if shared else B * T, dtype=np.int32)
        table = (np.broadcast_to(table, (B, T)) if shared
                 else table.reshape(B, T))
        n_pages = int(table.max()) + 1
        kw = dict(window=window, interpret=bool(a.rehearse))
        if a.compile_only:
            kw["interpret"] = False

        def call(q, pk, pv, layer, table, pos, qlen, selected):
            more = {"selected": selected} if selecting else {}
            return rpa.ragged_paged_attention_mixed(
                q, pk, pv, layer, table, pos, qlen, **kw, **more)

        def run(q, pk, pv, table, pos, qlen, selected):
            def body(i, seen):
                # seen stays 0: the compiler cannot know, so the call stays
                o = call(q, pk, pv, i % 2, table, pos + jnp.minimum(seen, 0),
                         qlen, selected)
                return seen + (jnp.abs(o[0, 0, 0, 0]) > 1e30).astype(
                    jnp.int32)
            return lax.fori_loop(0, N, body, jnp.int32(0))

        shapes = [((B, C, H, hd), dtype), ((2, n_pages, page, KV * hd), dtype),
                  ((2, n_pages, page, KV * hd), dtype)]
        sel_shape = (B, T, C, page) if selecting else (1,)
        block = (rpa.mixed_block(page, H, KV, hd, C, T,
                                 jnp.dtype(dtype).itemsize,
                                 jnp.dtype(dtype).itemsize,
                                 selecting=selecting) if walks else 1)
        walked = [rpa.mixed_walk(p, n, page, T, block, window) if walks
                  else (T, T) for p, n in rows]
        case = {"case": name, "rows": B, "block": block,
                "pages": sum(w[0] for w in walked),
                "folds": sum(w[1] for w in walked), "table": B * T}
        if a.compile_only:
            sds = lambda shape, dt: jax.ShapeDtypeStruct(
                shape, dt, sharding=sharding)
            try:
                with jax.default_matmul_precision("default"):
                    compiled = jax.jit(run).lower(
                        *(sds(*s) for s in shapes), sds((B, T), jnp.int32),
                        sds((B,), jnp.int32), sds((B,), jnp.int32),
                        sds(sel_shape, jnp.float32)).compile()
                case["compiles"] = "cake_mixed_attn" in compiled.as_text()
            except Exception as e:  # the compiler's refusal, by its words
                case["compiles"] = False
                case["refused"] = str(e)[-400:]
            ok = ok and case["compiles"]
            out["cases"].append(case)
            continue
        keys = jax.random.split(jax.random.PRNGKey(B * C + T), 3)
        q, pk, pv = (jax.random.normal(k, s, jnp.float32).astype(dt)
                     for k, (s, dt) in zip(keys, shapes))
        selected = jnp.ones(sel_shape, jnp.float32)
        table = jnp.asarray(table)
        got = np.asarray(jax.jit(call)(q, pk, pv, 1, table, pos, qlen,
                                       selected).astype(jnp.float32))
        want = np.asarray(jax.jit(
            lambda *x: paged_attention_mixed(*x, window=window))(
                q, pk, pv, 1, table, pos, qlen).astype(jnp.float32))
        real = [got[b, :n] for b, (_p, n) in enumerate(rows)]
        err = max([float(np.max(np.abs(got[b, :n] - want[b, :n])))
                   for b, (_p, n) in enumerate(rows) if n] or [0.0])
        case["max_err"] = round(err, 5)
        case["finite"] = bool(np.isfinite(got).all())
        case["sha"] = hashlib.sha256(
            b"".join(r.tobytes() for r in real)).hexdigest()[:16]
        ok = ok and case["finite"] and err <= (2e-2 if not a.rehearse
                                               else 1e-4)
        fn = jax.jit(run)
        args = (q, pk, pv, table, pos, qlen, selected)
        jax.block_until_ready(fn(*args))
        best = float("inf")
        for _ in range(5):
            t = time.perf_counter()
            jax.block_until_ready(fn(*args))
            best = min(best, time.perf_counter() - t)
        case["us"] = round(best / N * 1e6, 1)
        # pairs the folds compute: a folded row's queries x the keys of
        # the pages it walks (whole blocks), two products of hd each
        Tq = min(C, rpa.MIXED_Q_TILE)
        computed = sum((C if n > Tq else Tq) * f * block * page
                       for (_p, n), (_w, f) in zip(rows, walked))
        peak = BF16_PEAK.get(kind)
        if peak and walks:
            case["mxu_pct"] = round(
                100 * computed * H * hd * 4 / peak / (best / N), 1)
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
