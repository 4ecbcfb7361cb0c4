#!/usr/bin/env python3
"""The grid `cake_moe_gmm` walks in a cell's step programs, from shapes.

A call of `ops/moe.grouped_matmul` is a Pallas grid of (output columns /
`tn`, visits): its time follows its count of grid steps where the blocks
are small (PERF.md §6, PR 49). This tool reads a cell's directory (its
`config.json`, and its `cell.json` for the slots and the mixed step's
window), takes the expert leaves' shapes from the family's own
`init_params` (abstractly: nothing is drawn) and the packed sizes from
`paged.mixed_token_buckets`, and prints, for a decode step and for each
packed size of the mixed step, every projection's tile and grid beside
what the rule before PR 49 (the largest of 512 / 256 / 128 that divides
the output width) gave. No device, no time: counts.

    JAX_PLATFORMS=cpu python tools/moe_grid.py benchmarks/configs/*/
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from functools import partial
from typing import List, NamedTuple


class Call(NamedTuple):
    """One `grouped_matmul` call of a sparse layer: `leaf` [E, K, N] at
    `w_bytes` a weight over `n_pairs` (token, expert) pairs of a `kind`
    step of `n_tokens` positions; `layers` sparse layers run it."""
    kind: str
    n_tokens: int
    n_pairs: int
    leaf: str
    n_experts: int
    K: int
    N: int
    w_bytes: int
    scaled: bool
    layers: int


def tile_before(n_out: int) -> int:
    """`ops/moe._out_tile` as it stood before PR 49."""
    return next((t for t in (512, 256, 128) if n_out % t == 0), n_out)


def cell_calls(model_dir: str) -> List[Call]:
    """The expert matmuls of the cell's decode step and of each packed
    size of its mixed step, on the int8 weights the cells serve; [] for
    a dense model."""
    import jax
    import jax.numpy as jnp

    from cake_tpu.models.llama.config import load_config
    from cake_tpu.models.llama.paged import mixed_token_buckets
    from cake_tpu.ops.moe import EXPERT_LEAVES
    from cake_tpu.ops.quant import QTensor

    config = load_config(model_dir)
    if not getattr(config, "is_moe", False):
        return []
    from cake_tpu.models.moe.params import init_params

    slots, width = 16, 128
    cell = os.path.join(model_dir, "cell.json")
    if os.path.exists(cell):
        with open(cell) as f:
            cell = json.load(f)
        slots = cell["server_args"].get("max-slots", slots)
        width = cell.get("shape", {}).get("mixed_width", width)
    blocks = jax.eval_shape(
        partial(init_params, config, dtype=jnp.bfloat16, bits=8),
        jax.random.PRNGKey(0))["blocks"]
    k = config.num_experts_per_tok
    steps = [("decode", slots)] + [
        ("mixed", t) for t in mixed_token_buckets(
            slots, width, prefill_rows=config.family.prefill_rows)]
    calls = []
    for kind, n_tokens in steps:
        for leaf in EXPERT_LEAVES:
            if leaf not in blocks:
                continue
            w = blocks[leaf]
            scaled = isinstance(w, QTensor)
            q = w.q if scaled else w
            L, E, K, N = q.shape
            calls.append(Call(kind, n_tokens, n_tokens * k, leaf, E, K, N,
                              q.dtype.itemsize, scaled, L))
    return calls


def rows(model_dir: str) -> List[dict]:
    """One dict a call: the grid now (`ops/moe.gmm_grid`) and before."""
    from cake_tpu.ops.moe import gmm_grid

    out = []
    for c in cell_calls(model_dir):
        g = gmm_grid(c.n_pairs, c.n_experts, c.K, c.N, 2, c.w_bytes,
                     c.scaled)
        tn0 = tile_before(c.N)
        out.append(dict(c._asdict(), tn=g.tn, column_tiles=g.column_tiles,
                        visits=g.visits, steps=g.steps, tn_before=tn0,
                        steps_before=c.N // tn0 * g.visits))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("model_dirs", nargs="+")
    a = ap.parse_args(argv)
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    print("| cell directory | step (tokens) | E held | visits | "
          "projections K→N: `tn` before → now | steps a layer before → "
          "now | sparse layers |\n| --- | --- | --- | --- | --- | --- | --- |")
    for d in a.model_dirs:
        by_step = {}
        for r in rows(d):
            by_step.setdefault((r["kind"], r["n_tokens"]), []).append(r)
        for (kind, n_tokens), rs in by_step.items():
            proj = ", ".join(
                f"{r['leaf'][3:]} {r['K']}→{r['N']}: {r['tn_before']} → "
                f"{r['tn']}" for r in rs)
            print(f"| `{os.path.basename(os.path.normpath(d))}` | {kind} "
                  f"({n_tokens}) | {rs[0]['n_experts']} | {rs[0]['visits']} "
                  f"| {proj} | {sum(r['steps_before'] for r in rs):,} → "
                  f"{sum(r['steps'] for r in rs):,} | {rs[0]['layers']} |")
    return 0


if __name__ == "__main__":
    sys.exit(main())
