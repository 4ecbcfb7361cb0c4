"""Kernels: how much of its rows' windows the mixed attention kernel
folds. Every `mixed` step record of a paged dense or sparse engine
carries `attn_q_tiles`, the query tiles `cake_mixed_attn` folds a live
page into for the step's active rows (one for a row whose real queries
lie in its first tile, a decode row; the window's for any other:
`ops/ragged_paged_attention.mixed_q_tiles`, counted on the host from
each row's q_len), and `attn_q_tiles_window`, the tiles of those rows'
whole windows, which is what a kernel that ignored q_len would fold.
Their ratio over the window's mixed steps is the share of the padded
window the kernel still computes on. A program whose mixed records
have no such fields reports nothing."""

KERNELS = "kernels"

METRICS = [{"name": "mixed_attn_q_tiles_folded_pct", "unit": "%",
            "layer": KERNELS, "moves": "ttft_mean_ms",
            "source": "program_counter"}]


def read(run):
    steps = [s for s in run.get("steps", [])
             if s["kind"] == "mixed" and s.get("attn_q_tiles_window")]
    if not steps:
        return {}
    return {"mixed_attn_q_tiles_folded_pct":
            100.0 * sum(s["attn_q_tiles"] for s in steps)
            / sum(s["attn_q_tiles_window"] for s in steps)}
