"""Kernels: how many pages the latent window kernel folds a softmax
update. Every `mixed` step record of a latent family (`glm_moe_dsa`,
`dots3_note`, `deepseek_v2`) carries `window_pages`, the pages a query
tile of `cake_mla_window_attn` / `cake_swa_window_attn` walks over the
step's dispatches and the layers that run the kernel (the live pages
of the window's row, a sliding layer's whole ring; counted on the host
from the window's last position as the kernel counts its trips), and
`window_folds`, the softmax updates those pages take: one accumulator
pass, one max / exp / sum a block of pages. Their ratio over the
window's mixed steps is how far the block engages: 1.0 is a kernel
that updates at every page, the block's size the most, and a short
ring or a short row reads under it (its last block is part empty). A
program whose mixed records have no such fields reports nothing."""

KERNELS = "kernels"

METRICS = [{"name": "mla_window_pages_per_fold", "unit": "pages",
            "layer": KERNELS, "moves": "out_tok_s",
            "source": "program_counter"}]


def read(run):
    steps = [s for s in run.get("steps", [])
             if s["kind"] == "mixed" and s.get("window_folds")]
    if not steps:
        return {}
    return {"mla_window_pages_per_fold":
            sum(s["window_pages"] for s in steps)
            / sum(s["window_folds"] for s in steps)}
