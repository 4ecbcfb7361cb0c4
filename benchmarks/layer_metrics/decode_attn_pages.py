"""Kernels: how much of the page table holds work for the decode
attention kernel. Every `decode` step record of a paged engine whose
decode rows go through `cake_decode_attn` carries `attn_pages`, the
KV pages the kernel streams a layer for the step's active rows
(position // page + 1 for each, counted on the host from the positions
it dispatches), and `attn_pages_table`, slots x pages a slot, which is
what a kernel that stepped through the whole table would visit. Their
ratio over the window's decode steps is the live share of the table:
a kernel whose time follows its pages takes that share of what one
that follows its table takes. A program whose decode records have no
such fields reports nothing."""

KERNELS = "kernels"

METRICS = [{"name": "decode_attn_pages_live_pct", "unit": "%",
            "layer": KERNELS, "moves": "out_tok_s",
            "source": "program_counter"}]


def read(run):
    steps = [s for s in run.get("steps", [])
             if s["kind"] == "decode" and s.get("attn_pages_table")]
    if not steps:
        return {}
    return {"decode_attn_pages_live_pct":
            100.0 * sum(s["attn_pages"] for s in steps)
            / sum(s["attn_pages_table"] for s in steps)}
