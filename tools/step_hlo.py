#!/usr/bin/env python3
"""Ask the TPU compiler about a step program, with no chip.

libtpu is installed in the sandbox, so XLA:TPU compiles ahead of time for
a v5e that is described and not attached. This tool builds a step
program's arguments as shapes (`ShapeDtypeStruct`s, the weights as
`QTensor` leaves), compiles the program and reads the optimised HLO for
the int8 weights it MATERIALISES: a weight that a dot streams out of the
stacked `[L, in, out]` leaf is sliced inside that dot's fusion and never
appears as an `s8[...]` result outside one; a `copy` or a stand-alone
slice with an `s8[...]` result is a layer's weight written to memory
again, every layer, every step (PERF.md §6, PR 43: `wq` and `wk`,
2.7 ms of a 13.5 ms decode step).

Nothing runs, so this says nothing about results or times.

    JAX_PLATFORMS=cpu python tools/step_hlo.py benchmarks/configs/mistral-7b-int8
    JAX_PLATFORMS=cpu python tools/step_hlo.py benchmarks/configs/olmoe-1b-7b-int8 --layers 2 --hlo /root/scratch/olmoe

tests/test_step_hlo.py holds the dense step programs to "none" in tier-1.
"""

from __future__ import annotations

import argparse
import os
import re
import sys
from functools import partial
from typing import List, NamedTuple, Optional

# an s8 result outside a fusion is a weight in memory again unless the
# instruction only names or forwards a buffer, or is the compiler's own
# prefetch into faster memory (asynchronous, the layout as it was: a
# two-layer stack is small enough to be fetched whole)
_FORWARDS = {"parameter", "get-tuple-element", "bitcast", "tuple", "while",
             "conditional", "call", "optimization-barrier",
             "slice-done", "copy-done"}
_PREFETCH_JOIN = 'custom_call_target="ConcatBitcast"'
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{\s*$")
_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(s8\[[\d,]*\])\S*\s+([\w\-]+)\(")


class Materialised(NamedTuple):
    """An int8 array the program writes: `copy.41 s8[1,4096,4096]`."""
    name: str
    shape: str
    opcode: str

    def __str__(self):
        return f"{self.name} {self.shape} ({self.opcode})"


def describe_v5e():
    """One described v5e device's sharding (raises where no TPU
    topology can be described). Loads libtpu: call it from a test or a
    fixture, never while a module is imported."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-4")
    os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    return SingleDeviceSharding(topo.devices[0])


def abstract_step_args(config, sharding, *, bits: Optional[int] = 8,
                       slots: int = 16, n_pages: int = 256,
                       page_size: int = 128, max_seq_len: int = 2048,
                       width: Optional[int] = None):
    """The positional arguments of a paged step program as shapes on
    `sharding`: (params, tokens, pos, active, cache, rope) for a decode
    step, (params, tokens, pos, q_len, active, cache, rope) with the
    mixed step's window `width`. bits: 8 draws the matmul leaves as
    int8 QTensors (what --quant int8 serves), None keeps bf16."""
    import jax
    import jax.numpy as jnp

    from cake_tpu.models.llama.model import RopeTables
    from cake_tpu.models.llama.paged import PagedKVCache

    if config.is_moe:
        from cake_tpu.models.moe.params import init_params
        init = partial(init_params, config, dtype=jnp.bfloat16, bits=bits)
    elif bits:
        from cake_tpu.models.llama.params import init_params_quantized
        init = partial(init_params_quantized, config, dtype=jnp.bfloat16,
                       bits=bits)
    else:
        from cake_tpu.models.llama.params import init_params
        init = partial(init_params, config, dtype=jnp.bfloat16)

    def on_device(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=sharding), tree)

    params = on_device(jax.eval_shape(init, jax.random.PRNGKey(0)))
    cache = on_device(jax.eval_shape(lambda: PagedKVCache.create(
        config, slots, n_pages, page_size, max_seq_len, width=width)))
    rope = on_device(jax.eval_shape(
        lambda: RopeTables.create(config, max_seq_len)))
    row = on_device(jax.ShapeDtypeStruct((slots,), jnp.int32))
    active = on_device(jax.ShapeDtypeStruct((slots,), jnp.bool_))
    tokens = on_device(jax.ShapeDtypeStruct((slots, width or 1), jnp.int32))
    if width is None:
        return params, tokens, row, active, cache, rope
    return params, tokens, row, row, active, cache, rope


def compile_step(step_fn, config, sharding, *, width: Optional[int] = None,
                 n_tokens: Optional[int] = None, attn: str = "pallas",
                 **shape):
    """`step_fn` (paged.decode_step_ragged_paged / mixed_step_paged or a
    family's own pair: one signature) compiled for the described chip
    with the real Mosaic kernels. Returns the jax `Compiled`."""
    from cake_tpu.ops import ragged_paged_attention as rpa

    args = abstract_step_args(config, sharding, width=width, **shape)
    kw = dict(config=config, attn=attn)
    if width is not None:
        kw["n_tokens"] = n_tokens
    # the kernels interpret unless they see a chip; there is none to see
    on_tpu, rpa._on_tpu = rpa._on_tpu, lambda: True
    try:
        return step_fn.lower(*args, **kw).compile()
    finally:
        rpa._on_tpu = on_tpu


def materialised_int8(hlo_text: str) -> List[Materialised]:
    """The int8 arrays an optimised HLO module writes outside any
    fusion: transposing copies and stand-alone slices of a weight (and,
    where the page pool is quantised, its in-place writes)."""
    fused = set(re.findall(r"calls=%?([\w.\-]+)", hlo_text))
    found, inside = [], None
    for line in hlo_text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            inside = head.group(1)
            continue
        if inside is None or inside in fused:
            continue
        m = _INSTRUCTION.match(line)
        if (m and m.group(3) not in _FORWARDS
                and _PREFETCH_JOIN not in line):
            found.append(Materialised(*m.groups()))
    return found


def step_fns(config):
    """(decode step, mixed step) of the config's family."""
    family = config.family
    return family.decode_step, family.mixed_step


def main(argv=None) -> int:
    import dataclasses
    import json

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("model_dir", help="a directory with a config.json "
                    "(a cell's: its cell.json gives the pool and slots)")
    ap.add_argument("--layers", type=int, default=0,
                    help="compile this many layers (0: the config's own; "
                    "a scan compiles as fast at 32 as at 2)")
    ap.add_argument("--n-tokens", type=int, default=144,
                    help="the mixed step's packed size")
    ap.add_argument("--hlo", help="write <hlo>.decode.txt / .mixed.txt")
    a = ap.parse_args(argv)

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from cake_tpu.models.llama.config import load_config

    config = load_config(a.model_dir)
    if a.layers and not getattr(config, "layer_types", None):
        config = dataclasses.replace(config, num_hidden_layers=a.layers)
    shape, width = {}, 128
    cell = os.path.join(a.model_dir, "cell.json")
    if os.path.exists(cell):
        with open(cell) as f:
            cell = json.load(f)
        sa = cell["server_args"]
        # a dense-cache cell names no pool: the defaults stand in
        shape = {arg: sa[flag] for arg, flag in (
            ("slots", "max-slots"), ("n_pages", "kv-pages"),
            ("page_size", "kv-page-size"), ("max_seq_len", "max-seq-len"))
            if flag in sa}
        width = cell.get("shape", {}).get("mixed_width", width)
    sharding = describe_v5e()
    decode, mixed = step_fns(config)
    bad = 0
    for name, fn, kw in (("decode", decode, {}),
                         ("mixed", mixed, dict(width=width,
                                               n_tokens=a.n_tokens))):
        try:
            hlo = compile_step(fn, config, sharding, **kw, **shape).as_text()
        except ValueError:  # the kernel refuses the shape: served by the fold
            print(f"{name}: no kernel at this shape, through the fold")
            hlo = compile_step(fn, config, sharding, attn="fold", **kw,
                               **shape).as_text()
        if a.hlo:
            with open(f"{a.hlo}.{name}.txt", "w") as f:
                f.write(hlo)
        found = materialised_int8(hlo)
        bad += len(found)
        print(f"{name}: {len(found)} int8 arrays materialised")
        for m in found:
            print(f"  {m}")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
