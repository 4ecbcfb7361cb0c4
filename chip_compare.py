#!/usr/bin/env python3
"""Logits of the served path against the plain float32 reference, on the
chip, at published widths.

    python chip_compare.py                       OLMoE-1B-7B, the cell's
                                                 configuration and options
    python chip_compare.py --rehearse            its toy configuration on
                                                 the CPU: proves the
                                                 script, never the chip

chip_smoke.py proves that the server starts and answers; this proves
that what it answers is the model. Logits never cross the HTTP API, so
this script holds the chip itself (there is no server child to share
with chip_smoke.py): it builds what `cake_tpu.cli` builds from the same
options (`Master.from_args` -> `context.load_text_model` -> the paged
engine, never started), takes the engine's weights, page pool, rope
tables and resolved attention, and drives the step programs' own
trunks (`paged._mixed_windows_trunk`, `paged._forward_ragged_paged`,
which `mixed_step_paged` and `decode_step_ragged_paged` wrap) with the
head at every position:

  * 8 seeded sequences, one of each prompt class of `chat-closed` and
    three between (65 .. 1792 tokens), in 8 of the 16 rows at once;
  * prompts prefilled through 128-wide mixed windows, each row at its
    own pace, rows that have finished decoding (one-token rows) beside
    rows that still prefill, every step at the packed size the engine
    dispatches (`engine._mixed_groups`, `paged.mixed_bucket_for`: two
    prefilling rows a dispatch at 272 positions, the last alone at
    144), each dispatch the engine's own program with the head at
    every packed position; then decode steps through the decode
    program until every row has 32, teacher-forced;
  * logits at the last 256 prompt positions and at every decode step,
    and each layer's top-k expert sets at those positions, against
    `cake_tpu/models/reference/olmoe.py` run in float32 at `highest`
    matmul precision over the SAME weights (the int8 leaves
    dequantized, one layer's float32 at a time), after the served path
    has finished and given its page pool back.

THE TOLERANCE, and why. Errors are |system - reference| relative to the
range (max - min) of the reference's logits at that position. The
system stores and multiplies bfloat16 activations (8 mantissa bits,
3.9e-3 a rounding) through 16 layers; the weights are the same numbers
on both sides. Two limits: the mean error over all compared positions
and vocabulary entries must be under MEAN_TOL, and the worst entry
under MAX_TOL. They are set from two readings (PERF.md §6, PR 26): the
largest the served path gives over seeds, and what the reference itself
gives with int8 activations (the nearest precision below the stated
bfloat16), which must fail; so must a reference whose top-k weights are
renormalised. A per-head QK norm changes every logit by its own size
and fails both by two orders of magnitude (tests/test_olmoe_reference.py
holds the float32 path to 2e-4).

The last line of stdout is one JSON object with `ok`.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from functools import partial

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(ROOT, "chiprun_out", "chip_compare")
CONFIG_DIR = os.path.join(ROOT, "benchmarks", "configs", "olmoe-1b-7b-int8")

MEAN_TOL = 1.6e-3   # mean |error| / range, all compared entries
MAX_TOL = 3e-2      # worst entry / range
PROMPTS = (100, 352, 736, 1248, 1792, 65, 384, 1000)
N_DECODE = 32
LAST = 256          # prompt positions compared, from the prompt's end
DECODE_IN_MIXED = 16  # of the 32, at most this many as one-token rows of
                      # mixed steps; the rest through the decode program


def say(msg: str) -> None:
    print(msg, flush=True)


def cli_argv(cell: dict, model_dir: str, rehearse: bool) -> list:
    """The cell's `server_args` as `cake_tpu.cli` would get them."""
    opts = dict(cell["server_args"])
    if rehearse:
        opts.update(cell["rehearse"]["server_args"])
    argv = ["--model", model_dir]
    for key, value in opts.items():
        argv += [f"--{key}"] if value is True else [f"--{key}", str(value)]
    return argv


def build_engine(rehearse: bool):
    with open(os.path.join(CONFIG_DIR, "config.json")) as f:
        config = json.load(f)
    with open(os.path.join(CONFIG_DIR, "cell.json")) as f:
        cell = json.load(f)
    if rehearse:
        config.update(cell["rehearse"]["config"])
    model_dir = os.path.join(OUT_DIR, "model")
    os.makedirs(model_dir, exist_ok=True)
    with open(os.path.join(model_dir, "config.json"), "w") as f:
        json.dump(config, f)
    from cake_tpu.args import parse_args
    from cake_tpu.master import Master
    from cake_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    args, sd_args, _ = parse_args(cli_argv(cell, model_dir, rehearse))
    master = Master.from_args(args, sd_args)
    return master.make_engine(), cell, config


def fake_int8(x):
    """Activations as symmetric per-row int8 would hold them."""
    import jax.numpy as jnp
    scale = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.round(x / scale) * scale


def dequantized(leaf):
    """A leaf as the float32 array the reference is fed: an int8
    QTensor's q * scale, on the device that holds it."""
    import jax.numpy as jnp

    from cake_tpu.ops.quant import QTensor
    if isinstance(leaf, QTensor):
        return (leaf.q.astype(jnp.float32)
                * jnp.expand_dims(leaf.scale, leaf.q.ndim - 2))
    return leaf.astype(jnp.float32)


def reference_run(ref, params, sequences, ref_cfg):
    """The reference's logits and routing for every sequence, one
    layer's float32 weights alive at a time. It runs where the weights
    are (float32 at `highest` matmul precision, which the reference
    sets): the host's single-threaded eager float32 took 25 minutes
    for these 6 200 tokens; the chip takes 36 s once its compile cache
    holds the eight sequence lengths, 490 s when it does not (PR 26)."""
    import jax

    blocks = params["blocks"]
    L = next(iter(blocks.values())).shape[0]
    t0 = time.monotonic()

    def layers():
        for i in range(L):
            say(f"  reference layer {i} of {L} at "
                f"{time.monotonic() - t0:.1f} s")
            yield {k: dequantized(jax.tree.map(lambda a: a[i], v))
                   for k, v in blocks.items()}

    top = {k: dequantized(params[k])
           for k in ("embed", "final_norm", "lm_head")}
    routing = [[] for _ in sequences]
    logits = ref.forward(top, list(sequences), ref_cfg, layers=layers(),
                         routing=routing)
    return [np.asarray(x) for x in logits], routing


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--negatives", type=int, default=2,
                    help="sequences (the shortest) on which the "
                         "corrupted references are read")
    args = ap.parse_args()
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    os.makedirs(OUT_DIR, exist_ok=True)
    t_start = time.monotonic()

    import jax
    import jax.numpy as jnp

    from cake_tpu.models.llama import paged
    from cake_tpu.models.reference import olmoe as ref
    from cake_tpu.ops.quant import qmatmul

    engine, cell, raw_config = build_engine(args.rehearse)
    cfg, params, rope = engine.config, engine.params, engine.rope
    impl = {k: engine._step_impl(k) for k in ("mixed", "decode")}
    say(f"device {jax.devices()[0].device_kind}; attention {impl}; "
        f"engine built in {time.monotonic() - t_start:.1f} s")
    if not args.rehearse and impl != cell["expect_impl"]:
        say(f"FAILED: expected attention {cell['expect_impl']}")
        return 1
    attn = "pallas" if impl["mixed"] == "paged-pallas" else "fold"

    @partial(jax.jit, static_argnames=("n_tokens",),
             donate_argnames=("cache",))
    def window_step(params, tokens, pos, q_len, active, cache, n_tokens):
        # the program mixed_step_paged runs at this size, the head at
        # every packed position: [1, T, V]
        plan = paged.pack_plan(q_len, active, n_tokens, tokens.shape[1])
        x, cache, stats = paged._mixed_windows_trunk(
            params, tokens, pos, q_len, active, cache, rope, cfg, attn,
            plan)
        logits = qmatmul(x, params["lm_head"]).astype(jnp.float32)
        return logits, cache, stats.experts

    @partial(jax.jit, donate_argnames=("cache",))
    def decode_step(params, tokens, pos, active, cache):
        logits, cache, stats = paged._forward_ragged_paged(
            params, tokens, cache, pos, active, rope, cfg, attn)
        return logits, cache, stats.experts

    B, C = engine.max_slots, engine._mixed_chunk
    page, per_row = engine.cache.page_size, engine.cache.table.shape[1]
    prompts = PROMPTS if not args.rehearse else tuple(
        min(p, 8 * C) // 4 + 5 for p in PROMPTS)
    n_decode = N_DECODE
    rng = np.random.default_rng(args.seed)
    sequences = [rng.integers(0, cfg.vocab_size, p + n_decode)
                 for p in prompts]
    assert len(sequences) <= B and max(prompts) + n_decode <= per_row * page
    table = np.full((B, per_row), -1, np.int32)
    for b in range(len(sequences)):
        table[b] = 1 + b * per_row + np.arange(per_row)
    assert table.max() < engine.cache.n_pages
    cache = engine.cache._replace(table=jnp.asarray(table))

    got = [dict() for _ in sequences]       # position -> logits [V]
    routed = [dict() for _ in sequences]    # position -> experts [L, k]
    off = [0] * len(sequences)              # tokens consumed

    def wanted(b, position):
        return position >= prompts[b] - LAST

    steps = {"mixed": 0, "decode": 0}
    sizes = {}                               # packed size -> mixed steps
    t0 = time.monotonic()
    while any(off[b] < prompts[b] for b in range(len(sequences))):
        toks = np.zeros((B, C), np.int32)
        pos = np.zeros(B, np.int32)
        qlen = np.zeros(B, np.int32)
        active = np.zeros(B, bool)
        for b, seq in enumerate(sequences):
            if off[b] < prompts[b]:
                n = min(C, prompts[b] - off[b])
            elif off[b] < prompts[b] + DECODE_IN_MIXED:
                n = 1
            else:
                continue
            toks[b, :n] = seq[off[b]:off[b] + n]
            pos[b], qlen[b], active[b] = off[b], n, True
        # in the dispatches the engine would run this step in, each at
        # the packed size the engine would give it
        for group in engine._mixed_groups(qlen):
            glen = np.where(group, qlen, 0)
            n_tokens = paged.mixed_bucket_for(engine._mixed_buckets,
                                              int(glen.sum()))
            logits, cache, experts = window_step(
                params, jnp.asarray(toks), jnp.asarray(pos),
                jnp.asarray(glen), jnp.asarray(active & group), cache,
                n_tokens)
            sizes[n_tokens] = sizes.get(n_tokens, 0) + 1
            # a row's first token on the packed axis of the results
            first = np.cumsum(glen) - glen
            logits = logits.reshape(-1, logits.shape[-1])
            experts = np.asarray(experts).reshape(
                experts.shape[0], -1, experts.shape[-1])
            rows = [b for b in np.flatnonzero(glen) if any(
                wanted(b, off[b] + j) for j in range(glen[b]))]
            fetched = {b: np.asarray(logits[first[b]:first[b] + glen[b]])
                       for b in rows}
            for b in np.flatnonzero(glen):
                for j in range(glen[b]):
                    routed[b][off[b] + j] = experts[:, first[b] + j]
                    if b in fetched and wanted(b, off[b] + j):
                        got[b][off[b] + j] = fetched[b][j]
        for b in range(len(sequences)):
            off[b] += int(qlen[b])
        steps["mixed"] += 1
    while any(off[b] < prompts[b] + n_decode for b in range(len(sequences))):
        toks = np.zeros((B, 1), np.int32)
        pos = np.zeros(B, np.int32)
        active = np.zeros(B, bool)
        for b, seq in enumerate(sequences):
            if off[b] < prompts[b] + n_decode:
                toks[b, 0], pos[b], active[b] = seq[off[b]], off[b], True
        logits, cache, experts = decode_step(
            params, jnp.asarray(toks), jnp.asarray(pos), jnp.asarray(active),
            cache)
        logits, experts = np.asarray(logits), np.asarray(experts)
        for b in range(len(sequences)):
            if active[b]:
                got[b][off[b]] = logits[b]
                routed[b][off[b]] = experts[:, b]
                off[b] += 1
        steps["decode"] += 1
    say(f"served path: {steps['mixed']} mixed (dispatches by packed size: "
        f"{dict(sorted(sizes.items()))}) and {steps['decode']} decode "
        f"steps in {time.monotonic() - t0:.1f} s")

    # -- the reference, on the host ------------------------------------
    ref_cfg = {"num_attention_heads": cfg.num_attention_heads,
               "num_key_value_heads": cfg.num_key_value_heads,
               "rms_norm_eps": cfg.rms_norm_eps,
               "rope_theta": cfg.rope_theta,
               "num_experts_per_tok": cfg.num_experts_per_tok,
               "norm_topk_prob": cfg.norm_topk_prob}
    del cache, engine.cache              # the pool's 4 GiB, for the reference
    # one compilation per sequence length and function, not one per
    # operation: the functions are the reference's own
    plain = {"attention": ref.attention, "swiglu": ref.swiglu, "mm": ref.mm}

    def compiled(**replaced):
        """The reference's attention and swiglu under jit, traced anew
        (so that a replaced `mm` or config is what they run)."""
        for name, fn in dict(plain, **replaced).items():
            setattr(ref, name, fn)
        attention, cfgs = ref.attention, {}

        def jitted_attention(lp, h, config):
            key = tuple(sorted(config.items()))
            if key not in cfgs:
                cfgs[key] = jax.jit(lambda lp, h: attention(lp, h, config))
            return cfgs[key](lp, h)

        ref.attention = jitted_attention
        ref.swiglu = jax.jit(ref.swiglu)

    compiled()
    t0 = time.monotonic()
    want, want_routing = reference_run(ref, params, sequences, ref_cfg)
    say(f"reference: {sum(len(s) for s in sequences)} tokens in "
        f"{time.monotonic() - t0:.1f} s")

    abs_sum = n_entries = 0.0
    worst_abs = worst_rel = 0.0
    rel_sum = 0.0
    positions = 0
    L = len(want_routing[0])
    agree_layer = np.zeros(L)
    agree_all = 0
    for b, seq in enumerate(sequences):
        for position, logits in sorted(got[b].items()):
            w = want[b][position]
            err = np.abs(logits - w)
            scale = float(w.max() - w.min())
            abs_sum += float(err.sum())
            rel_sum += float(err.sum()) / scale
            n_entries += err.size
            worst_abs = max(worst_abs, float(err.max()))
            worst_rel = max(worst_rel, float(err.max()) / scale)
            same = np.array([
                set(routed[b][position][layer])
                == set(want_routing[b][layer][position])
                for layer in range(L)])
            agree_layer += same
            agree_all += bool(same.all())
            positions += 1
    expected = sum(min(LAST, p) + n_decode for p in prompts)
    result = {
        "positions": positions, "expected_positions": expected,
        "mean_abs_err": abs_sum / n_entries, "max_abs_err": worst_abs,
        "mean_rel_err": rel_sum / n_entries, "max_rel_err": worst_rel,
        "same_top_k_every_layer_share": agree_all / positions,
        "same_top_k_by_layer_share": [round(float(x) / positions, 4)
                                      for x in agree_layer],
        "mean_tol": MEAN_TOL, "max_tol": MAX_TOL, "seed": args.seed,
        "prompts": list(prompts), "steps": steps, "attention": impl,
        "device": jax.devices()[0].device_kind,
    }

    # -- what must NOT pass: the reference, corrupted, against itself --
    if args.negatives:
        short = sorted(range(len(sequences)),
                       key=lambda b: len(sequences[b]))[:args.negatives]
        seqs = [sequences[b] for b in short]

        def against_reference(logits):
            errs = [np.abs(x - want[b]) / (want[b].max(axis=-1, keepdims=True)
                                           - want[b].min(axis=-1,
                                                         keepdims=True))
                    for x, b in zip(logits, short)]
            return (float(np.mean(np.concatenate(errs))),
                    float(max(e.max() for e in errs)))

        renorm, _ = reference_run(ref, params, seqs,
                                  dict(ref_cfg, norm_topk_prob=True))
        result["renormalised_reference"] = against_reference(renorm)
        compiled(mm=lambda x, w: plain["mm"](fake_int8(x), w))
        int8_act, _ = reference_run(ref, params, seqs, ref_cfg)
        compiled()
        result["int8_activation_reference"] = against_reference(int8_act)

    def passes(mean, worst):
        return mean < MEAN_TOL and worst < MAX_TOL

    ok = (positions == expected
          and passes(result["mean_rel_err"], result["max_rel_err"]))
    for name in ("renormalised_reference", "int8_activation_reference"):
        if name in result and passes(*result[name]):
            say(f"FAILED: the {name} passes the tolerance")
            ok = False
    result["ok"] = bool(ok)
    result["seconds"] = round(time.monotonic() - t_start, 1)
    with open(os.path.join(OUT_DIR, f"result_seed{args.seed}.json"),
              "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
