#!/usr/bin/env python3
"""Logits of the served path against the plain float32 reference, on the
chip, at published widths.

    python chip_compare.py [CONFIG_DIR]          a cell's configuration and
                                                 options (a directory of
                                                 benchmarks/configs; default
                                                 OLMoE-1B-7B's)
    python chip_compare.py [CONFIG_DIR] --rehearse   its toy configuration
                                                 on the CPU: proves the
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

GLM-5.2 (`benchmarks/configs/glm-5.2-int8-share16`, model_type
glm_moe_dsa) goes the same way through its own step programs
(`models/moe/glm_dsa.mixed_trunk`, `decode_trunk`) and its own
reference (`cake_tpu/models/reference/glm_moe_dsa.py`, given the same
held experts): four sequences of 12,200, 6,100, 4,100 and 3,000 prompt
tokens in four of the eight rows, one 512-token window a dispatch with
the rows that already decode beside it, then the decode program;
logits at the last 256 prompt positions and at 16 decode steps (all
beyond 2,048: every query there dropped keys) and at the last 256
positions under 2,048 (where precision is read), each sparse layer's
expert sets and each attention layer's attended key sets (the share in
common with the reference's). Its limits (GLM_TOL, read off the chip as PR 26 read
OLMoE's: PERF.md section 6, PR 30) lie between the worst the served
path reads over three seeds and what must fail: the reference with
int8 activations, with dense attention above index_topk keys, with
softmax routing, and with every shared layer running indexer weights
of its own drawn afresh.

DeepSeek-V2 (`benchmarks/configs/deepseek-v2-int8-share8`, model_type
deepseek_v2) goes through the same trunks as GLM-5.2 (their dense kind
of layer) and `cake_tpu/models/reference/deepseek_v2.py`, given the
same held group: three sequences of 4,600, 3,700 and 1,100 prompt
tokens, one 512-token window a dispatch with the rows that already
decode beside it, then the decode program through the pages to 4,624
positions (past the cell's prompt class, inside --max-seq-len); logits
at the last 128 prompt positions and at 24 decode steps; experts
teacher-forced and nothing else; the page-walking kernel and the window
pass each probed on the chip against exact attention. Its limits (DSV2_TOL) lie between what
the served path reads and what must fail: the reference with a
bfloat16 softmax, with the scale without mscale^2, with plain RoPE in
place of YaRN, with the group mask left out, with norm_topk_prob true,
and with the x 16 left out.

    python chip_compare.py benchmarks/configs/deepseek-v2-int8-share8 [--seed N] [--rehearse]

Ling-3.0-flash (`benchmarks/configs/ling-3.0-flash-int8-share4`,
model_type bailing_hybrid) goes through `models/moe/bailing_hybrid`'s
trunks and `cake_tpu/models/reference/bailing_hybrid.py`, given the same
two held groups: a `d8k` and a `t2k` prompt of the cell through
512-token windows, then decode through the state and the pages, every
other row of the 32 decoding beside them (fillers: the step is the timed
one), and a second request in the `t2k` row's slot once it has finished,
with its twin in a slot nothing has used. Experts teacher-forced (the
reference's recurrence token by token against the served chunked and
one-step forms). Its limits (LING_TOL) lie between what the served path
reads and what must fail: a bfloat16 state, a bfloat16 decay, the
unbounded gate, a state not zeroed at position 0, a group's score by its
best alone, the gate a head left out.

Keye-VL-2.0's language model
(`benchmarks/configs/keye-vl-2.0-lm-int8-8of48`, model_type KeyeVL2)
goes through `models/moe/keye_vl2.mixed_trunk` / `decode_trunk` and
`cake_tpu/models/reference/keye_vl2.py` (compare_keye): sequences of
32,700, 16,300, 8,200 and 1,900 prompt tokens (one UNDER `topk`) in four
of the eight rows beside four fillers, one 512-token window a dispatch,
then 32 decode steps a row. BOTH of a layer's choices are teacher-forced
in the reference (the served path's experts and its selected key sets at
every position), so the logits read the arithmetic; the selection is
compared on its own on that trajectory (the share of a query's set both
sides chose, and each disagreement's distance from the reference's
2,048th score). Its limits (KEYE_TOL, KEYE_KEYS) lie between what the
served path reads and what must fail: int8 activations, and the
reference's own selection at half the published `topk`.

Brumby-14B-Base (`benchmarks/configs/brumby-14b-int8-10of40`, model_type
brumby) goes through `models/moe/brumby.mixed_trunk` / `decode_trunk` and
`cake_tpu/models/reference/brumby.py` (compare_brumby): the quadratic
form of power retention against the served state. All 16 rows in every
step (a d8k prompt of 8,100 tokens, a t2k of 2,000, fillers decoding,
then a second request of 1,950 in the t2k row's slot and its twin in a
fresh slot), 512-token windows then 24 decode steps a request; nothing
is teacher-forced (no discrete choice). Compared: the logits, the FIRST
layer's stored S at a request's end (brought to the exact triangle's
layout) against the reference's own sum over its keys, and the reused
slot against its twin. Its limits (BRUMBY_TOL) lie between what the
served path reads and what must fail: a bfloat16 state, a float32
state read at one bfloat16 pass (the window form's products at default
precision), the gate dropped, the normaliser dropped, the rotation
dropped, a state not zeroed at reuse, degree 1 in place of 2. A control
is read twice: as the served path is (served against altered), and
`from_plain` (altered against the plain reference: what the alteration
alone moves).

LongCat-Flash-Chat (`benchmarks/configs/longcat-flash-int8-share32`,
model_type longcat_flash) goes through `models/moe/glm_dsa`'s trunks
(their shortcut kind of layer) and
`cake_tpu/models/reference/longcat_flash.py`, given the same held
experts (compare_longcat): a `d8k` and a `t2k` prompt of the cell
through 512-token windows, then decode through the pages, every other
row of the 32 decoding beside them. Experts teacher-forced; the router's
own choice compared on the served path's own input; both latent kernels
probed at 64 heads against exact attention. Its limits (LONGCAT_TOL) lie
between what the served path reads and what must fail: a bfloat16
softmax, int8 activations, the zero experts dropped, the weights
renormalised, either latent's scale left out, the shortcut tapped from
the second sublayer or returned before it, the choice bias in the
weight.

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

# glm_moe_dsa: 9 layers, two discrete choices a layer, and the choice of
# keys is chaotic under seeded weights (attention over 2,048 keys that
# all look alike: a rounding reorders the edge of the top 2,048, and
# the next layer's rounding starts from there). So precision is read
# where no key is dropped (`mean_dense`: positions under index_topk),
# as OLMoE's is; beyond it the limits are the mean error (`mean`) and
# the least share of attended keys in common with the reference over
# the nine attention layers (`keys`: a layer that attends other sets
# shares about 2048/context of them by chance). The worst entry is
# reported, not limited: one flipped expert moves an entry by 0.2 on
# either side. Readings (PERF.md section 6, PR 30; served path over
# three seeds / what must fail): mean_dense 3.4e-3 / int8 activations
# 7.5e-3; mean 1.4e-2 / dense attention 7.7e-2, softmax routing 3.8e-2;
# keys 0.90 / fresh shared indexers 0.61, dense attention 0.61.
GLM_TOL = {"mean_dense": 5e-3, "mean": 2.5e-2, "keys": 0.85}
GLM_PROMPTS = (12200, 6100, 4100, 3000)
GLM_DECODE = 16

# nemotron_h: 22 blocks, ten discrete choices of 22 among 512 and ten
# recurrent states. Under seeded weights the choice of experts is
# chaotic (22 of 512 sigmoid scores that lie 0.002 apart: a bf16
# rounding swaps the edge, 4 % of a block's tokens run another expert,
# the next block starts from there, and by the tenth E block 0.84 of
# the sets are shared), so every reading carries that floor: `mean`,
# the mean logit error over the compared positions, is 2e-2 where
# OLMoE's is 1e-3, and even the first Mamba block's state is 3-5 % off
# on its slowest heads. Each fault a served path could have is
# therefore read where it shows ABOVE the floor. Four limits: `mean`;
# `mean_edge`, the mean over the 3 positions behind the first window
# edge (whose conv reads the stored tail); `keys`, the first attention
# block's stored keys against the reference's, relative (a rotation
# shows there and nowhere else: attention over seeded keys is near
# uniform, so rotating them barely moves a logit); `reuse`, the second
# request of a slot against THE SAME request served in a slot nothing
# has used, relative to the reference's range (the served path against
# itself: no floor; for the altered reference that starts a request
# from the state the slot's last request left, that request against the
# plain reference). A bfloat16 STATE moves the readings by less than
# the floor (1.2e-2 in `mean`), so the stored state's type is held
# directly (`state_dtype`), and the effect on the CPU at float32
# (tests/test_nemotron_h.py). `mean_start` (a request's first 8
# positions), `state_slow` (the first Mamba block's 16 slowest heads'
# relative state error at a request's end), the worst entry, every
# block's state and the experts in common are reported, not limited.
# Readings: PERF.md section 6, PR 33.
NEMOTRON_TOL = {"mean": 2.6e-2, "mean_edge": 5e-2, "keys": 0.7,
                "reuse": 1e-3}
NEMOTRON_START, NEMOTRON_EDGE, NEMOTRON_SLOW_HEADS = 8, 3, 16
# (slot, prompt tokens): both prompt classes of agent-closed, one
# between, and a second request in slot 1 once its first has finished
NEMOTRON_JOBS = ((0, 4000), (1, 1000), (2, 2100), (1, 900))
NEMOTRON_DECODE = 16

# zaya: 40 layers, each a discrete choice of ONE expert of 16. Where a
# token's two best experts lie nearer than a bf16 rounding the served
# path takes the other, the next layer starts from there, and a free-
# running reference drifts away by whole experts: a floor that would
# hide a precision error. So the reference is TEACHER-FORCED: it takes
# the served path's choice in every (token, layer) and weighs it by its
# OWN probability, and its own choice along that trajectory is compared
# with the served one (`agree`, a share: no cascade). Limits, each read
# where its fault shows: `mean` and `max`, |error| / range of the
# logits over the compared positions (precision; the router's state and
# the top-1 weight through p); `mean_edge`, the mean over the first two
# positions behind each 128-token window edge of a prompt (their taps
# read the stored tail); `keys` and `values`, layer 0's stored K and V
# pages against the reference's, relative (the convolutions, the q-k
# mean, the norm and above all the ROTATED SHARE show in the keys, the
# SHIFT in the values: attention over seeded keys is near uniform, so
# neither moves a logit by much); `reuse`, a slot's second request
# against the same request in a slot nothing has used (the served path
# against itself: a tail that is not zeroed shows here); and `agree`
# from below. Each limit lies between the worst the served path read on
# the chip over seeds 0 / 1 / 2 and the LEAST the int8-activation
# reference read there (PERF.md section 6, PR 37), about the geometric
# middle: mean 1.079e-3 | 2.249e-3, max 1.128e-2 | 2.315e-2, mean_edge
# 1.058e-3 | 1.926e-3, keys 3.414e-3 | 8.729e-3, values 2.358e-3 |
# 8.693e-3, agree 0.9913 | 0.9749 (from above). What HOLDS the lower
# precision out is `values` (3.7 times between the readings) and
# `agree`; `mean` (2.08 times) and `keys` (2.56) are secondary: limits
# fitted to three seeds, a fresh seed read under them in PERF.md
# section 6. The five other altered
# references fail by tens of times (conv taps keys 0.80, shift values
# 1.0, rotation keys 1.02, renormalised mean 0.09) or, the router's
# state, by `agree` 0.50-0.54 and `mean` 2.3e-3.
ZAYA_TOL = {"mean": 1.55e-3, "max": 1.6e-2, "mean_edge": 1.5e-3,
            "keys": 5.5e-3, "values": 4.5e-3, "reuse": 1e-3}
ZAYA_AGREE = 0.984
ZAYA_EDGE = 2
# (slot, prompt tokens): both prompt classes of reason-closed in 8 of
# the 32 rows at once, then a second request in slot 0
ZAYA_JOBS = ((0, 129), (1, 192), (2, 256), (3, 192), (4, 897), (5, 1024),
             (6, 960), (7, 960), (0, 140))
ZAYA_DECODE = 64

# dots3_note: 9 layers, 3 full (an indexer each) and 6 sliding (513 keys
# of a 9-page ring), two latent geometries, a gate a head, 8 of 256
# experts a token of which this chip holds 32. As for glm_moe_dsa the
# choice of keys and of experts is chaotic under seeded weights, so the
# reference is TEACHER-FORCED in its experts (it computes the experts
# the served path chose, weighed by its own scores) and precision is
# read where no key is dropped (`mean_dense`: positions under
# index_topk; every sliding layer already cuts there, positions past
# 513); beyond it the limits are the mean error (`mean`) and the least
# share of attended keys in common with the reference over the three
# full layers (`keys`). `agree` is the least, over the sparse layers,
# share of positions where the reference's OWN choice of 8 experts is
# the served path's. `mean_wrapped` is `mean_dense` over the positions
# past 1,152 alone (the ring has wrapped: a prefilled row's and a
# decoded one's), reported. A window off by one and bfloat16 scores
# move the logits by LESS than the served path's own rounding (7e-4
# and 8e-4 against 2.5e-3), so no limit on an error can hold them:
# `nearer` does, the served path's distance from the altered reference
# over its distance from the plain one (root of summed squares over the
# compared rows): above 1 while the served path computes the plain
# reference, and what `nearer_if_served` reads (the same ratio for a
# served path that computed the altered one with the same rounding)
# if it did not. Readings (my chip runs, PR 41, seeds 0 / 1; served
# path / what must fail): mean_dense 2.49e-3 / no gate 0.110, no
# rescale 0.135; mean 1.21e-2 / dense attention 6.9e-2; keys 0.899 /
# dense attention 0.856; agree 0.653 / no gate, no rescale 0.0; nearer
# at its least 1.0159 (window - 1), 1.0406 (bf16 scores), 7.9 (dense
# attention) / nearer_if_served at its most 0.985. PERF.md section 6.
# THE PROBE (after review: `nearer` has 1 % of room on either side).
# The trunk hands out the first sliding layer's normed input, its
# attention's output and its FFN's normed input (TrunkOut.probe), and
# the reference's layer runs on THAT input, so that no other layer's
# rounding is on either side. `layer_err`: the served output against
# the reference's (root of summed squares over the reference's, compared
# positions of the three shorter rows); `layer_nearer`: the `nearer`
# ratio on that output; `agree_same_input`: the share of ALL their
# positions where the reference's router, on the served FFN input,
# chooses the served path's 8 experts (no teacher, no cascade: `agree`
# above reads the reference's own trajectory, where a third of the
# positions differ by chaos, so it only tells a router that is wrong
# from one that is right); `router_logit_err`: ops/moe.router_logits
# (what moe_mlp calls) on the chip against the reference's product on
# the same input, worst entry. The limits were set on a first reading
# (my chip run, PR 41, seed 5, rows of 2,500 / 1,120 / 700; served path
# / what must fail): layer_err 3.40e-3 / a window of 514, 512: 1.25e-2,
# 1.23e-2 (no gate 1.01, no rescale 0.59); layer_nearer 3.79, 3.76 for
# the window (298, 172) / layer_nearer_if_served 0.263, 0.267;
# agree_same_input 1.0 / a bf16 router 0.904; router_logit_err 0.0 /
# 1.49e-2: each between its two readings, 1.9 times of room either side
# of layer_err, 2.5 times above and 5.6 below layer_nearer, 0.05 either
# side of agree_same_input. Then seeds 0 / 1 with the 16.3k row, from
# the clean archive: layer_err 3.49e-3 / 3.51e-3 against 1.38e-2 at
# the least for a window off by one; layer_nearer 4.09 at the least,
# layer_nearer_if_served 0.245 at the most; agree_same_input 1.0 / 1.0
# against 0.921 / 0.917; router_logit_err 0.0 / 0.0 against 1.55e-2.
DOTS3_TOL = {"mean_dense": 5e-3, "mean": 2.5e-2, "keys": 0.875, "agree": 0.3,
             "nearer": 1.005, "layer_err": 6.5e-3, "layer_nearer": 1.5,
             "agree_same_input": 0.95, "router_logit_err": 1e-3}
# (prompt tokens): the cell's long class, a row past index_topk, one of
# the short class whose DECODE crosses 1,152 (the ring wraps under the
# decode program and under mixed steps), one past the window alone
DOTS3_PROMPTS = (16300, 2500, 1120, 700)
DOTS3_DECODE = 48

# deepseek_v2: 15 layers, no choice of keys (every visible key is
# attended), one discrete choice a sparse layer: 6 of 160 experts inside
# the 3 best of 8 groups, chaotic under seeded weights (softmax scores
# 1e-3 apart), so the reference is TEACHER-FORCED in its experts as for
# dots3_note and zaya, and NOTHING ELSE is forced: attention is held at
# the logits with no index_topk floor. Limits, each read where its fault
# shows: `mean` and `max`, |error| / range of the logits over the
# compared positions (the last 128 of each prompt and every decode
# step; the longest row ends past 4,608 keys): precision, the softmax
# scale (mscale^2), YaRN's frequencies, the routing weights (x 16, not
# renormalised); `agree`, the least over the 14 sparse layers of the
# share of compared positions where the reference's OWN choice of 6
# experts along the served trajectory is the served path's: the group
# rule (a top 6 over all 160 shares few sets with a top 6 inside 3
# groups); `probe`, the page-walking kernel ITSELF on the chip against
# exact float32 attention over the same latent pages (layer 0's, as the
# served path left them, the longest row) for queries drawn so that the
# scores spread over +-30 and a few keys carry a row: relative error,
# root of summed squares. There a bfloat16 score (2^-9 of 30 is 0.06:
# 6 % of a probability) shows far above the kernel's own rounding (its
# inputs are the same bfloat16 numbers on both sides, its scores, max,
# exponentials and sums float32, its probabilities bfloat16 for the
# value product), where at the logits the near-uniform attention of
# seeded weights averages it away: `probe` is what holds the softmax's
# precision, and the exact attention with bfloat16 scores and
# probabilities must fail it. `probe_window` is the same reading of the
# WINDOW pass (cake_mla_window_attn under causality, as attend_dense
# calls it: every prefill token, most of the cell's device time): the
# longest row's last 512 positions as one window over the same pages,
# queries drawn the same way, held to the same limit. Each limit lies
# between the worst the served path read on the chip and the LEAST an
# altered reference that
# it has to hold out read there, about the geometric middle (my chip
# runs, PR 45, seeds 0 / 1 / 2; PERF.md section 6; served | must
# fail): mean 3.85e-3 / 3.84e-3 / 3.89e-3 | 6.84e-2 at the least (x 16
# left out; no mscale^2 9.84e-2, plain RoPE 0.108, renormalised 0.121),
# max 3.09e-2 / 3.39e-2 / 3.53e-2 | 0.59, agree 0.761 / 0.772 / 0.754 |
# 0.0658 at the most (the group mask left out; seed 3), probe 1.70e-3 /
# 1.69e-3 / 1.63e-3 | 1.31e-2 at the least (a bfloat16 softmax: 7.7
# times; at the logits it reads mean 4.57e-3 - 4.70e-3 against the
# served path's 3.85e-3 and `nearer` 1.19, which no limit on an error
# could hold), probe_window 1.68e-3 on all three | 1.44e-2 at the least
# (a bfloat16 softmax: 8.6 times; the limit 5e-3 is 3.0 times over the
# one and 2.9 under the other).
DSV2_TOL = {"mean": 1.5e-2, "max": 0.15, "agree": 0.4, "probe": 5e-3}
DSV2_PROMPTS = (4600, 3700, 1100)
DSV2_DECODE = 24
DSV2_LAST = 128
DSV2_PROBE_SPREAD = 8.0     # the standard deviation of the probe's scores

# bailing_hybrid: 12 layers, 10 of them a float32 matrix state a row and
# head under the gated delta rule, 2 latent attention over every key, 10
# discrete choices of 8 of 512 experts inside 4 of 8 groups, chaotic
# under seeded weights (sigmoid scores 1e-3 apart), so the reference is
# TEACHER-FORCED in its experts as for deepseek_v2, and nothing else is
# forced. Limits, each read where its fault shows: `mean` and `max`,
# |error| / range of the logits over the compared positions (a prompt's
# first 8, the 3 behind its first window edge, its last 128 and every
# decode step: the window boundary, the passage from a row's last
# window to its first one-step update, contexts to 8.2k): precision,
# the gate's form, the head-wise gate; `state`, the FIRST KDA layer's
# stored matrix state at a request's end against the reference's,
# relative (root of summed squares; the worst request): what the state
# and the decay are held in shows there before any other layer's
# rounding (a bfloat16 alpha near 1 cannot say 0.9993: a channel with a
# half-life of 1,000 tokens forgets in 180 or never); `reuse`, a slot's
# second request against the same request in a slot nothing has used,
# relative to the reference's range (the served path against itself: no
# floor; over the request's PROMPT positions, which both rows take
# through the mixed program: the reused row's first decode tokens ride
# its twin's windows while the twin's go through the decode program, a
# rounding apart, and one flipped expert is 0.2 of a logit's range:
# `reuse_decode`, reported; for the altered reference that starts from
# the state the slot's last request left, against the plain reference);
# `agree_same_input`, the share of compared positions where the
# reference's router, on the served path's OWN input to the first
# sparse layer (TrunkOut.ffn_in), chooses the served path's 8 experts
# (no teacher, no cascade: the group rule); `router_logit_err`:
# ops/moe.router_logits on the chip against the reference's product on
# that input, worst entry. `agree` (the least over the sparse layers of
# the share of positions where the reference's own choice along the
# forced trajectory is the served path's), every KDA layer's state and
# `mean_edge` are reported, not limited. Each limit lies between the
# worst the served path read on the chip over seeds 0 / 1 / 2 and the
# LEAST an altered reference that it has to hold out read there (my
# chip runs, PR 48; served | must fail): mean 3.52e-3 / 3.53e-3 /
# 3.49e-3 | 7.33e-3 (a bfloat16 state: 1.42 times over, 1.47 under; the
# gate a head left out 2.34e-2, a state not zeroed 9.57e-3, a bfloat16
# decay 4.77e-2, the unbounded gate 0.123); max 3.0e-2 | 5.2e-2; state
# 5.17e-3 / 5.16e-3 / 5.14e-3 | 1.77e-2 (a bfloat16 state: 1.9 times
# either side; a bfloat16 decay 0.156, the unbounded gate 2.35); reuse
# 0.0 exactly | 1.53e-2 (a state not zeroed); agree_same_input 1.0 |
# 0.457-0.515 (a group by its best alone); router_logit_err 0.0.
LING_TOL = {"mean": 5e-3, "max": 0.1, "state": 1e-2, "reuse": 1e-3,
            "router_logit_err": 1e-3}
LING_AGREE = 0.9
LING_START, LING_EDGE, LING_LAST = 8, 3, 128
# (slot, prompt tokens): the cell's two prompt classes, and a second
# request in slot 1 once its first has finished
LING_JOBS = ((0, 8100), (1, 2000), (1, 1950))
LING_DECODE = 24

# K-EXAONE (benchmarks/configs/k-exaone-236b-int8-share8, model_type
# exaone_moe) goes the way Ling does (trunk_steps / drive_jobs: 32 rows
# in every step, one 512-token window a step, then 24 decode steps a
# request) against cake_tpu/models/reference/exaone_moe.py on
# teacher-forced experts. Limits: `mean` and `max` over the compared
# positions (a request's first 8, the 3 behind its first window edge,
# its last 128 prompt positions, every decode step); `reuse`, a slot's
# second request against its twin in a fresh slot over their PROMPT
# positions (the served path against itself: a ring and pages that
# still hold another request's keys must change nothing);
# `agree_same_input` and `router_logit_err` as Ling's; `probe`, BOTH
# ragged paged attention kernels THEMSELVES on the chip, banded through
# the ring and unbanded through the table, against exact float32
# attention over the K and V the served path left in row 0's pages (the
# d8k request's: first sliding layer, first full layer), for queries
# drawn so that the scores spread over +-30 and a few keys carry a row
# (exaone_probe; DeepSeek-V2's probe for these kernels): relative error,
# root of summed squares, the worst of the four calls. There a bfloat16
# score (2^-9 of 30 is 0.06: 6 % of a probability) shows far above the
# kernels' own rounding, where at the logits the near-uniform attention
# of seeded weights averages it away (`mean` 2.08e-3 against the served
# path's 1.89e-3, `nearer` 1.10: my chip run, PR 53, seed 0): `probe` is
# what holds the softmax's precision. `mean_decode`
# (the decode kernel's band through a ring that has wrapped),
# `mean_edge`, `agree` and `reuse_decode` are reported. Each limit lies
# between the worst the served path read on the chip and the LEAST an
# altered reference that it has to hold out read there (my chip runs,
# PR 53, seeds 0 / 1 / 2; served | must fail; the cell's cell.json,
# `chip_compare`, has every reading): mean 1.89e-3 / 1.94e-3 / 1.93e-3 |
# 1.065e-2 at the least (rotation in the full layers too; a window of
# 127 or 129 1.83e-2-1.95e-2, no q / k norm 3.96e-2, every layer under
# the band 7.04e-2); max 1.34e-2 / 1.41e-2 / 1.45e-2 | 7.38e-2; probe
# 1.70e-3 / 1.70e-3 / 1.74e-3 | 8.4e-3 at the least of its four calls
# (exact attention with a bfloat16 softmax; 1.87e-2 at the most); reuse
# 0.0 exactly; agree_same_input 1.0; router_logit_err 0.0.
EXAONE_TOL = {"mean": 5e-3, "max": 4e-2, "probe": 5e-3, "reuse": 1e-3,
              "router_logit_err": 1e-3}
EXAONE_PROBE_SPREAD = 8.0   # the standard deviation of the probe's scores
EXAONE_AGREE = 0.9
EXAONE_START, EXAONE_EDGE, EXAONE_LAST = 8, 3, 128
# (slot, prompt tokens): the cell's two prompt classes, and a second
# request in slot 1 once its first has finished
EXAONE_JOBS = ((0, 8100), (1, 2000), (1, 1950))
EXAONE_DECODE = 24

# granitemoehybrid: 40 dense layers, no discrete choice anywhere, so no
# floor and no teacher forcing: the reference runs free. Limits, each
# read where its fault shows, each between the worst the served path
# read on the chip over seeds 0 / 1 / 2 and the LEAST an altered
# reference that it has to catch read there (PERF.md section 6, PR 56):
# `mean` and `max`, |error| / range of the logits over the compared
# positions (bf16 activations through 40 layers and 80 residual
# branches; a dropped multiplier or the gate behind the norm move both
# by twenty times and more, a softmax scale of 1/sqrt(hd) `max` by
# eight); `mean_edge`, the mean over the 3 positions behind the first
# window edge, whose conv reads the stored tail; `attn`, BOTH attention
# kernels at the model's own scale on the chip against exact attention
# over the K and V pages the served path wrote (granite_probe), relative:
# the scale shows there at full size (0.50 against 1.7e-3) where
# attention over seeded keys at 1/64 is near uniform and four layers of
# forty barely move a logit; `reuse`, a slot's second request against
# the same request in a slot nothing has used (the served path against
# itself: no floor); `state_slow`, the FIRST Mamba layer's 16 slowest
# heads' carried state at a request's end against the reference's S_t,
# relative: a guard on the recurrence (a dropped multiplier reads
# 3.8e-2) that CANNOT tell a bfloat16 state (7.2e-3) from the served
# one (5.0e-3), because each token's contribution is rounded to
# bfloat16 on its way in and a sum of them keeps that relative error;
# so the state's precision is held where it shows without a floor:
# `state_f32_share` FROM BELOW, the share of the carried state's
# entries (first Mamba layer, every job) whose float32 pattern has a bit
# set under bfloat16's mantissa: ~1 for a float32 state, 0 for one
# rounded to bfloat16 every token (read on the altered reference's own
# S_t), beside `state_dtype`. `state` (every head of every layer, the
# worst layer: it grows with depth as the activations' error does) is
# reported, not limited. Readings, worst served over the three seeds |
# least altered (my chip runs, PR 56): mean 2.522e-3 | 5.98e-3 (scale;
# 5.4e-2 and more for a multiplier or the gate); max 2.10e-2 | 0.137
# (scale); mean_edge 2.52e-3 | 7.9e-2 (the conv's tail dropped at a
# window's edge); attn 1.669e-3 | 0.496; reuse 0.0 | 1.41e-2 (a slot's
# state inherited); state_slow 5.14e-3 | 3.58e-2 (no embedding
# multiplier; 7.7e-2 the conv's tail); state_f32_share 1.0 | 0.0.
GRANITE_TOL = {"mean": 4e-3, "max": 6e-2, "mean_edge": 4e-3, "attn": 2e-2,
               "reuse": 1e-3, "state_slow": 1e-2}
GRANITE_F32_SHARE = 0.9
GRANITE_START, GRANITE_EDGE, GRANITE_SLOW_HEADS = 8, 3, 16
GRANITE_LAST = 64
# (slot, prompt tokens): both prompt classes of sessions-closed, one
# that ends inside a window, and a second request in slot 1 once its
# first has finished (its twin is added beside it)
GRANITE_JOBS = ((0, 2048), (1, 512), (2, 1400), (1, 450))
GRANITE_DECODE = 24

# Brumby-14B-Base's comparison (compare_brumby). mean, max: |served -
# reference| / the range of the reference's logits at the position, over
# the compared positions. state: the FIRST layer's stored S (every K/V
# head) at a request's end against the reference's sum over its keys,
# relative (the worst job). reuse: a slot's second request against its
# twin in a fresh slot, prompt positions. Each limit lies between the
# worst the served path read over seeds 0 / 1 / 2 and the least an
# altered reference it has to hold out read. Readings, worst served |
# least altered (my chip runs, PR 63; seeds 0-3): mean 2.24e-3 | 5.27e-3
# (a bfloat16 state on seed 3, 7.5e-3-9.9e-3 on seeds 0-2; 6.2e-3 a
# float32 state read at one bf16 pass; 3.2e-2 a state not zeroed, 0.10
# and more the gate, the normaliser, the rotation or the degree); max
# 6.8e-2 | 0.70 (both precisions: 0.70-0.77); state 5.88e-3 | 0.82 (the
# gate dropped; 1.26 the rotation: bf16 activations set the served
# reading, which a bfloat16 STATE reads too, 5.5e-3); reuse 0.0 |
# 6.2e-2. What holds the state's and the read's PRECISION with room is
# `max` (3.5 times over its limit, 10 times the served path's worst);
# `mean` holds them too, by 1.3 times at the least.
BRUMBY_TOL = {"mean": 4e-3, "max": 0.2, "state": 5e-2, "reuse": 1e-3}
BRUMBY_START, BRUMBY_EDGE, BRUMBY_LAST = 8, 3, 128
# (slot, prompt tokens): both prompt classes of longreply16-closed, and a
# second request in slot 1 once its first has finished (its twin is added
# beside it)
BRUMBY_JOBS = ((0, 8100), (1, 2000), (1, 1950))
BRUMBY_DECODE = 24

# LongCat-Flash-Chat (benchmarks/configs/longcat-flash-int8-share32,
# model_type longcat_flash) goes the way Ling does (drive_jobs: 32 rows
# in every step, one 512-token window a step, then 24 decode steps a
# request) against cake_tpu/models/reference/longcat_flash.py on
# teacher-forced experts. Limits: `mean` and `max`, |error| / the
# reference's range over the compared positions (a request's first 8,
# the 3 behind its first window edge, its last 128 prompt positions,
# every decode step); `probe` and `probe_window`, the two latent kernels
# THEMSELVES at 64 heads against exact float32 attention over the pages
# the served path wrote, under DeepSeek-V2's limit (DSV2_TOL["probe"]:
# not loosened), where exact attention with a bfloat16 softmax must read
# past it; `agree_same_input`, the share of compared positions where the
# reference's router on the served path's own input to the first
# layer's MoE chooses the served path's 12, and `router_logit_err`.
# `moe_err`: the first layer's MoE (ops/moe.moe_mlp as the trunk calls
# it) over the compared positions' OWN inputs as one batch against the
# reference's moe_ffn on the same inputs and experts, relative error,
# root of summed squares: what holds a routing weight that is a few per
# cent off (the choice bias added to it), which moves the logits by a
# twentieth of the served path's own rounding.
# `agree` (along the forced trajectory) and `nearer` are reported. Each
# limit lies between the worst the served path read on the chip over
# seeds 0 / 1 / 2 and the LEAST an altered reference that it has to hold
# out read there (my chip runs, PR 65; served | must fail; the cell's
# cell.json, `chip_compare`, has every reading): mean 1.98e-3 / 2.02e-3
# / 1.99e-3 | 6.82e-3 (int8 activations; every other alteration of the
# layer 4.4e-2 to 0.13); max 1.38e-2 / 1.44e-2 / 1.41e-2 | 4.76e-2;
# moe_err 1.71e-3 / 1.67e-3 / 1.67e-3 | 1.94e-2 (the bias in the weight,
# whose logits read mean 2.09e-3, `nearer` 1.06: the logits cannot hold
# it; renormalised 0.88, the zero experts dropped 9.99); probe 1.70e-3 /
# 1.67e-3 / 1.72e-3 and probe_window 1.69e-3 (all three) | 1.26e-2 and
# 1.43e-2 (exact attention with a bfloat16 softmax, whose logits read
# mean 2.05e-3: the probes alone hold it); agree_same_input 1.0;
# router_logit_err 1.9e-6.
LONGCAT_TOL = {"mean": 3.5e-3, "max": 2.6e-2, "moe_err": 6e-3,
               "router_logit_err": 1e-3}
LONGCAT_AGREE = 0.9
LONGCAT_START, LONGCAT_EDGE, LONGCAT_LAST = 8, 3, 128
# (slot, prompt tokens): the cell's two prompt classes
LONGCAT_JOBS = ((0, 8100), (1, 2000))
LONGCAT_DECODE = 24
# the altered references: each must fail a limit
LONGCAT_NEGATIVES = {
    "bf16_softmax": dict(softmax_dtype="bfloat16"),
    "int8_activations": dict(int8_activations=True),
    "zero_experts_dropped": dict(zero_experts=False),
    "renormalised": dict(norm_topk_prob=True),
    "no_q_scale": dict(mla_scale_q_lora=False),
    "no_kv_scale": dict(mla_scale_kv_lora=False),
    "tapped_from_the_second_sublayer": dict(tap=1),
    "returned_before_the_second_sublayer": dict(back=0),
    "bias_in_the_weight": dict(bias_in_weight=True),
}

MEAN_TOL = 1.6e-3   # mean |error| / range, all compared entries
MAX_TOL = 3e-2      # worst entry / range
PROMPTS = (100, 352, 736, 1248, 1792, 65, 384, 1000)
N_DECODE = 32
# KeyeVL2: 8 layers, two discrete choices a layer (2,048 keys of up to
# 33k, 8 experts of 128). Both are TEACHER-FORCED in the reference (the
# served path's experts and key sets at every position), so that the
# logits read the arithmetic and not a flipped choice, and each choice
# is compared on its own on the forced trajectory: `keys`, the least
# layer's mean share of a query's set that both sides chose (positions
# past topk), and `margin`, the worst disagreement's distance from the
# reference's own threshold (its 2,048th score) in units of the
# visible scores' standard deviation: a key chosen on one side alone
# must be a near-tie, a bfloat16 rounding of the index query and key
# wide. Limits: KEYE_TOL, each between the served path's worst reading
# over seeds and the least an altered reference read (cell.json
# `chip_compare.limits_why` has the readings, PR 60).
KEYE_TOL = {"mean_dense": 1.1e-3, "mean": 1.2e-3, "max": 1.0e-2,
            "margin": 0.27}
KEYE_KEYS = 0.972
KEYE_PROMPTS = (32700, 16300, 8200, 1900)
KEYE_DECODE = 32

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


def build_engine(rehearse: bool, config_dir: str = CONFIG_DIR):
    with open(os.path.join(config_dir, "config.json")) as f:
        config = json.load(f)
    with open(os.path.join(config_dir, "cell.json")) as f:
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
    ap.add_argument("config_dir", nargs="?", default=CONFIG_DIR,
                    help="the cell's directory under benchmarks/configs")
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

    engine, cell, raw_config = build_engine(args.rehearse, args.config_dir)
    if raw_config.get("model_type") == "glm_moe_dsa":
        return compare_glm(engine, cell, args, t_start)
    if raw_config.get("model_type") == "dots3_note":
        return compare_dots3(engine, cell, args, t_start)
    if raw_config.get("model_type") == "deepseek_v2":
        return compare_deepseek_v2(engine, cell, args, t_start)
    if raw_config.get("model_type") == "nemotron_h":
        return compare_nemotron(engine, cell, args, t_start)
    if raw_config.get("model_type") == "zaya":
        return compare_zaya(engine, cell, args, t_start)
    if raw_config.get("model_type") == "bailing_hybrid":
        return compare_ling(engine, cell, args, t_start)
    if raw_config.get("model_type") == "exaone_moe":
        return compare_exaone_moe(engine, cell, args, t_start)
    if raw_config.get("model_type") == "granitemoehybrid":
        return compare_granite(engine, cell, args, t_start)
    if raw_config.get("model_type") == "KeyeVL2":
        return compare_keye(engine, cell, args, t_start)
    if raw_config.get("model_type") == "brumby":
        return compare_brumby(engine, cell, args, t_start)
    if raw_config.get("model_type") == "longcat_flash":
        return compare_longcat(engine, cell, args, t_start)
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


# -- deepseek_v2 ---------------------------------------------------------------


def probe_rel(a, b) -> float:
    """|a - b| / |b|, the probes' reading."""
    return float(np.sqrt(np.sum(np.square(a - b))
                         / max(np.sum(np.square(b)), 1e-300)))


def latent_pages_probe(pool, table, row: int, n_keys: int, geo, attn: str,
                       seed: int):
    """The page-walking kernel ITSELF (`cake_mla_decode_attn` under
    attn "pallas") against exact attention over the pages the served
    path wrote: layer 0 of the latent pool, `row`'s first n_keys keys,
    queries drawn so that the scores' standard deviation is
    DSV2_PROBE_SPREAD, every other row idle. -> ({"served",
    "bf16_softmax"}: relative errors against float32 softmax attention,
    the second that of exact attention whose scores and probabilities
    are rounded to bfloat16, which must read past the limit; the row's
    keys [S, row width]; their root mean square norm)."""
    import jax
    import jax.numpy as jnp

    from cake_tpu.ops import mla_attention as mla

    table = np.asarray(table)
    B, per_row = table.shape
    page, row_w, R = pool.shape[2], pool.shape[-1], geo.kv_lora_rank
    keys = pool[0, jnp.asarray(table[row, :-(-n_keys // page)])].reshape(
        -1, row_w)[:n_keys]                                     # [S, row]
    norm = float(jnp.sqrt(jnp.mean(jnp.sum(jnp.square(
        keys.astype(jnp.float32)), axis=-1))))
    q = (jax.random.normal(jax.random.PRNGKey(seed + 1),
                           (B, geo.heads, row_w), jnp.float32)
         * (DSV2_PROBE_SPREAD / (norm * geo.softmax_scale))
         ).astype(pool.dtype)
    probe_pos = np.full(B, -1, np.int32)
    probe_pos[row] = n_keys - 1
    probe_table = np.full((B, per_row), -1, np.int32)
    probe_table[row] = table[row]

    def exact(scores_dtype):
        """Softmax attention of the row's queries over its keys, float32
        at `highest` but for the scores and probabilities' type."""
        with jax.default_matmul_precision("highest"):
            kf, qf = keys.astype(jnp.float32), q[row].astype(jnp.float32)
            s = ((qf @ kf.T) * geo.softmax_scale).astype(scores_dtype)
            p = jax.nn.softmax(s, axis=-1).astype(jnp.float32)
            return np.asarray(p @ kf[:, :R], np.float64)

    served_attn = np.asarray(mla.attend_pages(
        q, pool, 0, jnp.asarray(probe_table), jnp.asarray(probe_pos), R,
        geo.softmax_scale, impl=attn)[row], np.float64)
    want_attn = exact(jnp.float32)

    probe = {"served": probe_rel(served_attn, want_attn),
             "bf16_softmax": probe_rel(exact(jnp.bfloat16), want_attn)}
    say(f"probe: kernel {probe['served']:.3e}, exact attention with a "
        f"bfloat16 softmax {probe['bf16_softmax']:.3e}")
    return probe, keys, norm


def compare_deepseek_v2(engine, cell, args, t_start) -> int:
    """The comparison above for latent attention over every live page
    and group-limited routing: the engine's own mixed and decode trunks
    with the head at every position, against
    models/reference/deepseek_v2.py on teacher-forced experts."""
    import jax
    import jax.numpy as jnp

    from cake_tpu.models.llama import paged
    from cake_tpu.models.moe import glm_dsa
    from cake_tpu.models.reference import deepseek_v2 as ref
    from cake_tpu.ops.moe import LayerOf
    from cake_tpu.ops.quant import qmatmul

    cfg, params, rope = engine.config, engine.params, engine.rope
    impl = {k: engine._step_impl(k) for k in ("mixed", "decode")}
    say(f"device {jax.devices()[0].device_kind}; attention {impl}; "
        f"engine built in {time.monotonic() - t_start:.1f} s")
    if not args.rehearse and impl != cell["expect_impl"]:
        say(f"FAILED: expected attention {cell['expect_impl']}")
        return 1
    attn = engine.attn_impl["mixed"]

    @partial(jax.jit, static_argnames=("n_tokens",),
             donate_argnames=("cache",))
    def window_step(params, tokens, pos, q_len, active, cache, n_tokens):
        out, _ = glm_dsa.mixed_trunk(params, tokens, pos, q_len, active,
                                     cache, rope, cfg, attn, n_tokens)
        logits = qmatmul(out.x, params["lm_head"]).astype(jnp.float32)
        return logits, out.cache, out.experts

    @partial(jax.jit, donate_argnames=("cache",))
    def decode_step(params, tokens, pos, active, cache):
        out = glm_dsa.decode_trunk(params, tokens, cache, pos, active, rope,
                                   cfg, attn)
        logits = qmatmul(out.x, params["lm_head"]).astype(jnp.float32)
        return logits, out.cache, out.experts

    B, C = engine.max_slots, engine._mixed_chunk
    page, per_row = engine.cache.page_size, engine.cache.table.shape[1]
    prompts = DSV2_PROMPTS if not args.rehearse else (300, 140, 30)
    n_decode = DSV2_DECODE if not args.rehearse else 8
    last = DSV2_LAST if not args.rehearse else 24
    rng = np.random.default_rng(args.seed)
    sequences = [rng.integers(0, cfg.vocab_size, p + n_decode)
                 for p in prompts]
    n_seq = len(sequences)
    assert n_seq <= B and max(prompts) + n_decode <= per_row * page
    table = np.full((B, per_row), -1, np.int32)
    at = 1
    for b, seq in enumerate(sequences):
        n = -(-len(seq) // page)
        table[b, :n] = at + np.arange(n)
        at += n
    assert at <= engine.cache.n_pages
    cache = engine.cache._replace(table=jnp.asarray(table))
    engine.cache = None

    got = [dict() for _ in sequences]       # position -> logits [V]
    off = [0] * n_seq
    Ls, k = len(cfg.sparse_layers), cfg.num_experts_per_tok
    # every position's experts, for the teacher-forced reference
    all_routed = [np.zeros((Ls, len(seq), k), np.int32)
                  for seq in sequences]

    def compared(b, position):
        return position >= prompts[b] - last

    steps = {"mixed": 0, "decode": 0}
    t0 = time.monotonic()
    while any(off[b] < prompts[b] for b in range(n_seq)):
        toks = np.zeros((B, C), np.int32)
        pos = np.zeros(B, np.int32)
        qlen = np.zeros(B, np.int32)
        for b, seq in enumerate(sequences):
            if off[b] < prompts[b]:
                n = min(C, prompts[b] - off[b])
            elif off[b] < prompts[b] + n_decode // 2:
                n = 1          # half the decode steps ride mixed steps
            else:
                continue
            toks[b, :n], pos[b], qlen[b] = seq[off[b]:off[b] + n], off[b], n
        active = qlen > 0
        for group in engine._mixed_groups(qlen):
            glen = np.where(group, qlen, 0)
            n_tokens = paged.mixed_bucket_for(engine._mixed_buckets,
                                              int(glen.sum()))
            logits, cache, experts = window_step(
                params, jnp.asarray(toks), jnp.asarray(pos),
                jnp.asarray(glen), jnp.asarray(active & group), cache,
                n_tokens)
            first = np.cumsum(glen) - glen
            experts = np.asarray(experts)
            wanted = []
            for b in np.flatnonzero(glen):
                all_routed[b][:, off[b]:off[b] + glen[b]] = experts[
                    :, first[b]:first[b] + glen[b]]
                wanted += [(b, j) for j in range(glen[b])
                           if compared(b, off[b] + j)]
            if wanted:
                fetched = np.asarray(logits[np.asarray(
                    [first[b] + j for b, j in wanted])])
                for i, (b, j) in enumerate(wanted):
                    got[b][off[b] + j] = fetched[i]
        for b in range(n_seq):
            off[b] += int(qlen[b])
        steps["mixed"] += 1
    while any(off[b] < len(s) for b, s in enumerate(sequences)):
        toks = np.zeros((B, 1), np.int32)
        pos = np.zeros(B, np.int32)
        active = np.zeros(B, bool)
        for b, seq in enumerate(sequences):
            if off[b] < len(seq):
                toks[b, 0], pos[b], active[b] = seq[off[b]], off[b], True
        logits, cache, experts = decode_step(
            params, jnp.asarray(toks), jnp.asarray(pos), jnp.asarray(active),
            cache)
        logits, experts = np.asarray(logits), np.asarray(experts)
        for b in np.flatnonzero(active):
            got[b][off[b]] = logits[b]
            all_routed[b][:, off[b]] = experts[:, b]
            off[b] += 1
        steps["decode"] += 1
    say(f"served path: {steps['mixed']} mixed and {steps['decode']} decode "
        f"steps in {time.monotonic() - t0:.1f} s")

    # -- the probe: the page-walking kernel itself against exact
    # attention over the pages the served path wrote (layer 0, row 0:
    # the longest), for queries whose scores spread widely
    geo = cfg.geometry(0)
    n_keys = len(sequences[0])
    probe, keys, norm = latent_pages_probe(cache.k, table, 0, n_keys, geo,
                                           attn, args.seed)

    # -- the same for the WINDOW pass (cake_mla_window_attn under
    # causality: all of prefill): the row's last C positions as one
    # window over the same pages, queries drawn the same way
    probe_window = latent_window_probe(cache.k, table, 0, n_keys, C, geo,
                                       attn, args.seed, keys, norm)

    # -- the reference: the served weights leave the device, then come
    # back dequantized one layer at a time --------------------------------
    del cache, keys
    host = jax.device_get(params)
    engine.params = params = None
    y = cfg.rope_scaling
    ref_cfg = {k_: getattr(cfg, k_) for k_ in (
        "num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim",
        "v_head_dim", "rms_norm_eps", "rope_theta", "n_group", "topk_group",
        "num_experts_per_tok", "norm_topk_prob", "routed_scaling_factor")}
    ref_cfg["rope_scaling"] = y and {
        "factor": y.factor, "original_max_position_embeddings": y.original,
        "beta_fast": y.beta_fast, "beta_slow": y.beta_slow,
        "mscale": y.mscale, "mscale_all_dim": y.mscale_all_dim}
    held = (cfg.first_routed_expert, cfg.num_local_experts)
    ref.attend_block = jax.jit(ref.attend_block,
                               static_argnames=("scale", "dtype"))
    ref.swiglu = jax.jit(ref.swiglu)

    def layers():
        for i in range(cfg.num_hidden_layers):
            lp = glm_dsa.layer_leaves(host["blocks"], cfg, i)
            yield {k_: dequantized(jax.tree.map(
                       lambda a: jnp.asarray(a[int(v.layer)]), v.stacked)
                       if isinstance(v, LayerOf)
                       else jax.tree.map(jnp.asarray, v))
                   for k_, v in lp.items()}

    top = {k_: dequantized(jax.tree.map(jnp.asarray, host[k_]))
           for k_ in ("embed", "final_norm", "lm_head")}

    def reference(which, config=ref_cfg):
        """The reference over the sequences `which`, TEACHER-FORCED in
        its experts; `routing` receives its own choice along that
        trajectory."""
        t0 = time.monotonic()
        seqs = [sequences[b] for b in which]
        routing = [[] for _ in seqs]
        logits = ref.forward(top, seqs, config, layers=layers(), held=held,
                             routing=routing,
                             forced=[list(all_routed[b]) for b in which])
        say(f"  reference over {sum(len(s_) for s_ in seqs)} tokens in "
            f"{time.monotonic() - t0:.1f} s")
        return dict(zip(which, ([np.asarray(x) for x in logits]))), dict(
            zip(which, routing))

    def readings(which, logits_of, routing_of, against=None):
        """mean / max |error| / range over the compared positions of the
        rows `which`, of the served logits against `logits_of`; `agree`:
        the least over the sparse layers of the share of compared
        positions where `routing_of`'s sets are the served path's;
        against: the plain reference's logits; `nearer` is then the
        served path's distance from `logits_of` over its distance from
        the plain reference (reported)."""
        err_sum = n = worst = 0.0
        same = np.zeros(Ls)
        count = 0
        to_this = to_plain = 0.0
        for b in which:
            for position, logits in sorted(got[b].items()):
                w = logits_of[b][position]
                err = np.abs(logits - w)
                scale = float(w.max() - w.min())
                err_sum += float(err.sum()) / scale
                n += err.size
                worst = max(worst, float(err.max()) / scale)
                same += [set(all_routed[b][layer, position].tolist())
                         == set(routing_of[b][layer][position].tolist())
                         for layer in range(Ls)]
                count += 1
                if against is not None:
                    to_this += float(np.sum(np.square(logits - w)))
                    to_plain += float(np.sum(np.square(
                        logits - against[b][position])))
        out = {"mean": err_sum / n, "max": worst,
               "agree": float(same.min()) / count, "positions": count}
        if against is not None:
            out["nearer"] = (to_this / max(to_plain, 1e-300)) ** 0.5
        return out

    want, want_routing = reference(list(range(n_seq)))
    served = readings(range(n_seq), want, want_routing)
    served["probe"] = probe["served"]
    served["probe_window"] = probe_window["served"]
    expected = sum(min(last, p) + n_decode for p in prompts)
    result = {
        "served": served, "expected_positions": expected, "tol": DSV2_TOL,
        "seed": args.seed, "prompts": list(prompts), "steps": steps,
        "attention": impl, "device": jax.devices()[0].device_kind,
        "longest_context": len(sequences[0]),
    }

    def passes(r):
        return (r["mean"] < DSV2_TOL["mean"] and r["max"] < DSV2_TOL["max"]
                and r["agree"] > DSV2_TOL["agree"]
                and r["probe"] < DSV2_TOL["probe"]
                and r["probe_window"] < DSV2_TOL["probe"])

    ok = served["positions"] == expected and passes(served)
    if not ok:
        say("FAILED: the served path is outside the tolerance")
    # -- what must NOT pass: the reference, altered, read as the served
    # path is (on the shortest rows; the probe's reading is the served
    # kernel's but for the softmax's own negative)
    if args.negatives:
        short = sorted(range(n_seq), key=lambda b: len(sequences[b]))[
            :args.negatives]
        negatives = {
            "bf16_softmax": dict(softmax_dtype="bfloat16"),
            "scale_without_mscale": dict(mscale_in_scale=False),
            "plain_rope": dict(yarn=False),
            "no_group_mask": dict(group_limited=False),
            "renormalised": dict(norm_topk_prob=True),
            "no_routed_scale": dict(routed_scaling_factor=1.0)}
        result["must_fail"] = {}
        for name, switch in negatives.items():
            say(f"negative: {name}")
            logits, routing = reference(short, dict(ref_cfg, **switch))
            r = readings(short, logits, routing, against=want)
            r["probe"] = probe.get(name, probe["served"])
            r["probe_window"] = probe_window.get(name,
                                                 probe_window["served"])
            result["must_fail"][name] = r
            if passes(r):
                say(f"FAILED: the reference with {name} passes the "
                    "tolerance")
                ok = False
    result["ok"] = bool(ok) or bool(args.rehearse and served["positions"]
                                    == expected)
    result["seconds"] = round(time.monotonic() - t_start, 1)
    with open(os.path.join(OUT_DIR, f"result_dsv2_seed{args.seed}.json"),
              "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result), flush=True)
    return 0 if result["ok"] else 1


# -- glm_moe_dsa ---------------------------------------------------------------


def compare_glm(engine, cell, args, t_start) -> int:
    """The comparison above for latent attention with the sparse
    indexer: the engine's own mixed and decode trunks with the head at
    every position, against models/reference/glm_moe_dsa.py."""
    import jax
    import jax.numpy as jnp

    from cake_tpu.models.llama import paged
    from cake_tpu.models.moe import glm_dsa
    from cake_tpu.models.reference import glm_moe_dsa as ref
    from cake_tpu.ops.quant import qmatmul

    cfg, params, rope = engine.config, engine.params, engine.rope
    impl = {k: engine._step_impl(k) for k in ("mixed", "decode")}
    say(f"device {jax.devices()[0].device_kind}; attention {impl}; "
        f"engine built in {time.monotonic() - t_start:.1f} s")
    if not args.rehearse and impl != cell["expect_impl"]:
        say(f"FAILED: expected attention {cell['expect_impl']}")
        return 1
    attn = engine.attn_impl["mixed"]

    @partial(jax.jit, static_argnames=("n_tokens",),
             donate_argnames=("cache",))
    def window_step(params, tokens, pos, q_len, active, cache, n_tokens):
        out, _ = glm_dsa.mixed_trunk(params, tokens, pos, q_len, active,
                                     cache, rope, cfg, attn, n_tokens)
        logits = qmatmul(out.x, params["lm_head"]).astype(jnp.float32)
        return (logits, out.cache, out.experts, out.selected,
                out.n_selected, out.selected_window)

    @partial(jax.jit, donate_argnames=("cache",))
    def decode_step(params, tokens, pos, active, cache):
        out = glm_dsa.decode_trunk(params, tokens, cache, pos, active, rope,
                                   cfg, attn)
        logits = qmatmul(out.x, params["lm_head"]).astype(jnp.float32)
        return logits, out.cache, out.experts, out.selected, out.n_selected

    B, C = engine.max_slots, engine._mixed_chunk
    page, per_row = engine.cache.page_size, engine.cache.table.shape[1]
    prompts = GLM_PROMPTS if not args.rehearse else (700, 400, 330, 300)
    n_decode = GLM_DECODE if not args.rehearse else 6
    last = LAST if not args.rehearse else 24
    rng = np.random.default_rng(args.seed)
    sequences = [rng.integers(0, cfg.vocab_size, p + n_decode)
                 for p in prompts]
    assert len(sequences) <= B and max(prompts) + n_decode <= per_row * page
    table = np.full((B, per_row), -1, np.int32)
    for b in range(len(sequences)):
        table[b] = b * per_row + np.arange(per_row)
    assert table.max() < engine.cache.n_pages
    cache = engine.cache._replace(table=jnp.asarray(table))
    engine.cache = None

    got = [dict() for _ in sequences]       # position -> logits [V]
    routed = [dict() for _ in sequences]    # position -> experts [Ls, k]
    picked = [dict() for _ in sequences]    # position -> [Lf] key sets
    off = [0] * len(sequences)

    def keep(b, position, logits, experts_t, selected, n_sel, window=None):
        """window: (the dispatch's window sets [Lf, C, S], the token's
        index in it) for a window's token; a row's single token reads
        its row of `selected` [Lf, B, K]."""
        got[b][position] = logits
        routed[b][position] = experts_t
        if window is None:
            picked[b][position] = [set(selected[f, b, :n_sel[b]].tolist())
                                   for f in range(selected.shape[0])]
        else:
            sets, col = window
            picked[b][position] = [set(np.flatnonzero(sets[f, col]).tolist())
                                   for f in range(sets.shape[0])]

    def compared(b, position):
        """The prompt's last positions (beyond index_topk: every query
        there dropped keys) and the last under index_topk (the dense
        regime, where precision is read)."""
        return (position >= prompts[b] - last
                or cfg.index_topk - last <= position < cfg.index_topk)

    steps = {"mixed": 0, "decode": 0}
    t0 = time.monotonic()
    while any(off[b] < prompts[b] for b in range(len(sequences))):
        toks = np.zeros((B, C), np.int32)
        pos = np.zeros(B, np.int32)
        qlen = np.zeros(B, np.int32)
        for b, seq in enumerate(sequences):
            if off[b] < prompts[b]:
                n = min(C, prompts[b] - off[b])
            elif off[b] < prompts[b] + n_decode // 2:
                n = 1          # half the decode steps ride mixed steps
            else:
                continue
            toks[b, :n], pos[b], qlen[b] = seq[off[b]:off[b] + n], off[b], n
        active = qlen > 0
        # the dispatches the engine would run this step in: one window
        # (a row of several tokens) each, the one-token rows beside it
        for group in engine._mixed_groups(qlen):
            glen = np.where(group, qlen, 0)
            n_tokens = paged.mixed_bucket_for(engine._mixed_buckets,
                                              int(glen.sum()))
            logits, cache, experts, selected, n_sel, sets = window_step(
                params, jnp.asarray(toks), jnp.asarray(pos),
                jnp.asarray(glen), jnp.asarray(active & group), cache,
                n_tokens)
            first = np.cumsum(glen) - glen
            wanted = [(b, j) for b in np.flatnonzero(glen)
                      for j in range(glen[b]) if compared(b, off[b] + j)]
            if wanted:
                experts, selected, n_sel, sets = (
                    np.asarray(experts), np.asarray(selected),
                    np.asarray(n_sel), np.asarray(sets))
                rows = np.asarray([first[b] + j for b, j in wanted])
                fetched = np.asarray(logits[rows])
                for i, (b, j) in enumerate(wanted):
                    keep(b, off[b] + j, fetched[i],
                         experts[:, first[b] + j], selected, n_sel,
                         (sets, j) if glen[b] > 1 else None)
        for b in range(len(sequences)):
            off[b] += int(qlen[b])
        steps["mixed"] += 1
    while any(off[b] < len(s) for b, s in enumerate(sequences)):
        toks = np.zeros((B, 1), np.int32)
        pos = np.zeros(B, np.int32)
        active = np.zeros(B, bool)
        for b, seq in enumerate(sequences):
            if off[b] < len(seq):
                toks[b, 0], pos[b], active[b] = seq[off[b]], off[b], True
        logits, cache, experts, selected, n_sel = decode_step(
            params, jnp.asarray(toks), jnp.asarray(pos), jnp.asarray(active),
            cache)
        logits, experts, selected, n_sel = (
            np.asarray(logits), np.asarray(experts), np.asarray(selected),
            np.asarray(n_sel))
        for b in np.flatnonzero(active):
            keep(b, off[b], logits[b], experts[:, b], selected, n_sel)
            off[b] += 1
        steps["decode"] += 1
    say(f"served path: {steps['mixed']} mixed and {steps['decode']} decode "
        f"steps in {time.monotonic() - t0:.1f} s")

    # -- the reference: the served weights leave the device, then come
    # back dequantized one layer at a time --------------------------------
    del cache
    host = jax.device_get(params)
    engine.params = params = None
    ref_cfg = {k: getattr(cfg, k) for k in (
        "num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim",
        "v_head_dim", "rms_norm_eps", "rope_theta", "index_n_heads",
        "index_head_dim", "index_topk", "num_experts_per_tok",
        "norm_topk_prob", "routed_scaling_factor", "scoring_func")}
    held = (cfg.first_routed_expert, cfg.num_local_experts)
    plain = {"attention": ref.attention, "swiglu": ref.swiglu,
             "index_scores": ref.index_scores, "select": ref.select,
             "mm": ref.mm}

    def compiled(**replaced):
        """The reference's heavy functions under jit, traced anew (so
        that a replaced `mm` is what they run); one compilation per
        sequence length, function and config, not one per operation."""
        for name, fn in dict(plain, **replaced).items():
            setattr(ref, name, fn)
        attention, index_scores = ref.attention, ref.index_scores
        jitted = {}

        def key_of(name, config):
            return name, tuple(sorted(config.items()))

        def jit_attention(lp, h, config, selected):
            key = key_of("attention", config)
            if key not in jitted:
                jitted[key] = jax.jit(
                    lambda lp, h, selected: attention(lp, h, config,
                                                      selected))
            return jitted[key](lp, h, selected)

        def jit_index_scores(lp, h, c_q, config):
            key = key_of("index_scores", config)
            if key not in jitted:
                jitted[key] = jax.jit(
                    lambda lp, h, c_q: index_scores(lp, h, c_q, config))
            return jitted[key](lp, h, c_q)

        ref.attention, ref.index_scores = jit_attention, jit_index_scores
        ref.swiglu = jax.jit(ref.swiglu)
        ref.select = jax.jit(ref.select, static_argnames=("topk",))

    def layers(fresh_indexers: bool = False):
        from cake_tpu.ops.moe import LayerOf
        donor = None
        for i in range(cfg.num_hidden_layers):
            lp = glm_dsa.layer_leaves(host["blocks"], cfg, i)
            lp = {k: dequantized(jax.tree.map(
                      lambda a: jnp.asarray(a[int(v.layer)]), v.stacked)
                      if isinstance(v, LayerOf)
                      else jax.tree.map(jnp.asarray, v))
                  for k, v in lp.items()}
            if "wi_q" in lp:
                donor = lp
            elif fresh_indexers:
                # the nearest full layer's indexer shapes, drawn afresh
                keys = jax.random.split(jax.random.PRNGKey(1000 + i), 3)
                for key, name in zip(keys, ("wi_q", "wi_k", "wi_w")):
                    w = donor[name]
                    lp[name] = (jax.random.normal(key, w.shape, jnp.float32)
                                * jnp.std(w))
                lp["wi_k_norm"] = donor["wi_k_norm"]
                lp["wi_k_bias"] = donor["wi_k_bias"]
            yield lp

    top = {k: dequantized(jax.tree.map(jnp.asarray, host[k]))
           for k in ("embed", "final_norm", "lm_head")}

    def reference(seqs, config=ref_cfg, **kw):
        t0 = time.monotonic()
        routing = [[] for _ in seqs]
        selections = [[] for _ in seqs]
        logits = ref.forward(top, list(seqs), config, layers=layers(**kw),
                             held=held, routing=routing,
                             selections=selections)
        say(f"  reference over {sum(len(s) for s in seqs)} tokens in "
            f"{time.monotonic() - t0:.1f} s")
        return [np.asarray(x) for x in logits], routing, selections

    compiled()
    want, want_routing, want_keys = reference(sequences)
    full = {layer: f for f, layer in enumerate(cfg.full_layers)}

    def readings(rows, logits_at, experts_at, keys_at):
        """The limits' readings over compared positions. rows: (b,
        position) pairs; logits_at / experts_at (b, position) ->
        logits [V] / [Ls, k]; keys_at (b, position, layer) -> the set
        that layer attended. Against the reference: mean |error| /
        range in the dense regime (positions under index_topk: every
        visible key is attended, so only the expert choice can amplify
        a rounding) and beyond it, with their worst entries; the least,
        over attention layers, mean share of attended keys in common;
        each sparse layer's share of positions with the same experts."""
        dense = {"sum": 0.0, "n": 0, "worst": 0.0}
        sparse = {"sum": 0.0, "n": 0, "worst": 0.0}
        shared = np.zeros(cfg.num_hidden_layers)
        same = np.zeros(len(cfg.sparse_layers))
        n_sparse = 0
        for b, position in rows:
            w = want[b][position]
            err = np.abs(logits_at(b, position) - w) / float(w.max() - w.min())
            acc = dense if position < cfg.index_topk else sparse
            acc["sum"] += float(err.sum())
            acc["n"] += err.size
            acc["worst"] = max(acc["worst"], float(err.max()))
            same += [set(experts_at(b, position)[j])
                     == set(want_routing[b][j][position])
                     for j in range(len(cfg.sparse_layers))]
            if position >= cfg.index_topk:
                n_sparse += 1
                for layer in range(cfg.num_hidden_layers):
                    theirs = set(np.flatnonzero(
                        want_keys[b][layer][position]).tolist())
                    ours = keys_at(b, position, layer)
                    shared[layer] += (len(ours & theirs)
                                      / max(len(ours), len(theirs)))
        return {
            "mean_dense": dense["sum"] / max(dense["n"], 1),
            "max_dense": dense["worst"],
            "mean": sparse["sum"] / max(sparse["n"], 1),
            "max": sparse["worst"],
            "keys": float(shared.min()) / max(n_sparse, 1),
            "keys_by_layer": [round(float(x) / max(n_sparse, 1), 4)
                              for x in shared],
            "same_experts_by_layer": [round(float(x) / len(rows), 4)
                                      for x in same],
        }

    def passes(r):
        return (r["mean_dense"] < GLM_TOL["mean_dense"]
                and r["mean"] < GLM_TOL["mean"]
                and r["keys"] >= GLM_TOL["keys"])

    def nearest_full(layer):
        return max(f for f in cfg.full_layers if f <= layer)

    rows = [(b, position) for b in range(len(sequences))
            for position in sorted(got[b])]
    served = readings(
        rows, lambda b, p: got[b][p], lambda b, p: routed[b][p],
        lambda b, p, layer: picked[b][p][full[nearest_full(layer)]])
    expected = sum(min(last, p) + n_decode
                   + sum(1 for q in range(max(0, cfg.index_topk - last),
                                          min(cfg.index_topk, p - last)))
                   for p in prompts)
    result = {
        "positions": len(rows), "expected_positions": expected,
        "served": served, "tol": GLM_TOL, "seed": args.seed,
        "prompts": list(prompts), "steps": steps, "attention": impl,
        "device": jax.devices()[0].device_kind,
    }
    ok = len(rows) == expected and passes(served)

    # -- what must NOT pass: the reference, altered, against itself ----
    if args.negatives:
        short = sorted(range(len(sequences)),
                       key=lambda b: len(sequences[b]))[:args.negatives]
        seqs = [sequences[b] for b in short]
        neg_rows = [(b, position) for b, position in rows if b in short]

        def against_reference(run):
            logits, routing, keys = run
            at = {b: i for i, b in enumerate(short)}
            return readings(
                neg_rows, lambda b, p: logits[at[b]][p],
                lambda b, p: [r[p] for r in routing[at[b]]],
                lambda b, p, layer: set(np.flatnonzero(
                    keys[at[b]][layer][p]).tolist()))

        altered = {
            "dense_attention_reference": dict(
                config=dict(ref_cfg, dense_attention=True)),
            "softmax_routing_reference": dict(
                config=dict(ref_cfg, scoring_func="softmax")),
            "fresh_shared_indexers_reference": dict(fresh_indexers=True),
        }
        for name, kw in altered.items():
            result[name] = against_reference(reference(seqs, **kw))
        compiled(mm=lambda x, w: plain["mm"](fake_int8(x), w))
        result["int8_activation_reference"] = against_reference(
            reference(seqs))
        compiled()
        for name in (*altered, "int8_activation_reference"):
            if passes(result[name]):
                say(f"FAILED: the {name} passes the tolerance")
                ok = False
    result["ok"] = bool(ok)
    result["seconds"] = round(time.monotonic() - t_start, 1)
    with open(os.path.join(OUT_DIR, f"glm_result_seed{args.seed}.json"),
              "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result), flush=True)
    return 0 if ok else 1


# -- dots3_note ----------------------------------------------------------------


def compare_dots3(engine, cell, args, t_start) -> int:
    """The comparison above for two kinds of latent layer over three
    pools: the engine's own mixed and decode trunks (the ring table as
    the engine maps it: R pages a row) with the head at every position,
    against models/reference/dots3_note.py."""
    import jax
    import jax.numpy as jnp

    from cake_tpu.models.llama import paged
    from cake_tpu.models.moe import glm_dsa
    from cake_tpu.models.reference import dots3_note as ref
    from cake_tpu.ops.moe import LayerOf
    from cake_tpu.ops.quant import qmatmul

    cfg, params, rope = engine.config, engine.params, engine.rope
    impl = {k: engine._step_impl(k) for k in ("mixed", "decode")}
    say(f"device {jax.devices()[0].device_kind}; attention {impl}; "
        f"engine built in {time.monotonic() - t_start:.1f} s")
    if not args.rehearse and impl != cell["expect_impl"]:
        say(f"FAILED: expected attention {cell['expect_impl']}")
        return 1
    attn = engine.attn_impl["mixed"]

    @partial(jax.jit, static_argnames=("n_tokens",),
             donate_argnames=("cache",))
    def window_step(params, tokens, pos, q_len, active, cache, n_tokens):
        out, _ = glm_dsa.mixed_trunk(params, tokens, pos, q_len, active,
                                     cache, rope, cfg, attn, n_tokens)
        logits = qmatmul(out.x, params["lm_head"]).astype(jnp.float32)
        return (logits, out.cache, out.experts, out.selected,
                out.n_selected, out.selected_window, out.probe)

    @partial(jax.jit, donate_argnames=("cache",))
    def decode_step(params, tokens, pos, active, cache):
        out = glm_dsa.decode_trunk(params, tokens, cache, pos, active, rope,
                                   cfg, attn)
        logits = qmatmul(out.x, params["lm_head"]).astype(jnp.float32)
        return (logits, out.cache, out.experts, out.selected,
                out.n_selected, out.probe)

    B, C = engine.max_slots, engine._mixed_chunk
    page, per_row = engine.cache.page_size, engine.cache.table.shape[1]
    R = engine.cache.ring_pages
    W, K = cfg.sliding_window_size, cfg.index_topk
    wrapped = R * page          # positions past it lie in a reused page
    prompts = DOTS3_PROMPTS if not args.rehearse else (620, 300, 140, 30)
    n_decode = DOTS3_DECODE if not args.rehearse else 8
    last = LAST if not args.rehearse else 24
    rng = np.random.default_rng(args.seed)
    sequences = [rng.integers(0, cfg.vocab_size, p + n_decode)
                 for p in prompts]
    n_seq = len(sequences)
    assert n_seq <= B and max(prompts) + n_decode <= per_row * page
    assert R == cfg.window_ring_pages(page, C)
    table = np.full((B, per_row), -1, np.int32)
    at = 0
    for b, seq in enumerate(sequences):
        n = -(-len(seq) // page)
        table[b, :n] = at + np.arange(n)
        at += n
    assert at <= engine.cache.n_pages
    cache = engine.cache._replace(table=jnp.asarray(table))
    engine.cache = None

    got = [dict() for _ in sequences]       # position -> logits [V]
    routed = [dict() for _ in sequences]    # position -> experts [Ls, k]
    picked = [dict() for _ in sequences]    # position -> [Lf] key sets
    shared_step = set()                     # (b, position): a decode row
    off = [0] * n_seq                       # beside another row's window
    # every position's experts, for the teacher-forced reference
    all_routed = [np.zeros((len(cfg.sparse_layers), len(seq),
                            cfg.num_experts_per_tok), np.int32)
                  for seq in sequences]
    # the first sliding layer from the inside (glm_dsa.TrunkOut.probe),
    # at EVERY position of every row but the longest: its normed input,
    # its attention's output, its FFN's normed input
    probe_rows = sorted(range(n_seq), key=lambda b: len(sequences[b]))[:-1]
    probed = {b: np.zeros((3, len(sequences[b]), cfg.hidden_size),
                          np.float32) for b in probe_rows}

    def keep(b, position, logits, experts_t, selected, n_sel, window=None):
        got[b][position] = logits
        routed[b][position] = experts_t
        if window is None:
            picked[b][position] = [set(selected[f, b, :n_sel[b]].tolist())
                                   for f in range(selected.shape[0])]
        else:
            sets, col = window
            picked[b][position] = [set(np.flatnonzero(sets[f, col]).tolist())
                                   for f in range(sets.shape[0])]

    def compared(b, position):
        """The prompt's last positions and every decode step; the last
        positions under index_topk (where precision is read); the first
        past the window (the band starts to cut) and the first past the
        ring's turn, in the rows that reach them."""
        return (position >= prompts[b] - last
                or K - last <= position < K
                or W - 8 <= position < W + 24
                or wrapped - 8 <= position < wrapped + 24)

    steps = {"mixed": 0, "decode": 0}
    t0 = time.monotonic()
    while any(off[b] < prompts[b] for b in range(n_seq)):
        toks = np.zeros((B, C), np.int32)
        pos = np.zeros(B, np.int32)
        qlen = np.zeros(B, np.int32)
        for b, seq in enumerate(sequences):
            if off[b] < prompts[b]:
                n = min(C, prompts[b] - off[b])
            elif off[b] < prompts[b] + n_decode // 2:
                n = 1          # half the decode steps ride mixed steps
            else:
                continue
            toks[b, :n], pos[b], qlen[b] = seq[off[b]:off[b] + n], off[b], n
        active = qlen > 0
        for group in engine._mixed_groups(qlen):
            glen = np.where(group, qlen, 0)
            n_tokens = paged.mixed_bucket_for(engine._mixed_buckets,
                                              int(glen.sum()))
            (logits, cache, experts, selected, n_sel, sets,
             probe) = window_step(
                params, jnp.asarray(toks), jnp.asarray(pos),
                jnp.asarray(glen), jnp.asarray(active & group), cache,
                n_tokens)
            first = np.cumsum(glen) - glen
            experts = np.asarray(experts)
            for b in np.flatnonzero(glen):
                all_routed[b][:, off[b]:off[b] + glen[b]] = experts[
                    :, first[b]:first[b] + glen[b]]
                if b in probed:
                    for i, tapped in enumerate(probe):
                        probed[b][i, off[b]:off[b] + glen[b]] = np.asarray(
                            tapped[first[b]:first[b] + glen[b]], np.float32)
            served_dtype = probe[2].dtype
            wanted = [(b, j) for b in np.flatnonzero(glen)
                      for j in range(glen[b]) if compared(b, off[b] + j)]
            if wanted:
                selected, n_sel, sets = (
                    np.asarray(selected), np.asarray(n_sel),
                    np.asarray(sets))
                rows = np.asarray([first[b] + j for b, j in wanted])
                fetched = np.asarray(logits[rows])
                for i, (b, j) in enumerate(wanted):
                    keep(b, off[b] + j, fetched[i],
                         experts[:, first[b] + j], selected, n_sel,
                         (sets, j) if glen[b] > 1 else None)
                    if glen[b] == 1 and glen.max() > 1:
                        shared_step.add((b, off[b] + j))
        for b in range(n_seq):
            off[b] += int(qlen[b])
        steps["mixed"] += 1
    while any(off[b] < len(s) for b, s in enumerate(sequences)):
        toks = np.zeros((B, 1), np.int32)
        pos = np.zeros(B, np.int32)
        active = np.zeros(B, bool)
        for b, seq in enumerate(sequences):
            if off[b] < len(seq):
                toks[b, 0], pos[b], active[b] = seq[off[b]], off[b], True
        logits, cache, experts, selected, n_sel, probe = decode_step(
            params, jnp.asarray(toks), jnp.asarray(pos), jnp.asarray(active),
            cache)
        probe = [np.asarray(tapped, np.float32) for tapped in probe]
        logits, experts, selected, n_sel = (
            np.asarray(logits), np.asarray(experts), np.asarray(selected),
            np.asarray(n_sel))
        for b in np.flatnonzero(active):
            keep(b, off[b], logits[b], experts[:, b], selected, n_sel)
            all_routed[b][:, off[b]] = experts[:, b]
            if b in probed:
                for i, tapped in enumerate(probe):
                    probed[b][i, off[b]] = tapped[b]
            off[b] += 1
        steps["decode"] += 1
    say(f"served path: {steps['mixed']} mixed and {steps['decode']} decode "
        f"steps in {time.monotonic() - t0:.1f} s")

    # -- the reference: the served weights leave the device, then come
    # back dequantized one layer at a time --------------------------------
    del cache
    host = jax.device_get(params)
    engine.params = params = None
    ref_cfg = {k: getattr(cfg, k) for k in (
        "hidden_size", "rms_norm_eps", "sliding_window_size",
        "num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim",
        "v_head_dim", "rope_theta", "swa_num_attention_heads",
        "swa_qk_nope_head_dim", "swa_qk_rope_head_dim", "swa_v_head_dim",
        "swa_rope_theta", "index_n_heads", "index_head_dim", "index_topk",
        "num_experts_per_tok", "norm_topk_prob", "routed_scaling_factor",
        "scoring_func")}
    ref_cfg["layer_types"] = cfg.indexer_types
    held = (cfg.first_routed_expert, cfg.num_local_experts)
    plain = {"attend_block": ref.attend_block, "index_block": ref.index_block,
             "select_block": ref.select_block, "swiglu": ref.swiglu,
             "router": ref.router}
    bf16 = lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)  # noqa: E731

    def compiled(low_precision: bool = False):
        """The reference's heavy functions under jit; low_precision:
        bfloat16 where the configuration says float32 (the attention
        scores before the softmax, the index scores, the router's
        scores)."""
        attend, index, route = (plain["attend_block"], plain["index_block"],
                                plain["router"])
        if low_precision:
            def attend(q_nope, q_pe, k_nope, k_pe, v, mask, scale):
                scores = (jnp.einsum("thd,shd->hts", q_nope, k_nope)
                          + jnp.einsum("thd,sd->hts", q_pe, k_pe)) * scale
                scores = jnp.where(mask[None], bf16(scores), ref.NEG)
                probs = bf16(jax.nn.softmax(scores, axis=-1))
                return jnp.einsum("hts,shd->thd", probs, v)

            def index(qI, kI, w):
                return bf16(plain["index_block"](qI, kI, w))

            def route(lp, h, config, forced=None):
                k = config["num_experts_per_tok"]
                scores = bf16(jax.nn.sigmoid(bf16(ref.mm(h, lp["router"]))))
                order = jnp.argsort(-(scores + lp["router_bias"]), axis=-1,
                                    stable=True)[:, :k]
                chosen = order if forced is None else jnp.asarray(forced)
                weights = jnp.take_along_axis(scores, chosen, axis=-1)
                weights = weights / (jnp.sum(weights, -1, keepdims=True)
                                     + 1e-20)
                return (weights * config["routed_scaling_factor"], chosen,
                        order)
        ref.attend_block = jax.jit(attend, static_argnames=("scale",))
        ref.index_block = jax.jit(index)
        ref.select_block = jax.jit(plain["select_block"],
                                   static_argnames=("topk",))
        ref.swiglu = jax.jit(plain["swiglu"])
        ref.router = route

    def layers():
        for i in range(cfg.num_hidden_layers):
            lp = glm_dsa.layer_leaves(host["blocks"], cfg, i)
            yield {k: dequantized(jax.tree.map(
                       lambda a: jnp.asarray(a[int(v.layer)]), v.stacked)
                       if isinstance(v, LayerOf)
                       else jax.tree.map(jnp.asarray, v))
                   for k, v in lp.items()}

    top = {k: dequantized(jax.tree.map(jnp.asarray, host[k]))
           for k in ("embed", "final_norm", "lm_head")}
    full = {layer: f for f, layer in enumerate(cfg.full_layers)}

    def reference(which, config=ref_cfg):
        """The reference over the sequences `which`, TEACHER-FORCED:
        every sparse layer computes the experts the served path chose
        for the token, weighed by its own scores; `routing` receives
        its own choice along that trajectory."""
        t0 = time.monotonic()
        seqs = [sequences[b] for b in which]
        routing = [[] for _ in seqs]
        selections = [[] for _ in seqs]
        logits = ref.forward(top, seqs, config, layers=layers(),
                             held=held, routing=routing,
                             selections=selections,
                             forced=[list(all_routed[b]) for b in which])
        # the full layers' sets alone are compared: drop the bands
        keys = [[m if i in full else None for i, m in enumerate(sel)]
                for sel in selections]
        say(f"  reference over {sum(len(s) for s in seqs)} tokens in "
            f"{time.monotonic() - t0:.1f} s")
        return [np.asarray(x) for x in logits], routing, keys

    # -- the probe: ONE sliding layer (the first) on the served path's
    # own inputs, so that the rounding of the layers before it and after
    # it is in neither side: its attention's output and its router
    p_layer = cfg.sliding_layers[0]
    p_sparse = cfg.sparse_layers.index(p_layer)
    lp = glm_dsa.layer_leaves(host["blocks"], cfg, p_layer)
    # (a layer holds the leaves its query path has: no `wq` beside the
    # low-rank three)
    p_leaves = {k: dequantized(jax.tree.map(jnp.asarray, lp[k]))
                for k in (*glm_dsa.ATTN_LEAVES, glm_dsa.GATE_LEAF, "router",
                          "router_bias") if k in lp}
    del lp

    def probe_run(config=ref_cfg, low_precision=False):
        """{row: (the layer's attention output [S, D], its router's own
        choice [S, k], its router's logits [S, E])} from the reference's
        functions as they stand (compiled() may have lowered them), on
        the inputs the served path had."""
        out = {}
        with jax.default_matmul_precision("highest"):
            for b in probe_rows:
                h_attn, h_mlp = (jnp.asarray(probed[b][i]) for i in (0, 2))
                logits = ref.mm(h_mlp, p_leaves["router"])
                out[b] = (
                    np.asarray(ref.attention(p_leaves, h_attn, config,
                                             "sliding")),
                    np.asarray(ref.router(p_leaves, h_mlp, config)[2]),
                    np.asarray(bf16(logits) if low_precision else logits))
        return out

    def probe_readings(rows, ours, theirs, against=None):
        """ours / theirs: {row: (attention output, experts chosen,
        router logits)} of the probed layer. `layer_err`: the root of
        the summed squares of the attention outputs' difference over
        that of theirs, at the compared positions; `agree_same_input`:
        the share of ALL positions whose expert sets are the same;
        `router_logit_err`: the worst difference of a router logit.
        against: a third run; `layer_nearer` is then the distance of
        `against` from ours over its distance from theirs, and
        `layer_nearer_if_served` the same ratio for an `against` that
        computed ours with the rounding it has."""
        diff = norm = same = count = 0.0
        worst = to_ours = to_theirs = if_served = 0.0
        for b in probe_rows:
            at = [position for r, position in rows if r == b]
            a, t = ours[b][0][at].astype(np.float64), theirs[b][0][at]
            diff += float(np.sum(np.square(a - t)))
            norm += float(np.sum(np.square(t)))
            same += sum(set(x) == set(y)
                        for x, y in zip(ours[b][1].tolist(),
                                        theirs[b][1].tolist()))
            count += len(ours[b][1])
            worst = max(worst, float(np.abs(ours[b][2] - theirs[b][2]).max()))
            if against is not None:
                d = against[b][0][at].astype(np.float64) - t
                to_ours += float(np.sum(np.square(d - (a - t))))
                to_theirs += float(np.sum(np.square(d)))
                if_served += float(np.sum(np.square(d + (a - t))))
        out = {"layer_err": (diff / max(norm, 1e-300)) ** 0.5,
               "agree_same_input": same / max(count, 1),
               "router_logit_err": worst}
        if against is not None:
            out["layer_nearer"] = (to_ours / max(to_theirs, 1e-300)) ** 0.5
            out["layer_nearer_if_served"] = (
                to_theirs / max(if_served, 1e-300)) ** 0.5
        return out

    compiled()
    want, want_routing, want_keys = reference(range(n_seq))
    want_probe = probe_run()
    # the served path's side of it: what the trunk handed out, the
    # experts it chose there, and the logits of ITS router function
    # (ops/moe.router_logits, what moe_mlp calls) on the same input
    from cake_tpu.ops.moe import router_logits
    served_probe = {
        b: (probed[b][1], all_routed[b][p_sparse],
            np.asarray(jax.jit(router_logits)(
                jnp.asarray(probed[b][2], served_dtype),
                jnp.asarray(host["blocks"]["router"][p_sparse]))))
        for b in probe_rows}

    def readings(rows, of, logits_at, experts_at, keys_at):
        """The limits' readings over compared positions. rows: (b,
        position) pairs; of: the reference run (logits, routing, keys)
        the rows are read against; logits_at / experts_at (b, position)
        -> logits [V] / [Ls, k]; keys_at (b, position, full layer) ->
        the set that layer attended. Mean |error| / range in the dense
        regime (positions under index_topk) and beyond it, with their
        worst entries; the dense regime past the ring's turn alone; the
        least, over the full layers, mean share of attended keys in
        common; `agree`, the least over the sparse layers of the share
        of positions whose expert sets are the same (under teacher
        forcing: no cascade)."""
        w_logits, w_routing, w_keys = of
        acc = {k: {"sum": 0.0, "n": 0, "worst": 0.0}
               for k in ("dense", "sparse", "wrapped", "shared_step")}
        in_common = np.zeros(len(cfg.full_layers))
        same = np.zeros(len(cfg.sparse_layers))
        n_sparse = 0
        for b, position in rows:
            w = w_logits[b][position]
            err = np.abs(logits_at(b, position) - w) / float(w.max() - w.min())
            kinds = ["dense" if position < K else "sparse"]
            if wrapped <= position < K:
                kinds.append("wrapped")
            if (b, position) in shared_step:
                kinds.append("shared_step")
            for kind in kinds:
                a = acc[kind]
                a["sum"] += float(err.sum())
                a["n"] += err.size
                a["worst"] = max(a["worst"], float(err.max()))
            same += [set(experts_at(b, position)[j])
                     == set(w_routing[b][j][position])
                     for j in range(len(cfg.sparse_layers))]
            if position >= K:
                n_sparse += 1
                for layer, f in full.items():
                    theirs = set(np.flatnonzero(
                        w_keys[b][layer][position]).tolist())
                    ours = keys_at(b, position, f)
                    in_common[f] += (len(ours & theirs)
                                     / max(len(ours), len(theirs)))
        mean = lambda a: a["sum"] / max(a["n"], 1)  # noqa: E731
        return {
            "mean_dense": mean(acc["dense"]),
            "max_dense": acc["dense"]["worst"],
            "mean": mean(acc["sparse"]), "max": acc["sparse"]["worst"],
            "mean_wrapped": mean(acc["wrapped"]),
            "mean_shared_step": mean(acc["shared_step"]),
            "keys": float(in_common.min()) / max(n_sparse, 1),
            "keys_by_layer": [round(float(x) / max(n_sparse, 1), 4)
                              for x in in_common],
            "agree": float(same.min()) / max(len(rows), 1),
            "same_experts_by_layer": [round(float(x) / max(len(rows), 1), 4)
                                      for x in same],
            "positions": {k: a["n"] // max(int(cfg.vocab_size), 1)
                          for k, a in acc.items()},
        }

    def failing(r):
        """The limits a reading fails, by name."""
        out = [k for k in ("mean_dense", "mean", "layer_err",
                           "router_logit_err") if r[k] >= DOTS3_TOL[k]]
        out += [k for k in ("keys", "agree", "agree_same_input")
                if r[k] < DOTS3_TOL[k]]
        # an altered reference only: the served path tells it from the
        # plain one
        return out + [k for k in ("nearer", "layer_nearer")
                      if r.get(k, 0.0) >= DOTS3_TOL[k]]

    rows = [(b, position) for b in range(n_seq)
            for position in sorted(got[b])]
    run = (want, want_routing, want_keys)
    served = readings(rows, run, lambda b, p: got[b][p],
                      lambda b, p: routed[b][p],
                      lambda b, p, f: picked[b][p][f])
    served.update(probe_readings(rows, served_probe, want_probe))
    expected = sum(sum(1 for q in range(p + n_decode) if compared(b, q))
                   for b, p in enumerate(prompts))
    must_hold = {
        "past_window": any(p >= W for _, p in rows),
        "ring_wrapped_prefilled": any(wrapped <= p < prompts[b]
                                      for b, p in rows),
        "ring_wrapped_decoded": any(p >= max(wrapped, prompts[b])
                                    for b, p in rows),
        "past_index_topk": any(p >= K for _, p in rows),
        "decode_row_beside_a_window": bool(shared_step),
    }
    result = {
        "positions": len(rows), "expected_positions": expected,
        "must_hold": must_hold, "served": served, "tol": DOTS3_TOL,
        "seed": args.seed, "prompts": list(prompts), "steps": steps,
        "attention": impl, "ring_pages": int(R),
        "probe": {"layer": int(p_layer), "rows": probe_rows},
        "device": jax.devices()[0].device_kind,
    }
    ok = (len(rows) == expected and not failing(served)
          and all(must_hold.values()))

    # -- what must NOT pass: the reference, altered, against itself ----
    if args.negatives:
        short = sorted(range(n_seq),
                       key=lambda b: len(sequences[b]))[:args.negatives]
        at = {b: i for i, b in enumerate(short)}
        neg_rows = [(at[b], position) for b, position in rows if b in short]
        plain_run = ([want[b] for b in short],
                     [want_routing[b] for b in short],
                     [want_keys[b] for b in short])

        def against_reference(altered_run, altered_probe):
            logits, routing, keys = altered_run
            r = readings(
                neg_rows, plain_run, lambda i, p: logits[i][p],
                lambda i, p: [r[p] for r in routing[i]],
                lambda i, p, f: set(np.flatnonzero(
                    keys[i][cfg.full_layers[f]][p]).tolist()))
            # the served path's distance from this reference over its
            # distance from the plain one (root of the summed squares)
            # and the same ratio had the served path computed THIS
            # reference with the rounding it has: altered + (served -
            # plain) against the two
            to_altered = to_plain = if_served = 0.0
            for i, p in neg_rows:
                plain_p = want[short[i]][p]
                d = got[short[i]][p].astype(np.float64) - plain_p
                e = logits[i][p].astype(np.float64) - plain_p
                to_altered += float(np.sum(np.square(d - e)))
                to_plain += float(np.sum(np.square(d)))
                if_served += float(np.sum(np.square(d + e)))
            r["nearer"] = (to_altered / max(to_plain, 1e-300)) ** 0.5
            r["nearer_if_served"] = (to_plain / max(if_served, 1e-300)) ** 0.5
            # the probed layer: the altered layer as if it were served,
            # and how far the served layer lies from it
            r.update(probe_readings(rows, altered_probe, want_probe,
                                    against=served_probe))
            return r

        altered = {
            "dense_attention_reference": dict(ref_cfg, dense_attention=True),
            "window_plus_one_reference": dict(ref_cfg,
                                              sliding_window_size=W + 1),
            "window_minus_one_reference": dict(ref_cfg,
                                               sliding_window_size=W - 1),
            "no_gate_reference": dict(ref_cfg, gate=False),
            "no_rescale_reference": dict(ref_cfg, rescale=False),
        }
        for name, config in altered.items():
            result[name] = against_reference(reference(short, config),
                                             probe_run(config))
        compiled(low_precision=True)
        result["bf16_scores_reference"] = against_reference(
            reference(short), probe_run(low_precision=True))
        compiled()
        result["fails"] = {}
        for name in (*altered, "bf16_scores_reference"):
            result["fails"][name] = failing(result[name])
            if not result["fails"][name]:
                say(f"FAILED: the {name} passes the tolerance")
                ok = False
    for name, fn in plain.items():
        setattr(ref, name, fn)
    result["ok"] = bool(ok)
    result["seconds"] = round(time.monotonic() - t_start, 1)
    with open(os.path.join(OUT_DIR, f"dots3_result_seed{args.seed}.json"),
              "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result), flush=True)
    return 0 if ok else 1


# -- nemotron_h ----------------------------------------------------------------


def compare_nemotron(engine, cell, args, t_start) -> int:
    """The comparison above for recurrent blocks beside the page pool:
    the engine's own mixed and decode trunks with the head at every
    position, against models/reference/nemotron_h.py. Jobs run a slot
    each, one window a dispatch with the rows that already decode
    beside it, the decode program when no row prefills; slot 1 takes a
    second request when its first has finished (the state it left
    behind must not reach the second)."""
    import jax
    import jax.numpy as jnp

    from cake_tpu.models.llama import paged
    from cake_tpu.models.moe import nemotron_h as nh
    from cake_tpu.models.reference import nemotron_h as ref
    from cake_tpu.ops.quant import qmatmul

    cfg, params = engine.config, engine.params
    impl = {k: engine._step_impl(k) for k in ("mixed", "decode")}
    say(f"device {jax.devices()[0].device_kind}; attention {impl}; "
        f"engine built in {time.monotonic() - t_start:.1f} s")
    if not args.rehearse and impl != cell["expect_impl"]:
        say(f"FAILED: expected attention {cell['expect_impl']}")
        return 1
    attn = engine.attn_impl["mixed"]

    @partial(jax.jit, static_argnames=("n_tokens",),
             donate_argnames=("cache",))
    def window_step(params, tokens, pos, q_len, active, cache, n_tokens):
        out, _ = nh.mixed_trunk(params, tokens, pos, q_len, active, cache,
                                cfg, attn, n_tokens)
        logits = qmatmul(out.x, params["lm_head"]).astype(jnp.float32)
        return logits, out.cache, out.experts

    @partial(jax.jit, donate_argnames=("cache",))
    def decode_step(params, tokens, pos, active, cache):
        out = nh.decode_trunk(params, tokens, cache, pos, active, cfg, attn)
        logits = qmatmul(out.x, params["lm_head"]).astype(jnp.float32)
        return logits, out.cache, out.experts

    B, C = engine.max_slots, engine._mixed_chunk
    page, per_row = engine.cache.page_size, engine.cache.table.shape[1]
    jobs = NEMOTRON_JOBS if not args.rehearse else (
        (0, 70), (1, 30), (2, 45), (1, 25))
    # the slot's second request again, beside it, in a slot nothing has
    # used: the same tokens, the same steps
    second = len(jobs) - 1
    opener = next(i for i, (slot, _) in enumerate(jobs)
                  if slot == jobs[second][0])
    twin = len(jobs)
    jobs = (*jobs, (max(slot for slot, _ in jobs) + 1, jobs[second][1]))
    n_decode = NEMOTRON_DECODE if not args.rehearse else 6
    last = LAST if not args.rehearse else 12
    rng = np.random.default_rng(args.seed)
    sequences = [rng.integers(0, cfg.vocab_size, p + n_decode)
                 for _, p in jobs[:twin]]
    sequences.append(sequences[second])
    prompts = [p for _, p in jobs]
    assert max(prompts) + n_decode <= per_row * page
    table = np.full((B, per_row), -1, np.int32)
    for slot in {slot for slot, _ in jobs}:
        table[slot] = slot * per_row + np.arange(per_row)
    assert table.max() < engine.cache.n_pages
    cache = engine.cache._replace(table=jnp.asarray(table))
    state_dtype = str(cache.ssm.dtype)
    engine.cache = None

    got = [dict() for _ in jobs]        # position -> logits [V]
    routed = [dict() for _ in jobs]     # position -> experts [L_E, k]
    states = [None] * len(jobs)         # the rows' state at a job's end
    stored = [None] * len(jobs)         # attention block 0's keys, ditto
    off = [0] * len(jobs)

    def compared(i, position):
        """The prompt's last positions, every decode step, the request's
        first positions and those behind the first window edge."""
        return (position >= prompts[i] - last or position < NEMOTRON_START
                or C <= position < C + NEMOTRON_EDGE)

    def current(slot):
        """The slot's first unfinished job."""
        return next((i for i, (s, _) in enumerate(jobs)
                     if s == slot and off[i] < len(sequences[i])
                     and (i != twin
                          or off[opener] == len(sequences[opener]))), None)

    steps = {"mixed": 0, "decode": 0}
    t0 = time.monotonic()
    while any(off[i] < len(s) for i, s in enumerate(sequences)):
        live = {slot: current(slot) for slot in {s for s, _ in jobs}}
        live = {slot: i for slot, i in live.items() if i is not None}
        qlen = np.zeros(B, np.int32)
        pos = np.zeros(B, np.int32)
        for slot, i in live.items():
            qlen[slot] = (min(C, prompts[i] - off[i])
                          if off[i] < prompts[i] else 1)
            pos[slot] = off[i]
        if (qlen > 1).any():
            toks = np.zeros((B, C), np.int32)
            for slot, i in live.items():
                toks[slot, :qlen[slot]] = \
                    sequences[i][off[i]:off[i] + qlen[slot]]
            for group in engine._mixed_groups(qlen):
                glen = np.where(group, qlen, 0)
                n_tokens = paged.mixed_bucket_for(engine._mixed_buckets,
                                                  int(glen.sum()))
                logits, cache, experts = window_step(
                    params, jnp.asarray(toks), jnp.asarray(pos),
                    jnp.asarray(glen), jnp.asarray(glen > 0), cache,
                    n_tokens)
                first = np.cumsum(glen) - glen
                wanted = [(slot, j) for slot in np.flatnonzero(glen)
                          for j in range(glen[slot])
                          if compared(live[slot], off[live[slot]] + j)]
                if wanted:
                    experts = np.asarray(experts)
                    rows = np.asarray([first[s] + j for s, j in wanted])
                    fetched = np.asarray(logits[rows])
                    for n, (slot, j) in enumerate(wanted):
                        i = live[slot]
                        got[i][off[i] + j] = fetched[n]
                        routed[i][off[i] + j] = experts[:, first[slot] + j]
            steps["mixed"] += 1
        else:
            toks = np.zeros((B, 1), np.int32)
            for slot, i in live.items():
                toks[slot, 0] = sequences[i][off[i]]
            logits, cache, experts = decode_step(
                params, jnp.asarray(toks), jnp.asarray(pos),
                jnp.asarray(qlen > 0), cache)
            logits, experts = np.asarray(logits), np.asarray(experts)
            for slot, i in live.items():
                got[i][off[i]] = logits[slot]
                routed[i][off[i]] = experts[:, slot]
            steps["decode"] += 1
        for slot, i in live.items():
            off[i] += int(qlen[slot])
            if off[i] == len(sequences[i]):
                states[i] = np.asarray(cache.ssm[:, slot])
                rows_k = cache.k[0, jnp.asarray(table[slot])]
                stored[i] = np.asarray(rows_k.reshape(
                    -1, rows_k.shape[-1])[:off[i]].astype(jnp.float32))
    say(f"served path: {steps['mixed']} mixed and {steps['decode']} decode "
        f"steps in {time.monotonic() - t0:.1f} s")

    # -- the reference: the served weights leave the device, then come
    # back dequantized one block at a time ---------------------------------
    del cache
    host = jax.device_get(params)
    engine.params = params = None
    ref_cfg = {k: getattr(cfg, k) for k in (
        "rms_norm_eps", "mamba_num_heads", "mamba_head_dim", "n_groups",
        "ssm_state_size", "num_attention_heads", "num_key_value_heads",
        "num_experts_per_tok", "norm_topk_prob", "routed_scaling_factor",
        "scoring_func")}
    held = (cfg.first_routed_expert, cfg.num_local_experts)
    mamba_core, attention, expert = ref.mamba_core, ref.attention, ref.expert
    jitted = {}

    def under_jit(name, config, make):
        """A heavy function of the reference under jit: one trace per
        config (its switches are read while tracing) and shape."""
        key = (name, tuple(sorted(config.items())))
        if key not in jitted:
            jitted[key] = jax.jit(make(config))
        return jitted[key]

    def arrays(lp):
        return {k: v for k, v in lp.items() if k != "kind"}

    ref.mamba_core = lambda lp, h, config, state, tail: under_jit(
        "mamba", config, lambda c: lambda lp, h, state, tail: mamba_core(
            lp, h, c, state, tail))(arrays(lp), h, state, tail)
    def jit_attention(lp, h, config, keys=None):
        # the tap's list cannot cross a jit: the jitted function returns
        # the keys beside the output
        def make(c):
            def run(lp, h):
                tap = []
                return attention(lp, h, c, tap), tap[0]
            return run
        out, k = under_jit("attention", config, make)(arrays(lp), h)
        if keys is not None:
            keys.append(k)
        return out

    ref.attention = jit_attention
    ref.expert = lambda u, w_up, w_down, config: under_jit(
        "expert", config, lambda c: lambda u, a, b: expert(u, a, b, c))(
        u, w_up, w_down)

    def blocks():
        # from the host copy: one block's leaves cross to the device at
        # a time, as stored, and widen there
        return nh.reference_blocks(host["blocks"], cfg)

    top = {k: dequantized(jax.tree.map(jnp.asarray, host[k]))
           for k in ("embed", "final_norm", "lm_head")}

    def reference(seqs, config=ref_cfg, starts=None):
        t0 = time.monotonic()
        routing = [[] for _ in seqs]
        finals = [[] for _ in seqs]
        keys = [[] for _ in seqs]
        logits = ref.forward(top, list(seqs), config, layers=blocks(),
                             held=held, routing=routing, states=finals,
                             starts=starts, keys=keys)
        say(f"  reference over {sum(len(s) for s in seqs)} tokens in "
            f"{time.monotonic() - t0:.1f} s")
        return ([np.asarray(x) for x in logits], routing,
                [[np.asarray(S) for S, _ in f] for f in finals], finals,
                [np.asarray(k[0]) for k in keys])

    (want, want_routing, want_states, want_finals,
     want_keys) = reference(sequences[:twin])

    def rel(a, b):
        return float(np.linalg.norm(a - b) / np.linalg.norm(b))

    # the first Mamba block's slowest heads: least A * softplus(dt_bias)
    rate = (np.exp(np.asarray(host["blocks"]["A_log"][0], np.float64))
            * np.log1p(np.exp(np.asarray(host["blocks"]["dt_bias"][0],
                                         np.float64))))
    slow = np.argsort(rate)[:NEMOTRON_SLOW_HEADS]

    def readings(rows, logits_at, experts_at, states_of, keys_of):
        """Over compared (job, position) rows: mean and worst |error| /
        range of the logits; the mean share of each E block's experts
        in common; each Mamba block's relative state error at the jobs'
        ends (worst job); the first attention block's keys' relative
        error (worst job)."""
        total, n, worst = 0.0, 0, 0.0
        start, edge = [], []
        common = np.zeros(len(cfg.sparse_layers))
        for i, position in rows:
            w = want[i][position]
            err = np.abs(logits_at(i, position) - w) / float(w.max()
                                                             - w.min())
            total += float(err.sum())
            n += err.size
            worst = max(worst, float(err.max()))
            if position < NEMOTRON_START:
                start.append(float(err.mean()))
            if C <= position < C + NEMOTRON_EDGE:
                edge.append(float(err.mean()))
            common += [len(set(experts_at(i, position)[j])
                           & set(want_routing[i][j][position]))
                       / cfg.num_experts_per_tok
                       for j in range(len(cfg.sparse_layers))]
        state = [max(rel(S[m], R[m]) for S, R in states_of)
                 for m in range(len(cfg.mamba_layers))]
        return {"mean": total / max(n, 1), "max": worst,
                "mean_start": float(np.mean(start)),
                "mean_edge": float(np.mean(edge)) if edge else 0.0,
                "state_slow": max(rel(S[0][slow], R[0][slow])
                                  for S, R in states_of),
                "keys": max(rel(k, r) for k, r in keys_of),
                "state_by_block": [round(x, 5) for x in state],
                "experts_in_common_by_block": [
                    round(float(x) / len(rows), 4) for x in common]}

    def passes(r):
        return all(r[k] < limit for k, limit in NEMOTRON_TOL.items())

    def apart(logits_at):
        """The slot's second request, by `logits_at`, against the
        reference-ranged yardstick: mean |difference| / range."""
        errs = [np.abs(logits_at(p) - other) / float(
                    want[second][p].max() - want[second][p].min())
                for p, other in sorted(yardstick.items())]
        return float(np.mean(np.concatenate(errs)))

    rows = [(i, position) for i in range(twin)
            for position in sorted(got[i])]
    served = readings(rows, lambda i, p: got[i][p], lambda i, p: routed[i][p],
                      [(states[i], want_states[i]) for i in range(twin)],
                      [(stored[i], want_keys[i]) for i in range(twin)])
    # the served path against itself: the reused slot against the fresh
    yardstick = got[twin]
    served["reuse"] = apart(lambda p: got[second][p])
    assert sorted(got[twin]) == sorted(got[second])
    expected = sum(min(last, p) + n_decode
                   + sum(1 for q in (*range(NEMOTRON_START),
                                     *range(C, C + NEMOTRON_EDGE))
                         if q < p - last)
                   for p in prompts[:twin])
    result = {
        "positions": len(rows), "expected_positions": expected,
        "served": served, "tol": NEMOTRON_TOL, "seed": args.seed,
        "jobs": [list(j) for j in jobs], "steps": steps, "attention": impl,
        "device": jax.devices()[0].device_kind,
        "state_dtype": state_dtype,
    }
    ok = (len(rows) == expected and passes(served)
          and state_dtype == "float32")

    # -- what must NOT pass: the reference, altered, against itself ----
    if args.negatives:
        # the slot that is used twice: its first request and its second
        short = [opener, second]
        yardstick = {p: want[second][p] for p in got[second]}
        seqs = [sequences[i] for i in short]
        neg_rows = [(i, position) for i, position in rows if i in short]
        at = {i: n for n, i in enumerate(short)}

        def against_reference(run, leftover=False):
            """leftover: the run started the second request from the
            first's state: what `reuse` reads on the served side."""
            logits, routing, finals, _, keys = run
            r = readings(
                neg_rows, lambda i, p: logits[at[i]][p],
                lambda i, p: [r[p] for r in routing[at[i]]],
                [(finals[at[i]], want_states[i]) for i in short],
                [(keys[at[i]], want_keys[i]) for i in short])
            r["reuse"] = (apart(lambda p: logits[at[second]][p])
                          if leftover else 0.0)
            return r

        altered = {
            "int8_activation_reference": dict(
                config=dict(ref_cfg, int8_activations=True)),
            "bf16_state_reference": dict(
                config=dict(ref_cfg, ssm_state_dtype="bfloat16")),
            # the second request starts from what the first left
            "state_not_zeroed_reference": dict(
                starts=[None, want_finals[short[0]]]),
            "conv_tail_dropped_reference": dict(
                config=dict(ref_cfg, conv_window=C)),
            "softmax_routing_reference": dict(
                config=dict(ref_cfg, scoring_func="softmax")),
            "swiglu_experts_reference": dict(
                config=dict(ref_cfg, expert_act="swiglu")),
            "rope_attention_reference": dict(
                config=dict(ref_cfg, attn_rope_theta=10000.0)),
        }
        for name, kw in altered.items():
            result[name] = against_reference(
                reference(seqs, **kw), leftover="starts" in kw)
            # (a bfloat16 state lies under the floor: held by
            # `state_dtype` above; its readings say by how much)
            if passes(result[name]) and name != "bf16_state_reference":
                say(f"FAILED: the {name} passes the tolerance")
                ok = False
    result["ok"] = bool(ok)
    result["seconds"] = round(time.monotonic() - t_start, 1)
    with open(os.path.join(OUT_DIR, f"nemotron_result_seed{args.seed}.json"),
              "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result), flush=True)
    return 0 if ok else 1


def compare_zaya(engine, cell, args, t_start) -> int:
    """The comparison above for compressed convolutional attention: the
    engine's own mixed and decode trunks with the head at every
    position, against models/reference/zaya.py on teacher-forced
    routing (ZAYA_TOL says why). Jobs run a slot each, two prefilling
    rows a dispatch with the rows that already decode beside them, the
    decode program when no row prefills; slot 0 takes a second request
    when its first has finished (the tail it left must not reach the
    second), and that request runs again in a slot nothing has used."""
    import jax
    import jax.numpy as jnp

    from cake_tpu.models.llama import paged
    from cake_tpu.models.moe import zaya
    from cake_tpu.models.reference import zaya as ref
    from cake_tpu.ops.quant import qmatmul

    cfg, params, rope = engine.config, engine.params, engine.rope
    impl = {k: engine._step_impl(k) for k in ("mixed", "decode")}
    say(f"device {jax.devices()[0].device_kind}; attention {impl}; "
        f"engine built in {time.monotonic() - t_start:.1f} s")
    if not args.rehearse and impl != cell["expect_impl"]:
        say(f"FAILED: expected attention {cell['expect_impl']}")
        return 1
    attn = engine.attn_impl["mixed"]

    @partial(jax.jit, static_argnames=("n_tokens",),
             donate_argnames=("cache",))
    def window_step(params, tokens, pos, q_len, active, cache, n_tokens):
        out, _ = zaya.mixed_trunk(params, tokens, pos, q_len, active, cache,
                                  rope, cfg, attn, n_tokens)
        logits = qmatmul(out.x, params["lm_head"]).astype(jnp.float32)
        return logits, out.cache, out.experts

    @partial(jax.jit, donate_argnames=("cache",))
    def decode_step(params, tokens, pos, active, cache):
        out = zaya.decode_trunk(params, tokens, cache, pos, active, rope,
                                cfg, attn)
        logits = qmatmul(out.x, params["lm_head"]).astype(jnp.float32)
        return logits, out.cache, out.experts

    B, C = engine.max_slots, engine._mixed_chunk
    page, per_row = engine.cache.page_size, engine.cache.table.shape[1]
    jobs = ZAYA_JOBS if not args.rehearse else (
        (0, 21), (1, 30), (2, 45), (3, 17), (0, 19))
    second = len(jobs) - 1
    opener = next(i for i, (slot, _) in enumerate(jobs)
                  if slot == jobs[second][0])
    twin = len(jobs)
    jobs = (*jobs, (max(slot for slot, _ in jobs) + 1, jobs[second][1]))
    n_decode = ZAYA_DECODE if not args.rehearse else 6
    last = LAST if not args.rehearse else 12
    rng = np.random.default_rng(args.seed)
    sequences = [rng.integers(0, cfg.vocab_size, p + n_decode)
                 for _, p in jobs[:twin]]
    sequences.append(sequences[second])
    prompts = [p for _, p in jobs]
    assert max(prompts) + n_decode <= per_row * page
    table = np.full((B, per_row), -1, np.int32)
    for slot in {slot for slot, _ in jobs}:
        table[slot] = slot * per_row + np.arange(per_row)
    assert table.max() < engine.cache.n_pages
    cache = engine.cache._replace(table=jnp.asarray(table))
    engine.cache = None
    L, k = cfg.num_hidden_layers, cfg.num_experts_per_tok

    got = [dict() for _ in jobs]        # position -> logits [V]
    chosen = [np.zeros((len(s), L, k), np.int32) for s in sequences]
    stored = [None] * len(jobs)         # layer 0's (K, V) at a job's end
    off = [0] * len(jobs)

    def compared(i, position):
        """The prompt's last positions, every decode step, and the
        positions behind each window edge."""
        return (position >= prompts[i] - last
                or (position >= C and position % C < ZAYA_EDGE))

    def current(slot):
        """The slot's first unfinished job."""
        return next((i for i, (s, _) in enumerate(jobs)
                     if s == slot and off[i] < len(sequences[i])
                     and (i != second
                          or off[opener] == len(sequences[opener]))), None)

    steps = {"mixed": 0, "decode": 0}
    t0 = time.monotonic()
    while any(off[i] < len(s) for i, s in enumerate(sequences)):
        live = {slot: current(slot) for slot in {s for s, _ in jobs}}
        live = {slot: i for slot, i in live.items() if i is not None
                # the twin starts with the request it mirrors
                and (i != twin or off[opener] == len(sequences[opener]))}
        qlen = np.zeros(B, np.int32)
        pos = np.zeros(B, np.int32)
        for slot, i in live.items():
            qlen[slot] = (min(C, prompts[i] - off[i])
                          if off[i] < prompts[i] else 1)
            pos[slot] = off[i]
        if (qlen > 1).any():
            toks = np.zeros((B, C), np.int32)
            for slot, i in live.items():
                toks[slot, :qlen[slot]] = \
                    sequences[i][off[i]:off[i] + qlen[slot]]
            for group in engine._mixed_groups(qlen):
                glen = np.where(group, qlen, 0)
                n_tokens = paged.mixed_bucket_for(engine._mixed_buckets,
                                                  int(glen.sum()))
                logits, cache, experts = window_step(
                    params, jnp.asarray(toks), jnp.asarray(pos),
                    jnp.asarray(glen), jnp.asarray(glen > 0), cache,
                    n_tokens)
                first = np.cumsum(glen) - glen
                experts = np.asarray(experts)
                wanted = []
                for slot in np.flatnonzero(glen):
                    i = live[slot]
                    chosen[i][off[i]:off[i] + glen[slot]] = experts[
                        :, first[slot]:first[slot] + glen[slot]
                    ].transpose(1, 0, 2)
                    wanted += [(slot, j) for j in range(glen[slot])
                               if compared(i, off[i] + j)]
                if wanted:
                    rows = np.asarray([first[s] + j for s, j in wanted])
                    fetched = np.asarray(logits[rows])
                    for n, (slot, j) in enumerate(wanted):
                        got[live[slot]][off[live[slot]] + j] = fetched[n]
            steps["mixed"] += 1
        else:
            toks = np.zeros((B, 1), np.int32)
            for slot, i in live.items():
                toks[slot, 0] = sequences[i][off[i]]
            logits, cache, experts = decode_step(
                params, jnp.asarray(toks), jnp.asarray(pos),
                jnp.asarray(qlen > 0), cache)
            logits, experts = np.asarray(logits), np.asarray(experts)
            for slot, i in live.items():
                got[i][off[i]] = logits[slot]
                chosen[i][off[i]] = experts[:, slot]
            steps["decode"] += 1
        for slot, i in live.items():
            off[i] += int(qlen[slot])
            if off[i] == len(sequences[i]):
                pages = jnp.asarray(table[slot])
                stored[i] = tuple(
                    np.asarray(pool[0, pages].reshape(
                        -1, pool.shape[-1])[:off[i]].astype(jnp.float32))
                    for pool in (cache.k, cache.v))
    say(f"served path: {steps['mixed']} mixed and {steps['decode']} decode "
        f"steps in {time.monotonic() - t0:.1f} s")

    # -- the reference: the served weights leave the device, then come
    # back dequantized one layer at a time ---------------------------------
    del cache
    host = jax.device_get(params)
    engine.params = params = None
    ref_cfg = zaya.reference_config(cfg)
    attention, experts_fn = ref.attention, ref.experts
    jitted = {}

    def under_jit(name, config, make):
        """A heavy function of the reference under jit: one trace per
        config (its switches are read while tracing) and shape."""
        key = (name, tuple(sorted(config.items())))
        if key not in jitted:
            jitted[key] = jax.jit(make(config))
        return jitted[key]

    def jit_attention(lp, u, config, keys=None):
        # a tap's list cannot cross a jit: the jitted function returns
        # what it received beside the output
        def make(c):
            def run(lp, u):
                tap = []
                return attention(lp, u, c, tap), tap[0]
            return run
        out, kv = under_jit("attention", config, make)(lp, u)
        if keys is not None:
            keys.append(kv)
        return out

    def jit_experts(lp, m, p, config, routing=None, forced=None):
        def make(c):
            def run(lp, m, p, forced):
                tap = []
                return experts_fn(lp, m, p, c, tap, forced), tap[0]
            return run
        out, own = under_jit("experts", config, make)(
            lp, m, p, jnp.asarray(forced))
        if routing is not None:
            routing.append(own)
        return out

    ref.attention, ref.experts = jit_attention, jit_experts
    top = {key: dequantized(jax.tree.map(jnp.asarray, host[key]))
           for key in ("embed", "final_norm", "lm_head")}

    def reference(idx, config=ref_cfg):
        """The jobs `idx` through the reference, routed as the served
        path routed them -> per job (logits, its own choices [S, L, k],
        layer 0's (k, v))."""
        t0 = time.monotonic()
        routing = [[] for _ in idx]
        keys = [[] for _ in idx]
        forced = [[chosen[i][:, n] for n in range(L)] for i in idx]
        logits = ref.forward(
            top, [sequences[i] for i in idx], config,
            layers=zaya.reference_layers(host["blocks"], cfg),
            routing=routing, forced=forced, keys=keys)
        say(f"  reference over {sum(len(sequences[i]) for i in idx)} "
            f"tokens in {time.monotonic() - t0:.1f} s")
        return {i: (np.asarray(logits[n]),
                    np.stack([np.asarray(r) for r in routing[n]], axis=1),
                    tuple(np.asarray(x) for x in keys[n][0]))
                for n, i in enumerate(idx)}

    want = reference(list(range(twin)))

    def rel(a, b):
        return float(np.linalg.norm(a - b) / np.linalg.norm(b))

    def readings(idx, run, logits_at, stored_of):
        """Over the compared positions of the jobs `idx`, against the
        plain reference `want`: mean and worst |error| / range of the
        logits; the mean behind the window edges; the share of (token,
        layer) choices of `run` (a reference's, along the served
        trajectory) that are the served path's; layer 0's stored keys
        and values, relative (worst job)."""
        total, n, worst, edge = 0.0, 0, 0.0, []
        for i in idx:
            for position in sorted(got[i]):
                w = want[i][0][position]
                err = np.abs(logits_at(i, position) - w) / float(
                    w.max() - w.min())
                total += float(err.sum())
                n += err.size
                worst = max(worst, float(err.max()))
                if position < prompts[i] - last:
                    edge.append(float(err.mean()))
        agree = np.concatenate([(run[i][1] == chosen[i]).ravel()
                                for i in idx])
        return {"mean": total / max(n, 1), "max": worst,
                "mean_edge": float(np.mean(edge)) if edge else 0.0,
                "agree": float(agree.mean()),
                "agree_by_layer": [round(float(np.mean(np.concatenate(
                    [(run[i][1][:, j] == chosen[i][:, j]).ravel()
                     for i in idx]))), 4) for j in range(L)],
                "keys": max(rel(stored_of(i)[0], want[i][2][0])
                            for i in idx),
                "values": max(rel(stored_of(i)[1], want[i][2][1])
                              for i in idx)}

    def passes(r):
        return (all(r[key] < limit for key, limit in ZAYA_TOL.items())
                and r["agree"] >= ZAYA_AGREE)

    def apart(logits_at):
        """The slot's second request, by `logits_at`, against the same
        request in a fresh slot: mean |difference| / range."""
        errs = [np.abs(logits_at(p) - got[twin][p]) / float(
                    want[second][0][p].max() - want[second][0][p].min())
                for p in sorted(got[twin])]
        return float(np.mean(np.concatenate(errs)))

    every = list(range(twin))
    served = readings(every, want, lambda i, p: got[i][p],
                      lambda i: stored[i])
    assert sorted(got[twin]) == sorted(got[second])
    served["reuse"] = apart(lambda p: got[second][p])
    n_rows = sum(len(g) for g in got[:twin])
    expected = sum(
        min(last, p) + n_decode
        + sum(1 for q in range(C, p - last) if q % C < ZAYA_EDGE)
        for p in prompts[:twin])
    result = {
        "positions": n_rows, "expected_positions": expected,
        "served": served, "tol": ZAYA_TOL, "agree_floor": ZAYA_AGREE,
        "seed": args.seed, "jobs": [list(j) for j in jobs], "steps": steps,
        "attention": impl, "device": jax.devices()[0].device_kind,
        "packed_sizes": list(engine._mixed_buckets),
    }
    ok = n_rows == expected and passes(served)

    # -- what must NOT pass: the reference, altered, against itself ----
    if args.negatives:
        # the slot that is used twice, and the shortest long prompt (a
        # window edge among its compared positions)
        short = [opener, second, min(
            (i for i in every if prompts[i] > C + last),
            key=lambda i: prompts[i], default=opener)]
        short = sorted(set(short))
        altered = {
            "int8_activation_reference": {"int8_activations": True},
            "conv_taps_dropped_reference": {"drop_conv_taps": True},
            "no_value_shift_reference": {"no_value_shift": True},
            "full_rotary_reference": {"full_rotary": True},
            "renormalised_top1_reference": {"renormalise_top1": True},
            "no_router_state_reference": {"no_router_state": True},
        }
        for name, switch in altered.items():
            run = reference(short, dict(ref_cfg, **switch))
            r = readings(short, run, lambda i, p: run[i][0][p],
                         lambda i: run[i][2])
            r["reuse"] = 0.0
            result[name] = r
            if passes(r):
                say(f"FAILED: the {name} passes the tolerance")
                ok = False
    result["ok"] = bool(ok)
    result["seconds"] = round(time.monotonic() - t_start, 1)
    with open(os.path.join(OUT_DIR, f"zaya_result_seed{args.seed}.json"),
              "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result), flush=True)
    return 0 if ok else 1


def trunk_steps(module, engine, ffn_input=lambda out: out.ffn_in[0]):
    """(window_step, decode_step): a family's own mixed and decode
    trunks (`module.mixed_trunk` / `decode_trunk`, the signatures
    bailing_hybrid's, exaone_moe's and glm_dsa's share) under jit with
    the head at EVERY position. Each hands the host (logits [T, V], the
    cache, every sparse layer's choice [L_sparse, T, k], the first
    sparse layer's input [T, D] (`ffn_input` of the trunk's result), the
    router's logits on it as moe_mlp makes them [T, E])."""
    import jax
    import jax.numpy as jnp

    from cake_tpu.ops import moe as moe_ops
    from cake_tpu.ops.quant import qmatmul

    cfg, rope, attn = engine.config, engine.rope, engine.attn_impl["mixed"]

    def outputs(params, out):
        logits = qmatmul(out.x, params["lm_head"]).astype(jnp.float32)
        h = ffn_input(out)
        router = params["blocks"]["router"][0]
        return (logits, out.cache, out.experts, h,
                moe_ops.router_logits(h, router))

    @partial(jax.jit, static_argnames=("n_tokens",),
             donate_argnames=("cache",))
    def window_step(params, tokens, pos, q_len, active, cache, n_tokens):
        out, _ = module.mixed_trunk(params, tokens, pos, q_len, active,
                                    cache, rope, cfg, attn, n_tokens)
        return outputs(params, out)

    @partial(jax.jit, donate_argnames=("cache",))
    def decode_step(params, tokens, pos, active, cache):
        return outputs(params, module.decode_trunk(
            params, tokens, cache, pos, active, rope, cfg, attn))

    return window_step, decode_step


def drive_jobs(engine, params, cache, steps_of, jobs, sequences, prompts,
               compared, rng, waits_for=None, at_end=None):
    """Jobs (slot, prompt) through `steps_of` = trunk_steps(...) as the
    timed path runs them: a slot each, ONE window a step, the jobs
    mid-prefill taking turns in slot order (family.Windows.STEP), every
    other live row decoding beside it (the jobs' rows that have finished
    their prompts, and fillers in every slot no job uses, so that the
    step has every row), the decode program when no row prefills; a
    slot's later job starts when its earlier one has finished.
    waits_for {job: job}: a job that starts only when another has
    finished (a twin that runs beside a slot's second request).
    at_end(job, slot, cache): called when a job's last token is done.
    compared(job, position) -> bool: the positions whose logits come
    back. Returns (got [{position: logits [V]}], ffn_in [{position:
    (h [D], router logits [E])}], all_routed [[L_sparse, S, k]], steps,
    cache)."""
    import jax.numpy as jnp

    from cake_tpu.models.llama import paged

    window_step, decode_step = steps_of
    cfg = engine.config
    B, C = engine.max_slots, engine._mixed_chunk
    per_row = cache.table.shape[1]
    waits_for = waits_for or {}
    job_slots = sorted({slot for slot, _ in jobs})
    fillers = [b for b in range(B) if b not in job_slots]
    filler_tokens = rng.integers(0, cfg.vocab_size,
                                 (B, per_row * cache.page_size))
    Ls = (len(getattr(cfg, "sparse_layers", ()))
          or len(getattr(cfg, "shortcut_layers", ())))
    k = cfg.num_experts_per_tok
    got = [dict() for _ in jobs]
    ffn_in = [dict() for _ in jobs]
    # every position's experts, for the teacher-forced reference
    all_routed = [np.zeros((Ls, len(seq), k), np.int32)
                  for seq in sequences]
    off = [0] * len(jobs)

    def current(slot):
        """The slot's first unfinished job."""
        return next((i for i, (s, _) in enumerate(jobs)
                     if s == slot and off[i] < len(sequences[i])
                     and (i not in waits_for or off[waits_for[i]]
                          == len(sequences[waits_for[i]]))), None)

    steps = {"mixed": 0, "decode": 0}
    n_steps = 0
    t0 = time.monotonic()
    while any(off[i] < len(s) for i, s in enumerate(sequences)):
        live = {slot: current(slot) for slot in job_slots}
        live = {slot: i for slot, i in live.items() if i is not None}
        qlen = np.zeros(B, np.int32)
        pos = np.zeros(B, np.int32)
        prefilling = [slot for slot, i in sorted(live.items())
                      if off[i] < prompts[i]]
        for slot, i in live.items():
            if off[i] >= prompts[i]:
                qlen[slot] = 1
            elif slot == prefilling[0]:
                qlen[slot] = min(C, prompts[i] - off[i])
            pos[slot] = off[i]
        qlen[fillers], pos[fillers] = 1, n_steps
        width = C if prefilling else 1
        toks = np.zeros((B, width), np.int32)
        for slot, i in live.items():
            toks[slot, :qlen[slot]] = sequences[i][off[i]:off[i] + qlen[slot]]
        toks[fillers, 0] = filler_tokens[fillers, n_steps]
        if prefilling:
            logits, cache, experts, h, r_logits = window_step(
                params, jnp.asarray(toks), jnp.asarray(pos),
                jnp.asarray(qlen), jnp.asarray(qlen > 0), cache,
                paged.mixed_bucket_for(engine._mixed_buckets,
                                       int(qlen.sum())))
            first = np.cumsum(qlen) - qlen
            steps["mixed"] += 1
        else:
            logits, cache, experts, h, r_logits = decode_step(
                params, jnp.asarray(toks), jnp.asarray(pos),
                jnp.asarray(qlen > 0), cache)
            first = np.arange(B)
            steps["decode"] += 1
        experts = np.asarray(experts)
        wanted = []
        for slot, i in live.items():
            n = int(qlen[slot])
            all_routed[i][:, off[i]:off[i] + n] = experts[
                :, first[slot]:first[slot] + n]
            wanted += [(i, off[i] + j, first[slot] + j) for j in range(n)
                       if compared(i, off[i] + j)]
        if wanted:
            rows = jnp.asarray([r for _, _, r in wanted])
            fetched = [np.asarray(x[rows]) for x in (logits, h, r_logits)]
            for n, (i, position, _) in enumerate(wanted):
                got[i][position] = fetched[0][n]
                ffn_in[i][position] = (fetched[1][n], fetched[2][n])
        n_steps += 1
        for slot, i in live.items():
            off[i] += int(qlen[slot])
            if (at_end is not None and qlen[slot]
                    and off[i] == len(sequences[i])):
                at_end(i, slot, cache)
    say(f"served path: {steps['mixed']} mixed and {steps['decode']} decode "
        f"steps of {B} rows in {time.monotonic() - t0:.1f} s")
    return got, ffn_in, all_routed, steps, cache


def rows_table(engine, whole=None, other_pages: int = 0):
    """The comparisons' allocator: a page table mapped for good. Every
    slot a whole row of pages (slot b on pages b * per_row ..), or,
    where the pool is not provisioned for every row at full length,
    the slots `whole` a whole row each and every other slot its first
    `other_pages` pages (a filler that decodes from position 0)."""
    B, per_row = engine.max_slots, engine.cache.table.shape[1]
    table = np.full((B, per_row), -1, np.int32)
    at = 0
    for b in range(B):
        n = per_row if whole is None or b in whole else other_pages
        table[b, :n] = at + np.arange(n)
        at += n
    assert at <= engine.cache.n_pages
    return table


def same_router_input(ref, router, got, ffn_in, all_routed, which, config):
    """(agree_same_input, router_logit_err) over the compared positions
    of the jobs `which`: the reference's router (`ref.router`, its
    leaves `router`) on the served path's own input to the first sparse
    layer: the share of positions where it chooses the served path's
    experts, and ops/moe.router_logits on the chip against the
    reference's product on that input, worst entry."""
    import jax
    import jax.numpy as jnp

    hs, served_logits, chosen = [], [], []
    for i in which:
        for position in sorted(got[i]):
            hs.append(ffn_in[i][position][0])
            served_logits.append(ffn_in[i][position][1])
            chosen.append(all_routed[i][0, position])
    with jax.default_matmul_precision("highest"):
        h = jnp.asarray(np.stack(hs), jnp.float32)
        own = ref.router(router, h, config)[2]
        logit_err = float(jnp.max(jnp.abs(
            ref.mm(h, router["router"]) - np.stack(served_logits))))
    same = [set(a.tolist()) == set(b.tolist())
            for a, b in zip(np.asarray(own), chosen)]
    return float(np.mean(same)), logit_err


def second_request_apart(logits_at, yardstick, want, prompt: int,
                         decode: bool = False) -> float:
    """A slot's second request, by `logits_at(position)`, against
    `yardstick` {position: logits}: mean |difference| / the range of the
    reference's logits `want` at that position, over its compared prompt
    positions (decode: its decode steps)."""
    errs = [np.abs(logits_at(p) - other) / float(
                want[p].max() - want[p].min())
            for p, other in sorted(yardstick.items())
            if (p >= prompt) == decode]
    return float(np.mean(np.concatenate(errs)))


def logit_errors(got, logits_of, which):
    """[(job, position, |served - reference| / the reference's range at
    that position [V])] over the compared positions of `which`."""
    return [(i, position,
             np.abs(logits - logits_of[i][position])
             / float(logits_of[i][position].max()
                     - logits_of[i][position].min()))
            for i in which for position, logits in sorted(got[i].items())]


def compare_ling(engine, cell, args, t_start) -> int:
    """The comparison above for a matrix state a row and head beside the
    latent page pool: the engine's own mixed and decode trunks with the
    head at every position, against models/reference/bailing_hybrid.py
    on teacher-forced experts. Jobs run a slot each, one window a step
    with every other row decoding beside it (the jobs' rows that have
    finished their prompts, and fillers in every slot no job uses, so
    that the step is the timed one), the decode program when no row
    prefills; slot 1 takes a second request when its first has finished
    (the state it left behind must not reach the second), and its twin
    runs beside it in a slot nothing has used."""
    import jax
    import jax.numpy as jnp

    from cake_tpu.models.moe import bailing_hybrid as bh
    from cake_tpu.models.reference import bailing_hybrid as ref

    cfg, params = engine.config, engine.params
    impl = {k: engine._step_impl(k) for k in ("mixed", "decode")}
    say(f"device {jax.devices()[0].device_kind}; attention {impl}; "
        f"engine built in {time.monotonic() - t_start:.1f} s")
    if not args.rehearse and impl != cell["expect_impl"]:
        say(f"FAILED: expected attention {cell['expect_impl']}")
        return 1

    B, C = engine.max_slots, engine._mixed_chunk
    page, per_row = engine.cache.page_size, engine.cache.table.shape[1]
    jobs = LING_JOBS if not args.rehearse else ((0, 70), (1, 30), (1, 25))
    # the slot's second request again, beside it, in a slot nothing has
    # used: the same tokens, the same steps
    second = len(jobs) - 1
    opener = next(i for i, (slot, _) in enumerate(jobs)
                  if slot == jobs[second][0])
    twin = len(jobs)
    jobs = (*jobs, (max(slot for slot, _ in jobs) + 1, jobs[second][1]))
    n_decode = LING_DECODE if not args.rehearse else 6
    last = LING_LAST if not args.rehearse else 12
    rng = np.random.default_rng(args.seed)
    sequences = [rng.integers(0, cfg.vocab_size, p + n_decode)
                 for _, p in jobs[:twin]]
    sequences.append(sequences[second])
    prompts = [p for _, p in jobs]
    assert max(prompts) + n_decode <= per_row * page
    cache = engine.cache._replace(table=jnp.asarray(rows_table(engine)))
    state_dtype = str(cache.ssm.dtype)
    engine.cache = None

    Ls, k = len(cfg.sparse_layers), cfg.num_experts_per_tok
    states = [None] * len(jobs)         # the rows' state at a job's end

    def compared(i, position):
        """The prompt's last positions, every decode step, the request's
        first positions and those behind the first window edge."""
        return (position >= prompts[i] - last or position < LING_START
                or C <= position < C + LING_EDGE)

    def keep_state(i, slot, cache):
        states[i] = np.asarray(cache.ssm[:, slot])

    # the twin starts when the slot's first request has finished, so
    # that it runs beside the second: the same tokens, the same steps
    got, ffn_in, all_routed, steps, cache = drive_jobs(
        engine, params, cache, trunk_steps(bh, engine), jobs, sequences,
        prompts, compared, rng, waits_for={twin: opener}, at_end=keep_state)

    # -- the probe: the page-walking kernel itself against exact
    # attention over the pages the served path wrote (the first latent
    # layer, the longest job's row), as DeepSeek-V2's and under its limit
    probe, _keys, _norm = latent_pages_probe(
        cache.k, cache.table, jobs[0][0], len(sequences[0]),
        cfg.geometry(cfg.latent_layers[0]), engine.attn_impl["mixed"],
        args.seed)
    del _keys

    # -- the reference: the served weights leave the device, then come
    # back dequantized one layer at a time -----------------------------------
    del cache
    host = jax.device_get(params)
    engine.params = params = None
    ref_cfg = dict(
        num_attention_heads=cfg.num_attention_heads,
        head_dim=cfg.kda_head_dim, kda_lower_bound=cfg.kda_lower_bound,
        qk_nope_head_dim=cfg.qk_nope_head_dim,
        qk_rope_head_dim=cfg.qk_rope_head_dim, v_head_dim=cfg.v_head_dim,
        rms_norm_eps=cfg.rms_norm_eps, rope_theta=cfg.rope_theta,
        n_group=cfg.n_group, topk_group=cfg.topk_group,
        num_experts_per_tok=k,
        routed_scaling_factor=cfg.routed_scaling_factor)
    held = (cfg.first_routed_expert, cfg.num_local_experts)
    kda_core = ref.kda_core
    jitted = {}

    def jit_kda(lp, h, config, state=None, tails=None):
        """The recurrence under jit: one trace per config (its switches
        are read while tracing), shape and kind of start."""
        key = (tuple(sorted(config.items())), state is None)
        if key not in jitted:
            jitted[key] = jax.jit(
                lambda lp, h, state, tails: kda_core(lp, h, config, state,
                                                     tails))
        return jitted[key]({k_: v for k_, v in lp.items() if k_ != "kind"},
                           h, state, tails)

    ref.kda_core = jit_kda
    ref.attend_block = jax.jit(ref.attend_block, static_argnames=("scale",))
    ref.swiglu = jax.jit(ref.swiglu)

    def layers():
        # from the host copy: one layer's leaves cross to the device at
        # a time, as stored, and widen there
        return bh.reference_layers(host["blocks"], cfg)

    top = {k_: dequantized(jax.tree.map(jnp.asarray, host[k_]))
           for k_ in ("embed", "final_norm", "lm_head")}

    def reference(which, config=ref_cfg, starts=None):
        """The reference over the jobs `which`, TEACHER-FORCED in its
        experts -> ({job: logits}, {job: its own choice along that
        trajectory}, {job: each KDA layer's (final state, tails)})."""
        t0 = time.monotonic()
        seqs = [sequences[i] for i in which]
        routing = [[] for _ in seqs]
        finals = [[] for _ in seqs]
        logits = ref.forward(top, seqs, config, layers=layers(), held=held,
                             routing=routing, states=finals, starts=starts,
                             forced=[list(all_routed[i]) for i in which])
        say(f"  reference over {sum(len(s_) for s_ in seqs)} tokens in "
            f"{time.monotonic() - t0:.1f} s")
        return (dict(zip(which, (np.asarray(x) for x in logits))),
                dict(zip(which, routing)), dict(zip(which, finals)))

    def rel(a, b):
        return float(np.linalg.norm(np.asarray(a, np.float64)
                                    - np.asarray(b, np.float64))
                     / np.linalg.norm(np.asarray(b, np.float64)))

    # the first sparse layer's router, as the reference multiplies it
    first_sparse = next(lp for lp in layers() if "router" in lp)
    router = {k_: first_sparse[k_] for k_ in ("router", "router_bias")}
    del first_sparse

    def same_input(which, config):
        return same_router_input(ref, router, got, ffn_in, all_routed,
                                 which, config)

    def readings(which, logits_of, routing_of, states_of, config=ref_cfg,
                 against=None):
        """Over the compared positions of the jobs `which`, the served
        logits against `logits_of`: mean and worst |error| / range;
        `mean_edge` over the positions behind the first window edge;
        `agree`; `state`, the first KDA layer's stored state at the
        jobs' ends against `states_of`'s (the worst job), and every
        layer's; against: the plain reference's logits (`nearer` is then
        the served path's distance from `logits_of` over its distance
        from the plain reference, reported)."""
        err_sum = n = worst = 0.0
        edge = []
        same = np.zeros(Ls)
        count = 0
        to_this = to_plain = 0.0
        for i in which:
            for position, logits in sorted(got[i].items()):
                w = logits_of[i][position]
                err = np.abs(logits - w) / float(w.max() - w.min())
                err_sum += float(err.sum())
                n += err.size
                worst = max(worst, float(err.max()))
                if C <= position < C + LING_EDGE:
                    edge.append(float(err.mean()))
                same += [set(all_routed[i][layer, position].tolist())
                         == set(routing_of[i][layer][position].tolist())
                         for layer in range(Ls)]
                count += 1
                if against is not None:
                    to_this += float(np.sum(np.square(logits - w)))
                    to_plain += float(np.sum(np.square(
                        logits - against[i][position])))
        by_layer = [max(rel(states[i][m], states_of[i][m][0])
                        for i in which)
                    for m in range(len(cfg.kda_layers))]
        agree_same, logit_err = same_input(which, config)
        out = {"mean": err_sum / n, "max": worst,
               "mean_edge": float(np.mean(edge)) if edge else 0.0,
               "agree": float(same.min()) / count,
               "agree_same_input": agree_same,
               "router_logit_err": logit_err, "state": by_layer[0],
               "state_by_layer": [round(x, 5) for x in by_layer],
               "positions": count}
        if against is not None:
            out["nearer"] = (to_this / max(to_plain, 1e-300)) ** 0.5
        return out

    def apart(logits_at, yardstick, decode=False):
        return second_request_apart(logits_at, yardstick, want[second],
                                    prompts[second], decode)

    def passes(r):
        return (all(r[k_] < limit for k_, limit in LING_TOL.items())
                and r["agree_same_input"] > LING_AGREE)

    plain = list(range(twin))
    want, want_routing, want_finals = reference(plain)
    served = readings(plain, want, want_routing, want_finals)
    # the served path against itself: the reused slot against the fresh
    assert sorted(got[twin]) == sorted(got[second])
    served["reuse"] = apart(lambda p: got[second][p], got[twin])
    served["reuse_decode"] = apart(lambda p: got[second][p], got[twin],
                                   decode=True)
    served["probe"] = probe["served"]
    expected = sum(
        len({q for q in range(p + n_decode)
             if q >= p - last or q < LING_START or C <= q < C + LING_EDGE})
        for p in prompts[:twin])
    result = {
        "served": served, "expected_positions": expected, "tol": LING_TOL,
        "probe": dict(probe, limit=DSV2_TOL["probe"]),
        "agree_same_input_floor": LING_AGREE, "seed": args.seed,
        "jobs": [list(j) for j in jobs], "rows_a_step": B, "steps": steps,
        "attention": impl, "device": jax.devices()[0].device_kind,
        "state_dtype": state_dtype,
        # the seeded state neither dies nor grows: its root mean square
        # an entry at each request's end, first KDA layer
        "state_rms_layer0": [round(float(np.sqrt(np.mean(np.square(
            states[i][0])))), 5) for i in plain],
    }
    ok = (served["positions"] == expected and passes(served)
          and state_dtype == "float32"
          and probe["served"] < DSV2_TOL["probe"] < probe["bf16_softmax"])
    if not ok:
        say("FAILED: the served path is outside the tolerance")

    # -- what must NOT pass: the reference, altered, read as the served
    # path is (on the slot that is used twice: its two requests) --------
    if args.negatives:
        short = [opener, second]
        negatives = {
            "bf16_state": dict(config=dict(ref_cfg,
                                           kda_state_dtype="bfloat16")),
            "bf16_decay": dict(config=dict(ref_cfg,
                                           kda_decay_dtype="bfloat16")),
            "unbounded_gate": dict(config=dict(ref_cfg,
                                               kda_gate="softplus")),
            # the second request starts from what the first left
            "state_not_zeroed": dict(
                starts=[None, list(want_finals[opener])]),
            "group_score_by_the_best": dict(config=dict(ref_cfg,
                                                        group_top=1)),
            "no_head_gate": dict(config=dict(ref_cfg, head_gate=False))}
        result["must_fail"] = {}
        for name, kw in negatives.items():
            say(f"negative: {name}")
            logits, routing, finals = reference(short, **kw)
            r = readings(short, logits, routing, finals,
                         config=kw.get("config", ref_cfg), against=want)
            # what `reuse` reads on the served side: the second request
            # as this reference gives it against the plain one's
            r["reuse"] = (apart(lambda p: logits[second][p],
                                {p: want[second][p] for p in got[second]})
                          if "starts" in kw else 0.0)
            result["must_fail"][name] = r
            if passes(r):
                say(f"FAILED: the reference with {name} passes the "
                    "tolerance")
                ok = False
    result["ok"] = bool(ok) or bool(args.rehearse and served["positions"]
                                    == expected)
    result["seconds"] = round(time.monotonic() - t_start, 1)
    with open(os.path.join(OUT_DIR, f"result_ling_seed{args.seed}.json"),
              "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result), flush=True)
    return 0 if result["ok"] else 1


def exaone_probe(cache, cfg, n_keys: int, width: int, attn: str,
                 seed: int) -> dict:
    """{"served": {call: error}, "bf16_softmax": {call: error}}: the
    two ragged paged attention kernels as exaone_moe.attention calls
    them (`paged.paged_attention` for a row's single token,
    `paged.paged_attention_mixed` for a window in entries of
    `query_tile`), each over the first full layer's pool through the
    table and over the first sliding layer's through the ring under the
    band, against exact float32 attention over the same stored K and V
    of row 0 (n_keys positions written), and the exact attention with
    bfloat16 scores and probabilities against the same. Queries are
    drawn so that the scores' standard deviation is
    EXAONE_PROBE_SPREAD."""
    import jax
    import jax.numpy as jnp

    from cake_tpu.models.llama import paged
    from cake_tpu.models.moe import exaone_moe as ex

    H, KV, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    W, P, R = cfg.sliding_window_size, cache.page_size, cache.ring_pages
    B = cache.table.shape[0]
    C = min(width, n_keys)
    tile = ex.query_tile(C, H, KV, hd, P, cache.k.dtype.itemsize,
                         cache.k.dtype.itemsize)
    at = jnp.arange(n_keys)

    def stored(pool, table, ring):
        """Row 0's rows of layer 0 by position [S, KV, hd] (a ring holds
        the last R pages' alone: older positions read what overwrote
        them, and no banded query reaches them)."""
        entry = (at // P) % R if ring else at // P
        return pool[0, table[0, entry], at % P].reshape(n_keys, KV, hd)

    kinds = {"full": (cache.k, cache.v, cache.table, None),
             "window": (cache.wk, cache.wv, cache.wtable, W)}
    pos = np.full(B, -1, np.int32)
    pos[0] = n_keys - 1
    out = {"served": {}, "bf16_softmax": {}}
    for kind, (pk, pv, table, band) in kinds.items():
        keys, vals = (stored(pool, table, band is not None).astype(
            jnp.float32) for pool in (pk, pv))
        recent = keys[-W:] if band else keys
        norm = float(jnp.sqrt(jnp.mean(jnp.sum(jnp.square(recent), -1))))
        sigma = EXAONE_PROBE_SPREAD * hd ** 0.5 / norm
        q1 = (jax.random.normal(jax.random.PRNGKey(seed + 1), (B, 1, H, hd))
              * sigma).astype(pk.dtype)
        qw = (jax.random.normal(jax.random.PRNGKey(seed + 2), (C, H, hd))
              * sigma).astype(pk.dtype)

        @partial(jax.jit, static_argnames="dtype")
        def exact(q, q_pos, dtype, keys=keys, vals=vals, band=band):
            """q [T, H, hd] at positions q_pos over the stored rows."""
            with jax.default_matmul_precision("highest"):
                qf = q.astype(jnp.float32).reshape(-1, KV, H // KV, hd)
                sc = (jnp.einsum("tkgd,skd->tkgs", qf, keys)
                      * hd ** -0.5).astype(dtype)
                seen = at[None, :] <= q_pos[:, None]
                if band:
                    seen &= at[None, :] > q_pos[:, None] - band
                sc = jnp.where(seen[:, None, None, :], sc, -jnp.inf)
                pr = jax.nn.softmax(sc, axis=-1).astype(jnp.float32)
                return jnp.einsum("tkgs,skd->tkgd", pr, vals).reshape(
                    -1, H, hd)

        def exact_window(dtype):
            # (in blocks of queries: [C, heads, keys] float32 is a GB)
            return np.concatenate([np.asarray(exact(
                qw[i:i + tile], n_keys - C + i + jnp.arange(tile), dtype),
                np.float64) for i in range(0, C, tile)])

        def rel(a, b):
            return float(np.sqrt(np.sum(np.square(a - b))
                                 / max(np.sum(np.square(b)), 1e-300)))

        served1 = np.asarray(paged.paged_attention(
            q1, pk, pv, 0, table, jnp.asarray(pos), impl=attn,
            window=band)[0, 0], np.float64)
        want1 = np.asarray(exact(q1[0, 0][None], jnp.asarray([n_keys - 1]),
                                 jnp.float32)[0], np.float64)
        servedw = np.asarray(ex.attend_window(
            qw, pk, pv, 0, table[0], jnp.int32(n_keys - C), jnp.int32(C),
            attn, band), np.float64)
        wantw = exact_window(jnp.float32)
        out["served"].update({f"decode_{kind}": rel(served1, want1),
                              f"mixed_{kind}": rel(servedw, wantw)})
        out["bf16_softmax"].update({
            f"decode_{kind}": rel(np.asarray(exact(
                q1[0, 0][None], jnp.asarray([n_keys - 1]), jnp.bfloat16)[0],
                np.float64), want1),
            f"mixed_{kind}": rel(exact_window(jnp.bfloat16), wantw)})
    say(f"probe: kernels {out['served']}, exact attention with a bfloat16 "
        f"softmax {out['bf16_softmax']}")
    return out


def compare_exaone_moe(engine, cell, args, t_start) -> int:
    """The comparison above for GQA in two kinds of layer over K/V
    pages by kind (a ring a row under the sliding layers): the engine's
    own mixed and decode trunks with the head at every position, all 32
    rows in every step (drive_jobs), against
    models/reference/exaone_moe.py on teacher-forced experts. The jobs
    are the cell's two prompt classes (a d8k prompt wraps its ring a
    dozen times), and a second request in the t2k row's slot, whose
    ring and pages still hold the first's keys, beside its twin in a
    slot nothing has used."""
    import jax
    import jax.numpy as jnp

    from cake_tpu.models.moe import exaone_moe as ex
    from cake_tpu.models.reference import exaone_moe as ref

    cfg, params = engine.config, engine.params
    impl = {k: engine._step_impl(k) for k in ("mixed", "decode")}
    say(f"device {jax.devices()[0].device_kind}; attention {impl}; "
        f"engine built in {time.monotonic() - t_start:.1f} s")
    if not args.rehearse and impl != cell["expect_impl"]:
        say(f"FAILED: expected attention {cell['expect_impl']}")
        return 1
    C = engine._mixed_chunk
    page, per_row = engine.cache.page_size, engine.cache.table.shape[1]
    jobs = EXAONE_JOBS if not args.rehearse else ((0, 70), (1, 30), (1, 25))
    second = len(jobs) - 1
    opener = next(i for i, (slot, _) in enumerate(jobs)
                  if slot == jobs[second][0])
    twin = len(jobs)
    jobs = (*jobs, (max(slot for slot, _ in jobs) + 1, jobs[second][1]))
    n_decode = EXAONE_DECODE if not args.rehearse else 6
    last = EXAONE_LAST if not args.rehearse else 12
    rng = np.random.default_rng(args.seed)
    sequences = [rng.integers(0, cfg.vocab_size, p + n_decode)
                 for _, p in jobs[:twin]]
    sequences.append(sequences[second])
    prompts = [p for _, p in jobs]
    assert max(prompts) + n_decode <= per_row * page
    # the cell's pool holds its traffic, not 32 rows at full length:
    # the jobs' slots a whole row, a filler the pages its decoding fills
    # (one position a step; the steps are fewer than the positions of
    # all the jobs' windows and decode steps, one a step)
    most_steps = sum(-(-p // C) + n_decode for p in prompts)
    cache = engine.cache._replace(table=jnp.asarray(rows_table(
        engine, whole={slot for slot, _ in jobs},
        other_pages=-(-most_steps // page))))
    ring = (cache.ring_pages, str(cache.wk.dtype))
    engine.cache = None
    Ls = len(cfg.sparse_layers)

    def compared(i, position):
        """The prompt's last positions, every decode step, the request's
        first positions and those behind the first window edge."""
        return (position >= prompts[i] - last or position < EXAONE_START
                or C <= position < C + EXAONE_EDGE)

    got, ffn_in, all_routed, steps, cache = drive_jobs(
        engine, params, cache, trunk_steps(ex, engine), jobs, sequences,
        prompts, compared, rng, waits_for={twin: opener})
    probe = exaone_probe(cache, cfg, len(sequences[0]), C,
                         engine.attn_impl["mixed"], args.seed)

    # -- the reference: the served weights leave the device, then come
    # back dequantized one layer at a time -----------------------------------
    del cache
    host = jax.device_get(params)
    engine.params = params = None
    ref_cfg = ex.reference_config(cfg)
    held = (cfg.first_routed_expert, cfg.num_local_experts)
    ref.attend_block = jax.jit(ref.attend_block,
                               static_argnames=("window", "dtype"))
    ref.swiglu = jax.jit(ref.swiglu)

    def layers(kinds=None):
        # from the host copy: one layer's leaves cross to the device at
        # a time, as stored, and widen there
        for lp in ex.reference_layers(host["blocks"], cfg):
            yield lp if kinds is None else dict(lp, kind=kinds)

    top = {k_: dequantized(jax.tree.map(jnp.asarray, host[k_]))
           for k_ in ("embed", "final_norm", "lm_head")}

    def reference(which, config=ref_cfg, kinds=None):
        """The reference over the jobs `which`, TEACHER-FORCED in its
        experts -> ({job: logits}, {job: its own choice along that
        trajectory})."""
        t0 = time.monotonic()
        seqs = [sequences[i] for i in which]
        routing = [[] for _ in seqs]
        logits = ref.forward(top, seqs, config, layers=layers(kinds),
                             held=held, routing=routing,
                             forced=[list(all_routed[i]) for i in which])
        say(f"  reference over {sum(len(s_) for s_ in seqs)} tokens in "
            f"{time.monotonic() - t0:.1f} s")
        return (dict(zip(which, (np.asarray(x) for x in logits))),
                dict(zip(which, routing)))

    first_sparse = next(lp for lp in layers() if "router" in lp)
    router = {k_: first_sparse[k_] for k_ in ("router", "router_bias")}
    del first_sparse

    def readings(which, logits_of, routing_of, config=ref_cfg,
                 against=None, name="plain"):
        """Over the compared positions of the jobs `which`, the served
        logits against `logits_of`: mean and worst |error| / range;
        `moe_err`, the first layer's MoE on its own input (above);
        `mean_edge` over the positions behind the first window edge;
        `mean_decode` over the decode steps (what went through the
        decode kernel's band and the ring after it wrapped); `agree`;
        against: the plain reference's logits (`nearer`: the served
        path's distance from `logits_of` over its distance from the
        plain reference, reported)."""
        errs = logit_errors(got, logits_of, which)
        same = np.zeros(Ls)
        to_this = to_plain = 0.0
        for i, position, _ in errs:
            same += [set(all_routed[i][layer, position].tolist())
                     == set(routing_of[i][layer][position].tolist())
                     for layer in range(Ls)]
            if against is not None:
                to_this += float(np.sum(np.square(
                    got[i][position] - logits_of[i][position])))
                to_plain += float(np.sum(np.square(
                    got[i][position] - against[i][position])))
        edge = [float(e.mean()) for _, p_, e in errs if C <= p_ < C + EXAONE_EDGE]
        dec = [float(e.mean()) for i, p_, e in errs if p_ >= prompts[i]]
        agree_same, logit_err = same_router_input(
            ref, router, got, ffn_in, all_routed, which, config)
        out = {"mean": float(np.mean([e.mean() for *_, e in errs])),
               "max": max(float(e.max()) for *_, e in errs),
               "mean_edge": float(np.mean(edge)) if edge else 0.0,
               "mean_decode": float(np.mean(dec)) if dec else 0.0,
               "agree": float(same.min()) / len(errs),
               "agree_same_input": agree_same,
               "router_logit_err": logit_err, "positions": len(errs)}
        if against is not None:
            out["nearer"] = (to_this / max(to_plain, 1e-300)) ** 0.5
        return out

    def apart(logits_at, yardstick, decode=False):
        return second_request_apart(logits_at, yardstick, want[second],
                                    prompts[second], decode)

    def passes(r):
        return (all(r[k_] < limit for k_, limit in EXAONE_TOL.items())
                and r["agree_same_input"] > EXAONE_AGREE)

    plain = list(range(twin))
    want, want_routing = reference(plain)
    served = readings(plain, want, want_routing)
    served["probe"] = max(probe["served"].values())
    served["probe_by_kernel"] = probe["served"]
    # the served path against itself: the reused slot against the fresh
    assert sorted(got[twin]) == sorted(got[second])
    served["reuse"] = apart(lambda p: got[second][p], got[twin])
    served["reuse_decode"] = apart(lambda p: got[second][p], got[twin],
                                   decode=True)
    expected = sum(
        len({q for q in range(p + n_decode)
             if q >= p - last or q < EXAONE_START
             or C <= q < C + EXAONE_EDGE})
        for p in prompts[:twin])
    result = {
        "served": served, "expected_positions": expected,
        "tol": EXAONE_TOL, "agree_same_input_floor": EXAONE_AGREE,
        "seed": args.seed, "jobs": [list(j) for j in jobs],
        "rows_a_step": engine.max_slots, "steps": steps, "attention": impl,
        "device": jax.devices()[0].device_kind, "ring_pages": ring[0],
        "pool_dtype": ring[1],
        "query_tile": ex.query_tile(
            C, cfg.num_attention_heads, cfg.num_key_value_heads,
            cfg.head_dim, page, 2 if ring[1] == "bfloat16" else 4,
            2 if ring[1] == "bfloat16" else 4)}
    ok = served["positions"] == expected and passes(served)
    if not ok:
        say("FAILED: the served path is outside the tolerance")

    # -- what must NOT pass: the reference, altered, read as the served
    # path is (on the slot that is used twice: its two requests) --------
    if args.negatives:
        short = [opener, second]
        W = ref_cfg["sliding_window"]
        negatives = {
            "window_less_one": dict(config=dict(ref_cfg,
                                                sliding_window=W - 1)),
            "window_plus_one": dict(config=dict(ref_cfg,
                                                sliding_window=W + 1)),
            "bf16_softmax": dict(config=dict(ref_cfg,
                                             softmax_dtype="bfloat16")),
            "rope_in_full_layers": dict(config=dict(ref_cfg,
                                                    rope_in_full=True)),
            "no_qk_norm": dict(config=dict(ref_cfg, qk_norm=False)),
            # every layer under the band: what a full layer served
            # through a ring would answer
            "window_in_full_layers": dict(kinds="sliding")}
        result["must_fail"] = {}
        for name, kw in negatives.items():
            say(f"negative: {name}")
            logits, routing = reference(short, **kw)
            r = readings(short, logits, routing,
                         config=kw.get("config", ref_cfg), against=want)
            r["reuse"] = 0.0
            # the probe's reading of an altered reference is the served
            # kernels' but where the probe itself computes the alteration
            r["probe_by_kernel"] = probe.get(name, probe["served"])
            r["probe"] = max(r["probe_by_kernel"].values())
            result["must_fail"][name] = r
            if passes(r):
                say(f"FAILED: the reference with {name} passes the "
                    "tolerance")
                ok = False
    result["ok"] = bool(ok) or bool(args.rehearse and served["positions"]
                                    == expected)
    result["seconds"] = round(time.monotonic() - t_start, 1)
    with open(os.path.join(OUT_DIR, f"result_exaone_seed{args.seed}.json"),
              "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result), flush=True)
    return 0 if result["ok"] else 1


def granite_steps(engine):
    """trunk_steps for a family with no sparse layer
    (models/moe/granite_hybrid.py: no rope argument, logits through
    `logits_of`): the three expert outputs drive_jobs carries are empty."""
    import jax
    import jax.numpy as jnp

    from cake_tpu.models.moe import granite_hybrid as gh

    cfg, attn = engine.config, engine.attn_impl["mixed"]

    def outputs(params, out):
        T = out.x.shape[0]
        return (gh.logits_of(out.x, params, cfg), out.cache,
                jnp.zeros((0, T, 0), jnp.int32), jnp.zeros((T, 1)),
                jnp.zeros((T, 1)))

    @partial(jax.jit, static_argnames=("n_tokens",),
             donate_argnames=("cache",))
    def window_step(params, tokens, pos, q_len, active, cache, n_tokens):
        out, _ = gh.mixed_trunk(params, tokens, pos, q_len, active, cache,
                                cfg, attn, n_tokens)
        return outputs(params, out)

    @partial(jax.jit, donate_argnames=("cache",))
    def decode_step(params, tokens, pos, active, cache):
        return outputs(params, gh.decode_trunk(params, tokens, cache, pos,
                                               active, cfg, attn))

    return window_step, decode_step


def granite_probe(cache, cfg, n_keys: int, width: int, attn: str,
                  seed: int) -> dict:
    """Both attention kernels at the model's own scale against exact
    attention over the K and V pages the served path wrote (slot 0's
    first n_keys positions, attention layer 0): the one-token row
    through `paged.paged_attention`, a window of `width` queries that
    ends at n_keys through `paged.paged_attention_mixed` in the
    sub-windows the trunk hands it, each with `scale=
    attention_multiplier`. The queries are drawn so that the scores at
    that scale spread by EXAONE_PROBE_SPREAD (the trunk's, over seeded
    keys, are near uniform and would tell no scale from another).
    -> {"served": worst relative error of the two, "sqrt_hd": what exact
    attention at 1/sqrt(head_dim) reads against the same}."""
    import jax
    import jax.numpy as jnp

    from cake_tpu.models.llama import paged
    from cake_tpu.models.moe import granite_hybrid as gh

    H, KV, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    P, B = cache.page_size, cache.table.shape[0]
    scale = cfg.attention_multiplier
    at = jnp.arange(n_keys)

    def stored(pool):
        return pool[0, cache.table[0, at // P], at % P].reshape(
            n_keys, KV, hd).astype(jnp.float32)

    keys, vals = stored(cache.k), stored(cache.v)
    norm = float(jnp.sqrt(jnp.mean(jnp.sum(jnp.square(keys), -1))))
    sigma = EXAONE_PROBE_SPREAD / (scale * norm)
    q1 = (jax.random.normal(jax.random.PRNGKey(seed + 1), (B, 1, H, hd))
          * sigma).astype(cache.k.dtype)
    qw = (jax.random.normal(jax.random.PRNGKey(seed + 2), (width, H, hd))
          * sigma).astype(cache.k.dtype)
    sub = gh.subwindow(cfg, width, P, qw.dtype.itemsize,
                       cache.k.dtype.itemsize)

    @partial(jax.jit, static_argnames="scale")
    def exact(q, q_pos, scale):
        with jax.default_matmul_precision("highest"):
            qf = q.astype(jnp.float32).reshape(-1, KV, H // KV, hd)
            sc = jnp.einsum("tkgd,skd->tkgs", qf, keys) * scale
            seen = at[None, :] <= q_pos[:, None]
            sc = jnp.where(seen[:, None, None, :], sc, -jnp.inf)
            return jnp.einsum("tkgs,skd->tkgd", jax.nn.softmax(sc, axis=-1),
                              vals).reshape(-1, H, hd)

    def exact_all(scale):
        """(the one-token row's, the window's in blocks of queries)."""
        one = exact(q1[0, 0][None], jnp.asarray([n_keys - 1]), scale)[0]
        win = [exact(qw[i:i + sub], n_keys - width + i + jnp.arange(sub),
                     scale) for i in range(0, width, sub)]
        return np.concatenate([np.asarray(one, np.float64).reshape(1, H, hd),
                               *(np.asarray(w, np.float64) for w in win)])

    def rel(a, b):
        return float(np.sqrt(np.sum(np.square(a - b))
                             / max(np.sum(np.square(b)), 1e-300)))

    pos = np.full(B, -1, np.int32)
    pos[0] = n_keys - 1
    n_sub = width // sub
    starts = jnp.arange(n_sub, dtype=jnp.int32) * sub
    served = np.concatenate([
        np.asarray(paged.paged_attention(
            q1, cache.k, cache.v, jnp.int32(0), cache.table,
            jnp.asarray(pos), impl=attn, scale=scale)[0], np.float64),
        np.asarray(paged.paged_attention_mixed(
            qw.reshape(n_sub, sub, H, hd), cache.k, cache.v, jnp.int32(0),
            jnp.broadcast_to(cache.table[0][None],
                             (n_sub, cache.table.shape[1])),
            n_keys - width + starts, jnp.full(n_sub, sub, jnp.int32),
            impl=attn, scale=scale), np.float64).reshape(width, H, hd)])
    want = exact_all(scale)
    out = {"served": rel(served, want),
           "sqrt_hd": rel(exact_all(hd ** -0.5), want)}
    say(f"probe: both kernels at scale {scale} {out['served']:.3e}; exact "
        f"attention at 1/sqrt(hd) {out['sqrt_hd']:.3e}")
    return out


def compare_granite(engine, cell, args, t_start) -> int:
    """The comparison above for a mixer and a dense SwiGLU a layer, a
    recurrent state a row beside K/V pages of narrow heads: the
    engine's own mixed and decode trunks with the head at every
    position, all 64 rows in every step (drive_jobs), against
    models/reference/granite_hybrid.py's full forward (no discrete
    choice: nothing is teacher-forced), logits and the carried state
    S_t. The jobs are the cell's two prompt classes and one that ends
    inside a window; slot 1 takes a second request when its first has
    finished, and its twin runs beside it in a slot nothing has used."""
    import jax
    import jax.numpy as jnp

    from cake_tpu.models.moe import granite_hybrid as gh
    from cake_tpu.models.reference import granite_hybrid as ref

    cfg, params = engine.config, engine.params
    impl = {k: engine._step_impl(k) for k in ("mixed", "decode")}
    say(f"device {jax.devices()[0].device_kind}; attention {impl}; "
        f"engine built in {time.monotonic() - t_start:.1f} s")
    if not args.rehearse and impl != cell["expect_impl"]:
        say(f"FAILED: expected attention {cell['expect_impl']}")
        return 1
    B, C = engine.max_slots, engine._mixed_chunk
    page, per_row = engine.cache.page_size, engine.cache.table.shape[1]
    jobs = GRANITE_JOBS if not args.rehearse else (
        (0, 50), (1, 24), (2, 31), (1, 20))
    second = len(jobs) - 1
    opener = next(i for i, (slot, _) in enumerate(jobs)
                  if slot == jobs[second][0])
    twin = len(jobs)
    jobs = (*jobs, (max(slot for slot, _ in jobs) + 1, jobs[second][1]))
    n_decode = GRANITE_DECODE if not args.rehearse else 6
    last = GRANITE_LAST if not args.rehearse else 12
    rng = np.random.default_rng(args.seed)
    sequences = [rng.integers(0, cfg.vocab_size, p + n_decode)
                 for _, p in jobs[:twin]]
    sequences.append(sequences[second])
    prompts = [p for _, p in jobs]
    assert max(prompts) + n_decode <= per_row * page
    cache = engine.cache._replace(table=jnp.asarray(rows_table(engine)))
    state_dtype = str(cache.ssm.dtype)
    engine.cache = None
    states = [None] * len(jobs)         # the rows' state at a job's end

    def compared(i, position):
        """The prompt's last positions, every decode step, the request's
        first positions and those behind the first window edge."""
        return (position >= prompts[i] - last or position < GRANITE_START
                or C <= position < C + GRANITE_EDGE)

    def keep_state(i, slot, cache):
        states[i] = np.asarray(cache.ssm[:, slot])

    got, _, _, steps, cache = drive_jobs(
        engine, params, cache, granite_steps(engine), jobs, sequences,
        prompts, compared, rng, waits_for={twin: opener}, at_end=keep_state)
    # (a rehearsal's rows are shorter than its window)
    n_keys = prompts[0] + n_decode
    probe = granite_probe(cache, cfg, n_keys,
                          min(C, 1 << (n_keys.bit_length() - 1)),
                          engine.attn_impl["mixed"], args.seed)

    # -- the reference: the served weights leave the device, then come
    # back dequantized one layer at a time -----------------------------------
    del cache
    host = jax.device_get(params)
    engine.params = params = None
    ref_cfg = {k: getattr(cfg, k) for k in (
        "rms_norm_eps", "mamba_n_heads", "mamba_d_head", "mamba_n_groups",
        "mamba_d_state", "num_attention_heads", "num_key_value_heads",
        "embedding_multiplier", "attention_multiplier",
        "residual_multiplier", "logits_scaling")}
    mamba = ref.mamba
    jitted = {}

    def jit_mamba(lp, h, config, state=None, tail=None):
        """The mixer under jit: one trace per config (its switches are
        read while tracing), shape and kind of start."""
        key = (tuple(sorted(config.items())), state is None)
        if key not in jitted:
            jitted[key] = jax.jit(lambda lp, h, state, tail: mamba(
                lp, h, config, state, tail))
        return jitted[key]({k: v for k, v in lp.items() if k != "kind"}, h,
                           state, tail)

    ref.mamba = jit_mamba
    ref.mlp = jax.jit(ref.mlp)
    top = {k: dequantized(jax.tree.map(jnp.asarray, host[k]))
           for k in ("embed", "final_norm", "lm_head")}
    slow = np.argsort(np.asarray(host["blocks"]["A_log"][0]))[
        :GRANITE_SLOW_HEADS]

    def reference(which, config=ref_cfg, starts=None):
        """The reference over the jobs `which` -> ({job: logits at its
        compared positions}, {job: each Mamba layer's (S_t, tail)})."""
        t0 = time.monotonic()
        finals = [[] for _ in which]
        logits = ref.forward(
            top, [sequences[i] for i in which], config,
            layers=gh.reference_layers(host["blocks"], cfg), states=finals,
            starts=starts)
        # (the compared positions alone cross to the host)
        logits_of = {i: dict(zip(sorted(got[i]), np.asarray(
            x[jnp.asarray(sorted(got[i]))]))) for i, x in zip(which, logits)}
        finals_of = {i: [(np.asarray(S), np.asarray(t)) for S, t in f]
                     for i, f in zip(which, finals)}
        del logits
        say(f"  reference over {sum(len(sequences[i]) for i in which)} "
            f"tokens in {time.monotonic() - t0:.1f} s")
        return logits_of, finals_of

    def rel(a, b):
        return float(np.linalg.norm(np.asarray(a, np.float64)
                                    - np.asarray(b, np.float64))
                     / np.linalg.norm(np.asarray(b, np.float64)))

    def f32_share(first_layer_states):
        """The share of float32 entries with a bit set under bfloat16's
        mantissa (the least over the states handed in)."""
        return min(float(np.mean(
            np.ascontiguousarray(S, np.float32).view(np.uint32) & 0xFFFF
            != 0)) for S in first_layer_states)

    def readings(which, logits_of, finals_of, against=None):
        """Over the compared positions of the jobs `which`, the served
        logits against `logits_of`: mean and worst |error| / range;
        `mean_edge`; `state_slow`, the first Mamba layer's slowest
        heads' carried state against `finals_of`'s (the worst job);
        `state`, every head of every layer (the worst layer, reported)."""
        errs = logit_errors(got, logits_of, which)
        edge = [float(e.mean()) for _, p, e in errs
                if C <= p < C + GRANITE_EDGE]
        by_layer = [max(rel(states[i][m], finals_of[i][m][0])
                        for i in which)
                    for m in range(len(cfg.mamba_layers))]
        out = {"mean": float(np.mean(np.concatenate(
                   [e for _, _, e in errs]))),
               "max": max(float(e.max()) for _, _, e in errs),
               "mean_edge": float(np.mean(edge)) if edge else 0.0,
               "state_slow": max(rel(states[i][0][slow],
                                     finals_of[i][0][0][slow])
                                 for i in which),
               "state": max(by_layer),
               "state_by_layer": [round(x, 5) for x in by_layer],
               "positions": len(errs)}
        if against is not None:
            to_this = sum(float(np.sum(np.square(
                got[i][p] - logits_of[i][p]))) for i, p, _ in errs)
            to_plain = sum(float(np.sum(np.square(
                got[i][p] - against[i][p]))) for i, p, _ in errs)
            out["nearer"] = (to_this / max(to_plain, 1e-300)) ** 0.5
        return out

    def passes(r):
        return (all(r[k] < limit for k, limit in GRANITE_TOL.items())
                and r["state_f32_share"] > GRANITE_F32_SHARE)

    plain = list(range(twin))
    want, want_finals = reference(plain)
    served = readings(plain, want, want_finals)
    served["attn"] = probe["served"]
    served["state_f32_share"] = f32_share(states[i][0] for i in plain)
    # the served path against itself: the reused slot against the fresh
    assert sorted(got[twin]) == sorted(got[second])

    def apart(logits_at, yardstick):
        return second_request_apart(
            logits_at, yardstick,
            {p: want[second][p] for p in got[second]}, prompts[second])

    served["reuse"] = apart(lambda p: got[second][p], got[twin])
    expected = sum(len({q for q in range(p + n_decode) if compared(i, q)})
                   for i, p in enumerate(prompts[:twin]))
    result = {
        "served": served, "expected_positions": expected,
        "tol": GRANITE_TOL, "state_f32_share_floor": GRANITE_F32_SHARE,
        "seed": args.seed,
        "jobs": [list(j) for j in jobs], "rows_a_step": B, "steps": steps,
        "attention": impl, "device": jax.devices()[0].device_kind,
        "state_dtype": state_dtype, "probe": probe,
        "subwindow": gh.subwindow(cfg, C, page, 2, 2),
        "state_rms_layer0": [round(float(np.sqrt(np.mean(np.square(
            states[i][0])))), 5) for i in plain],
    }
    ok = (served["positions"] == expected and passes(served)
          and state_dtype == "float32")
    if not ok:
        say("FAILED: the served path is outside the tolerance")

    # -- what must NOT pass: the reference, altered, read as the served
    # path is (on the slot that is used twice: its two requests) --------
    if args.negatives:
        short = [opener, second]
        negatives = {
            "bf16_state": dict(config=dict(ref_cfg,
                                           ssm_state_dtype="bfloat16")),
            "scale_sqrt_hd": dict(config=dict(
                ref_cfg, attention_multiplier=cfg.head_dim ** -0.5)),
            "no_embedding_multiplier": dict(config=dict(
                ref_cfg, embedding_multiplier=1.0)),
            "no_residual_multiplier": dict(config=dict(
                ref_cfg, residual_multiplier=1.0)),
            "no_logits_scaling": dict(config=dict(ref_cfg,
                                                  logits_scaling=1.0)),
            "gate_after_norm": dict(config=dict(ref_cfg,
                                                gate_after_norm=True)),
            "conv_tail_dropped": dict(config=dict(ref_cfg, conv_window=C)),
            # the second request starts from what the first left
            "state_not_zeroed": dict(
                starts=[None, list(want_finals[opener])]),
        }
        result["must_fail"] = {}
        for name, kw in negatives.items():
            say(f"negative: {name}")
            logits, finals = reference(short, **kw)
            r = readings(short, logits, finals, against=want)
            # the probe reads exact attention at the other scale
            r["attn"] = (probe["sqrt_hd"] if name == "scale_sqrt_hd"
                         else probe["served"])
            # what a served path that did this would carry: its own S_t
            r["state_f32_share"] = f32_share(
                finals[i][0][0] for i in short)
            r["reuse"] = (apart(lambda p: logits[second][p],
                                {p: want[second][p] for p in got[second]})
                          if "starts" in kw else 0.0)
            result["must_fail"][name] = r
            if passes(r):
                say(f"FAILED: the reference with {name} passes the "
                    "tolerance")
                ok = False
    result["ok"] = bool(ok) or bool(args.rehearse and served["positions"]
                                    == expected)
    result["seconds"] = round(time.monotonic() - t_start, 1)
    with open(os.path.join(OUT_DIR, f"result_granite_seed{args.seed}.json"),
              "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result), flush=True)
    return 0 if result["ok"] else 1


# -- KeyeVL2 -------------------------------------------------------------------


def compare_keye(engine, cell, args, t_start) -> int:
    """The comparison above for a learned sparse indexer over ordinary
    K/V pages: the engine's own mixed and decode trunks with the head at
    the compared positions, every row in every step (the four sequences
    in four rows, fillers decoding in the other four), against
    models/reference/keye_vl2.py TEACHER-FORCED in both of a layer's
    choices: the served path's experts and key sets at every position.
    The selection is compared on its own on that trajectory: the
    reference's own scores and sets at the compared positions."""
    import jax
    import jax.numpy as jnp

    from cake_tpu.models.llama import paged
    from cake_tpu.models.moe import keye_vl2 as kv2
    from cake_tpu.models.reference import keye_vl2 as ref
    from cake_tpu.ops.quant import qmatmul

    cfg, params, rope = engine.config, engine.params, engine.rope
    impl = {k: engine._step_impl(k) for k in ("mixed", "decode")}
    say(f"device {jax.devices()[0].device_kind}; attention {impl}; "
        f"engine built in {time.monotonic() - t_start:.1f} s")
    # (the cell lists the mixed step alone: a benchmark window can hold
    # no decode step; both kinds are held to it here)
    if not args.rehearse and set(impl.values()) != set(
            cell["expect_impl"].values()):
        say(f"FAILED: expected attention {cell['expect_impl']} in both "
            "step kinds")
        return 1
    attn = engine.attn_impl["mixed"]
    B, C = engine.max_slots, engine._mixed_chunk
    page, per_row = engine.cache.page_size, engine.cache.table.shape[1]
    S_row = per_row * page
    L, K = cfg.num_hidden_layers, min(cfg.index_topk, S_row)
    prompts = KEYE_PROMPTS if not args.rehearse else (110, 70, 60, 30)
    n_decode = KEYE_DECODE if not args.rehearse else 6
    last = LAST if not args.rehearse else 12
    rng = np.random.default_rng(args.seed)
    sequences = [rng.integers(0, cfg.vocab_size, p + n_decode)
                 for p in prompts]
    assert len(sequences) <= B and max(prompts) + n_decode <= S_row
    fillers = list(range(len(sequences), B))
    n_steps_most = sum(-(-p // C) for p in prompts) + n_decode
    table = rows_table(engine, whole=set(range(len(sequences))),
                       other_pages=-(-n_steps_most // page))
    cache = engine.cache._replace(table=jnp.asarray(table))
    engine.cache = None
    filler_tokens = rng.integers(0, cfg.vocab_size, (B, n_steps_most))

    def sets_of(picked):
        """Sets [L, C, S] bool (a window's queries, or the rows' single
        tokens) as ascending index lists [L, C, K] uint16, S_row where
        a set has fewer than K."""
        score = jnp.where(picked, S_row - jnp.arange(S_row), 0)
        top, _ = jax.lax.top_k(score, K)
        return jnp.where(top > 0, S_row - top, S_row).astype(jnp.uint16)

    @partial(jax.jit, static_argnames=("n_tokens",),
             donate_argnames=("cache",))
    def window_step(params, tokens, pos, q_len, active, cache, n_tokens):
        out, _ = kv2.mixed_trunk(params, tokens, pos, q_len, active, cache,
                                 rope, cfg, attn, n_tokens, probe=True)
        return (out.x, out.cache, out.experts, sets_of(out.selected),
                out.n_selected, sets_of(out.selected_window))

    @partial(jax.jit, donate_argnames=("cache",))
    def decode_step(params, tokens, pos, active, cache):
        out = kv2.decode_trunk(params, tokens, cache, pos, active, rope,
                               cfg, attn, probe=True)
        return (out.x, out.cache, out.experts, sets_of(out.selected),
                out.n_selected)

    @jax.jit
    def head(params, x):
        return qmatmul(x, params["lm_head"]).astype(jnp.float32)

    def compared(b, position):
        """The prompt's last positions and every decode step."""
        return position >= prompts[b] - last

    got = [dict() for _ in sequences]       # position -> logits [V]
    # every position's choices, for the teacher-forced reference
    routed = [np.zeros((L, len(s), cfg.num_experts_per_tok), np.int32)
              for s in sequences]
    picked = [np.full((L, len(s), K), S_row, np.uint16) for s in sequences]
    off = [0] * len(sequences)
    steps = {"mixed": 0, "decode": 0}
    n_steps = 0
    t0 = time.monotonic()
    while any(off[b] < len(s) for b, s in enumerate(sequences)):
        prefilling = [b for b in range(len(sequences))
                      if off[b] < prompts[b]]
        qlen = np.zeros(B, np.int32)
        pos = np.zeros(B, np.int32)
        for b, seq in enumerate(sequences):
            if prefilling and b == prefilling[0]:
                qlen[b] = min(C, prompts[b] - off[b])
            elif prompts[b] <= off[b] < len(seq) and (
                    not prefilling
                    or off[b] < prompts[b] + n_decode // 2):
                qlen[b] = 1     # half the decode steps ride mixed steps
            pos[b] = off[b]
        qlen[fillers], pos[fillers] = 1, n_steps
        toks = np.zeros((B, C if prefilling else 1), np.int32)
        for b, seq in enumerate(sequences):
            toks[b, :qlen[b]] = seq[off[b]:off[b] + qlen[b]]
        toks[fillers, 0] = filler_tokens[fillers, n_steps]
        if prefilling:
            x, cache, experts, selected, n_sel, sets = window_step(
                params, jnp.asarray(toks), jnp.asarray(pos),
                jnp.asarray(qlen), jnp.asarray(qlen > 0), cache,
                paged.mixed_bucket_for(engine._mixed_buckets,
                                       int(qlen.sum())))
            first = np.cumsum(qlen) - qlen
            sets = np.asarray(sets)
            steps["mixed"] += 1
        else:
            x, cache, experts, selected, n_sel = decode_step(
                params, jnp.asarray(toks), jnp.asarray(pos),
                jnp.asarray(qlen > 0), cache)
            first = np.arange(B)
            steps["decode"] += 1
        experts, selected, n_sel = (np.asarray(experts),
                                    np.asarray(selected), np.asarray(n_sel))
        wanted = []
        for b in range(len(sequences)):
            n = int(qlen[b])
            if not n:
                continue
            routed[b][:, off[b]:off[b] + n] = experts[
                :, first[b]:first[b] + n]
            if n > 1:
                picked[b][:, off[b]:off[b] + n] = sets[:, :n]
            else:
                picked[b][:, off[b], :n_sel[b]] = selected[:, b, :n_sel[b]]
            wanted += [(b, off[b] + j, first[b] + j) for j in range(n)
                       if compared(b, off[b] + j)]
            off[b] += n
        if wanted:
            fetched = np.asarray(head(
                params, x[jnp.asarray([r for _, _, r in wanted])]))
            for n, (b, position, _) in enumerate(wanted):
                got[b][position] = fetched[n]
        n_steps += 1
    say(f"served path: {steps['mixed']} mixed and {steps['decode']} decode "
        f"steps of {B} rows in {time.monotonic() - t0:.1f} s")

    # -- the reference: the served weights leave the device, then come
    # back dequantized one layer at a time --------------------------------
    del cache, x
    host = jax.device_get(params)
    engine.params = params = None
    ref_cfg = kv2.reference_config(cfg)
    plain_mm = ref.mm

    def compiled(mm=plain_mm):
        """The reference's heavy functions under jit, traced anew (so
        that a replaced `mm` is what they run)."""
        ref.mm = mm
        for name, static in (("attend_block", ()), ("score_block", ()),
                             ("select_block", ("topk",)),
                             ("sets_as_mask", ("S",)), ("swiglu", ())):
            fn = getattr(ref, name)
            fn = getattr(fn, "__wrapped__", fn)
            setattr(ref, name, jax.jit(fn, static_argnames=static))

    def layers():
        # from the host copy: one layer's leaves cross to the device at
        # a time, as stored, and widen there
        yield from kv2.reference_layers(host["blocks"], cfg)

    top = {k_: dequantized(jax.tree.map(jnp.asarray, host[k_]))
           for k_ in ("embed", "final_norm", "lm_head")}
    keep = [np.asarray(sorted(g)) for g in got]

    def reference(which, config=ref_cfg, forced_sets=True):
        """The reference over the sequences `which`, teacher-forced in
        its experts and (forced_sets) its key sets -> ({b: logits at
        keep[b]}, {b: its own experts}, {b: its own scores and sets at
        keep[b], a layer})."""
        t0 = time.monotonic()
        routing = [[] for _ in which]
        seen = [[] for _ in which]
        logits = ref.forward(
            top, [sequences[b] for b in which], config, layers=layers(),
            routing=routing, selections=seen,
            forced=[list(routed[b]) for b in which],
            forced_sets=([list(picked[b]) for b in which]
                         if forced_sets else None),
            keep=[keep[b] for b in which])
        say(f"  reference over {sum(len(sequences[b]) for b in which)} "
            f"tokens in {time.monotonic() - t0:.1f} s")
        return (dict(zip(which, (np.asarray(v) for v in logits))),
                dict(zip(which, routing)), dict(zip(which, seen)))

    def readings(which, logits_of, routing_of, seen_of, topk):
        """Over the compared positions of `which`: mean and worst
        |error| / range of the logits under topk (every visible key is
        attended) and beyond it; `keys`, the least layer's mean share
        of a query's set both sides chose; `margin`, the worst
        disagreement's distance from the reference's threshold over
        the visible scores' standard deviation; `agree`, the least
        layer's share of positions with the reference's experts."""
        dense = {"sum": 0.0, "n": 0, "worst": 0.0}
        sparse = {"sum": 0.0, "n": 0, "worst": 0.0}
        shared, same = np.zeros(L), np.zeros(L)
        n_sparse = n_all = 0
        margin, disagreed = 0.0, 0
        for b in which:
            for n, position in enumerate(keep[b]):
                w = logits_of[b][n]
                err = np.abs(got[b][position] - w) / float(w.max() - w.min())
                acc = dense if position < topk else sparse
                acc["sum"] += float(err.sum())
                acc["n"] += err.size
                acc["worst"] = max(acc["worst"], float(err.max()))
                n_all += 1
                same += [set(routed[b][j, position].tolist())
                         == set(routing_of[b][j][position].tolist())
                         for j in range(L)]
                if position < topk:
                    continue
                n_sparse += 1
                for j in range(L):
                    theirs = seen_of[b][j]["sets"][n]
                    ours = np.zeros_like(theirs)
                    mine = picked[b][j, position]
                    ours[mine[mine < theirs.shape[0]]] = True
                    both = int(np.sum(ours & theirs))
                    shared[j] += both / max(int(ours.sum()),
                                            int(theirs.sum()))
                    apart = ours ^ theirs
                    if apart.any():
                        scores = seen_of[b][j]["scores"][n]
                        visible = scores[:position + 1]
                        threshold = scores[theirs].min()
                        margin = max(margin, float(
                            np.abs(scores[apart] - threshold).max()
                            / max(float(visible.std()), 1e-30)))
                        disagreed += int(apart.sum())
        return {
            "mean_dense": dense["sum"] / max(dense["n"], 1),
            "max_dense": dense["worst"],
            "mean": sparse["sum"] / max(sparse["n"], 1),
            "max": max(sparse["worst"], dense["worst"]),
            "keys": float(shared.min()) / max(n_sparse, 1),
            "keys_by_layer": [round(float(v) / max(n_sparse, 1), 5)
                              for v in shared],
            "margin": margin, "keys_apart": disagreed,
            "agree": float(same.min()) / max(n_all, 1),
            "positions": n_all, "positions_past_topk": n_sparse}

    def passes(r):
        return (all(r[k_] < limit for k_, limit in KEYE_TOL.items())
                and r["keys"] >= KEYE_KEYS)

    compiled()
    everyone = list(range(len(sequences)))
    served = readings(everyone, *reference(everyone), cfg.index_topk)
    expected = sum(min(last, p) + n_decode for p in prompts)
    result = {
        "served": served, "expected_positions": expected, "tol": KEYE_TOL,
        "keys_floor": KEYE_KEYS, "seed": args.seed,
        "prompts": list(prompts), "rows_a_step": B, "steps": steps,
        "attention": impl, "device": jax.devices()[0].device_kind,
        "query_tile": kv2.query_tile(
            C, cfg.num_attention_heads, cfg.num_key_value_heads,
            cfg.head_dim, page, *([2, 2] if not args.rehearse else [4, 4]))}
    ok = served["positions"] == expected and passes(served)
    if not ok:
        say("FAILED: the served path is outside the tolerance")

    # -- what must NOT pass: the reference, altered, read as the served
    # path is (on the shortest sequences) ---------------------------------
    if args.negatives:
        short = sorted(everyone, key=lambda b: len(sequences[b]))[
            :args.negatives]
        result["must_fail"] = {}
        # the reference's own selection at half the published topk:
        # another model past 1,024 keys
        half = dict(ref_cfg, topk=cfg.index_topk // 2)
        result["must_fail"]["selection_of_half_topk"] = readings(
            short, *reference(short, config=half, forced_sets=False),
            cfg.index_topk // 2)
        compiled(mm=lambda a, w: plain_mm(fake_int8(a), w))
        result["must_fail"]["int8_activations"] = readings(
            short, *reference(short), cfg.index_topk)
        compiled()
        for name, r in result["must_fail"].items():
            if passes(r):
                say(f"FAILED: the reference with {name} passes the "
                    "tolerance")
                ok = False
    result["ok"] = bool(ok) or bool(args.rehearse and served["positions"]
                                    == expected)
    result["seconds"] = round(time.monotonic() - t_start, 1)
    with open(os.path.join(OUT_DIR, f"result_keye_seed{args.seed}.json"),
              "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result), flush=True)
    return 0 if result["ok"] else 1


# -- brumby --------------------------------------------------------------------


def brumby_steps(engine):
    """trunk_steps for a family with no sparse layer
    (models/moe/brumby.py): the three expert outputs drive_jobs carries
    are empty."""
    import jax
    import jax.numpy as jnp

    from cake_tpu.models.moe import brumby as br

    cfg, rope, attn = engine.config, engine.rope, engine.attn_impl["mixed"]

    def outputs(params, out):
        T = out.x.shape[0]
        return (br.logits_of(out.x, params), out.cache,
                jnp.zeros((0, T, 0), jnp.int32), jnp.zeros((T, 1)),
                jnp.zeros((T, 1)))

    @partial(jax.jit, static_argnames=("n_tokens",),
             donate_argnames=("cache",))
    def window_step(params, tokens, pos, q_len, active, cache, n_tokens):
        out, _ = br.mixed_trunk(params, tokens, pos, q_len, active, cache,
                                rope, cfg, attn, n_tokens)
        return outputs(params, out)

    @partial(jax.jit, donate_argnames=("cache",))
    def decode_step(params, tokens, pos, active, cache):
        return outputs(params, br.decode_trunk(params, tokens, cache, pos,
                                               active, rope, cfg, attn))

    return window_step, decode_step


def brumby_exact_state(S, hd: int):
    """A row and layer's stored S [KV, NB, dv, DB] (16 x 16 tiles of the
    upper triangle of tiles, transposed, in blocks: ops/retention.py) in
    the exact triangle's layout [KV, hd (hd + 1) / 2, dv], as
    models/reference/brumby.phi orders it: entry (i, j), i <= j, lies in
    tile pair (i // 16, j // 16) at (i % 16, j % 16); inside a diagonal
    pair it was kept at weight 1 (both orders), so it takes the sqrt 2."""
    from cake_tpu.ops import retention

    KV, NB, dv, DB = S.shape
    S = np.moveaxis(np.asarray(S), 2, 3).reshape(KV, NB * DB, dv)
    pairs = {p: n for n, p in enumerate(retention.tile_pairs(hd))}
    i, j = np.triu_indices(hd)
    T = retention.TILE
    at = np.asarray([pairs[a // T, b // T] for a, b in zip(i, j)])
    index = at * retention.PAIR + (i % T) * T + (j % T)
    scale = np.where((i // T == j // T) & (i != j), np.sqrt(2.0), 1.0)
    return S[:, index, :] * scale[None, :, None].astype(np.float32)


def compare_brumby(engine, cell, args, t_start) -> int:
    """The comparison above for power retention in every layer, a state
    a row and K/V head beside a page pool of no layers: the engine's own
    mixed and decode trunks with the head at every position, all 16 rows
    in every step (drive_jobs), against models/reference/brumby.py's
    full forward in its QUADRATIC form (no discrete choice: nothing is
    teacher-forced), logits and the first layer's stored S. Slot 1 takes
    a second request when its first has finished, and its twin runs
    beside it in a slot nothing has used."""
    import jax
    import jax.numpy as jnp

    from cake_tpu.models.moe import brumby as br
    from cake_tpu.models.reference import brumby as ref

    cfg, params = engine.config, engine.params
    impl = {k: engine._step_impl(k) for k in ("mixed", "decode")}
    say(f"device {jax.devices()[0].device_kind}; attention {impl}; "
        f"engine built in {time.monotonic() - t_start:.1f} s")
    if not args.rehearse and impl != cell["expect_impl"]:
        say(f"FAILED: expected attention {cell['expect_impl']}")
        return 1
    B, C = engine.max_slots, engine._mixed_chunk
    page, per_row = engine.cache.page_size, engine.cache.table.shape[1]
    hd = cfg.head_dim
    jobs = BRUMBY_JOBS if not args.rehearse else ((0, 50), (1, 31), (1, 20))
    second = len(jobs) - 1
    opener = next(i for i, (slot, _) in enumerate(jobs)
                  if slot == jobs[second][0])
    twin = len(jobs)
    jobs = (*jobs, (max(slot for slot, _ in jobs) + 1, jobs[second][1]))
    n_decode = BRUMBY_DECODE if not args.rehearse else 6
    last = BRUMBY_LAST if not args.rehearse else 12
    rng = np.random.default_rng(args.seed)
    sequences = [rng.integers(0, cfg.vocab_size, p + n_decode)
                 for _, p in jobs[:twin]]
    sequences.append(sequences[second])
    prompts = [p for _, p in jobs]
    assert max(prompts) + n_decode <= per_row * page
    assert engine.cache.memory_bytes() == 0      # a pool of no layers
    cache = engine.cache._replace(table=jnp.asarray(rows_table(engine)))
    state_dtype = str(cache.ssm.dtype)
    engine.cache = None
    states = [None] * len(jobs)     # the first layer's S at a job's end

    def compared(i, position):
        """The prompt's last positions, every decode step, the request's
        first positions and those behind the first window edge."""
        return (position >= prompts[i] - last or position < BRUMBY_START
                or C <= position < C + BRUMBY_EDGE)

    def keep_state(i, slot, cache):
        states[i] = brumby_exact_state(cache.ssm[0, slot], hd)

    got, _, _, steps, cache = drive_jobs(
        engine, params, cache, brumby_steps(engine), jobs, sequences,
        prompts, compared, rng, waits_for={twin: opener}, at_end=keep_state)

    # -- the reference: the served weights leave the device, then come
    # back dequantized one layer at a time -----------------------------------
    del cache
    host = jax.device_get(params)
    engine.params = params = None
    ref_cfg = {k: getattr(cfg, k) for k in (
        "rms_norm_eps", "num_attention_heads", "num_key_value_heads",
        "head_dim", "rope_theta")}

    def jit_by_config(fn):
        """`fn(..., config, ...)` under jit, one trace a config (its
        switches are read while tracing) and shape."""
        jitted = {}

        def call(*a, **kw):
            a = list(a)
            at = next(n for n, x in enumerate(a) if isinstance(x, dict)
                      and "rms_norm_eps" in x)
            config = a.pop(at)
            key = tuple(sorted(config.items()))
            if key not in jitted:
                jitted[key] = jax.jit(lambda *b, **k: fn(
                    *b[:at], config, *b[at:], **k))
            return jitted[key](*a, **kw)

        return call

    for name in ("project", "quadratic", "recurrent"):
        setattr(ref, name, jit_by_config(getattr(ref, name)))
    top = {k: dequantized(jax.tree.map(jnp.asarray, host[k]))
           for k in ("embed", "final_norm", "lm_head")}

    @jax.jit
    def state_of(k, v, lg):
        """The reference's own sum over a sequence's keys: S [KV, hd (hd
        + 1) / 2, hd] at its end (`k`, `v`, `lg` as `layer` keeps them)."""
        cum = jnp.cumsum(lg, axis=0)
        w = jnp.exp(cum[-1][None, :] - cum)              # [S, KV]
        return jnp.einsum("sgd,sgv->gdv", ref.phi(k) * w[:, :, None], v,
                          precision=jax.lax.Precision.HIGHEST)

    def reference(which, config=ref_cfg, before=None):
        """The reference over the jobs `which` -> ({job: logits at its
        compared positions}, {job: the first layer's S at its end})."""
        t0 = time.monotonic()
        kept = [[] for _ in which]
        logits = ref.forward(
            top, [sequences[i] for i in which], config,
            layers=br.reference_layers(host["blocks"], cfg), kept=kept,
            before=before, keep=[sorted(got[i]) for i in which])
        logits_of = {i: dict(zip(sorted(got[i]), np.asarray(x)))
                     for i, x in zip(which, logits)}
        finals_of = {}
        for i, layers in zip(which, kept):
            first = layers[0]
            # (the recurrent form hands its own carried state over)
            finals_of[i] = np.asarray(
                first[3][0] if len(first) == 4 else state_of(*first[:3]))
        del logits
        say(f"  reference over {sum(len(sequences[i]) for i in which)} "
            f"tokens in {time.monotonic() - t0:.1f} s")
        return logits_of, finals_of, kept

    def rel(a, b):
        return float(np.linalg.norm(np.asarray(a, np.float64)
                                    - np.asarray(b, np.float64))
                     / np.linalg.norm(np.asarray(b, np.float64)))

    def readings(which, logits_of, finals_of, against=None):
        """Over the compared positions of the jobs `which`, the served
        logits against `logits_of`: mean and worst |error| / range;
        `mean_edge` (reported); `state`, the first layer's stored S
        against `finals_of`'s (the worst job)."""
        errs = logit_errors(got, logits_of, which)
        edge = [float(e.mean()) for _, p, e in errs
                if C <= p < C + BRUMBY_EDGE]
        out = {"mean": float(np.mean(np.concatenate(
                   [e for _, _, e in errs]))),
               "max": max(float(e.max()) for _, _, e in errs),
               "mean_edge": float(np.mean(edge)) if edge else 0.0,
               "state": max(rel(states[i], finals_of[i]) for i in which),
               "positions": len(errs)}
        if against is not None:
            to_this = sum(float(np.sum(np.square(
                got[i][p] - logits_of[i][p]))) for i, p, _ in errs)
            to_plain = sum(float(np.sum(np.square(
                got[i][p] - against[i][p]))) for i, p, _ in errs)
            out["nearer"] = (to_this / max(to_plain, 1e-300)) ** 0.5
            # the altered reference against the plain one: what the
            # alteration alone moves, without the served path's own error
            moved = logit_errors(logits_of, against, which)
            out["from_plain"] = {
                "mean": float(np.mean(np.concatenate(
                    [e for _, _, e in moved]))),
                "max": max(float(e.max()) for _, _, e in moved)}
        return out

    def passes(r):
        return all(r[k] < limit for k, limit in BRUMBY_TOL.items())

    plain = list(range(twin))
    want, want_finals, want_kept = reference(plain)
    served = readings(plain, want, want_finals)
    # the served path against itself: the reused slot against the fresh
    assert sorted(got[twin]) == sorted(got[second])

    def apart(logits_at, yardstick, decode=False):
        return second_request_apart(
            logits_at, yardstick,
            {p: want[second][p] for p in got[second]}, prompts[second],
            decode)

    served["reuse"] = apart(lambda p: got[second][p], got[twin])
    served["reuse_decode"] = apart(lambda p: got[second][p], got[twin],
                                   decode=True)
    expected = sum(len({q for q in range(p + n_decode) if compared(i, q)})
                   for i, p in enumerate(prompts[:twin]))
    result = {
        "served": served, "expected_positions": expected,
        "tol": BRUMBY_TOL, "seed": args.seed,
        "jobs": [list(j) for j in jobs], "rows_a_step": B, "steps": steps,
        "attention": impl, "device": jax.devices()[0].device_kind,
        "state_dtype": state_dtype,
        # the first layer's S, root mean square an entry, at each job's
        # end (after 8,124, 2,024 and 1,974 tokens): it must neither die
        # nor blow up under the draw
        "state_rms_layer0": [round(float(np.sqrt(np.mean(np.square(
            states[i])))), 5) for i in plain],
    }
    ok = (served["positions"] == expected and passes(served)
          and state_dtype == "float32")
    if not ok:
        say("FAILED: the served path is outside the tolerance")

    # -- what must NOT pass: the reference, altered, read as the served
    # path is (on the slot that is used twice: its two requests) --------
    if args.negatives:
        short = [opener, second]
        negatives = {
            "bf16_state": dict(config=dict(ref_cfg, form="recurrent",
                                           state_dtype="bfloat16")),
            # the float32 state READ at the matrix unit's one-pass
            # precision (phi(q), S and z rounded to bfloat16 as operands,
            # float32 sums): what the window form's products would be at
            # default precision
            "bf16_read": dict(config=dict(ref_cfg, form="recurrent",
                                          read_dtype="bfloat16")),
            "no_gate": dict(config=dict(ref_cfg, gate=False)),
            "no_normaliser": dict(config=dict(ref_cfg, normaliser=False)),
            "no_rotation": dict(config=dict(ref_cfg, rope=False)),
            "degree_1": dict(config=dict(ref_cfg, degree=1)),
            # the second request starts from what the first left
            "state_not_zeroed": dict(before=[None, want_kept[opener]]),
        }
        result["must_fail"] = {}
        for name, kw in negatives.items():
            say(f"negative: {name}")
            logits, finals, _ = reference(short, **kw)
            r = readings(short, logits, finals, against=want)
            r["reuse"] = (apart(lambda p: logits[second][p],
                                {p: want[second][p] for p in got[second]})
                          if "before" in kw else 0.0)
            result["must_fail"][name] = r
            if passes(r):
                say(f"FAILED: the reference with {name} passes the "
                    "tolerance")
                ok = False
    result["ok"] = bool(ok) or bool(args.rehearse and served["positions"]
                                    == expected)
    result["seconds"] = round(time.monotonic() - t_start, 1)
    with open(os.path.join(OUT_DIR, f"result_brumby_seed{args.seed}.json"),
              "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result), flush=True)
    return 0 if result["ok"] else 1


# -- longcat_flash -------------------------------------------------------------


def latent_window_probe(pool, table, row: int, n_keys: int, width: int,
                        geo, attn: str, seed: int, keys, norm) -> dict:
    """`cake_mla_window_attn` ITSELF under causality (all of prefill)
    against exact attention: `row`'s last `width` positions as one
    window over the pages the served path wrote, queries drawn as
    latent_pages_probe draws them (`keys`, `norm`: its returns).
    -> {"served", "bf16_softmax"}."""
    import jax
    import jax.numpy as jnp

    from cake_tpu.ops import mla_attention as mla

    R, row_w = geo.kv_lora_rank, pool.shape[-1]
    C = min(width, n_keys)
    win_pos = jnp.arange(n_keys - C, n_keys)
    qw = (jax.random.normal(jax.random.PRNGKey(seed + 2),
                            (C, geo.heads, row_w), jnp.float32)
          * (DSV2_PROBE_SPREAD / (norm * geo.softmax_scale))
          ).astype(pool.dtype)
    served = np.asarray(mla.attend_window(
        qw, pool, 0, jnp.asarray(np.asarray(table)[row]), None,
        jnp.int32(n_keys - 1), R, geo.softmax_scale, impl=attn,
        positions=win_pos.astype(jnp.int32)), np.float64)

    @partial(jax.jit, static_argnames="scores_dtype")
    def exact_block(qb, pos_b, scores_dtype):
        with jax.default_matmul_precision("highest"):
            kf, qf = keys.astype(jnp.float32), qb.astype(jnp.float32)
            s = (jnp.einsum("chw,sw->chs", qf, kf)
                 * geo.softmax_scale).astype(scores_dtype)
            s = jnp.where((jnp.arange(n_keys)[None, :]
                           <= pos_b[:, None])[:, None, :], s, -jnp.inf)
            p = jax.nn.softmax(s, axis=-1).astype(jnp.float32)
            return jnp.einsum("chs,sr->chr", p, kf[:, :R])

    def exact(scores_dtype):
        # (in blocks of queries: [C, heads, keys] float32 would be a GB)
        step = max(1, C // 8)
        return np.concatenate([np.asarray(exact_block(
            qw[i:i + step], win_pos[i:i + step], scores_dtype), np.float64)
            for i in range(0, C, step)])

    want = exact(jnp.float32)
    out = {"served": probe_rel(served, want),
           "bf16_softmax": probe_rel(exact(jnp.bfloat16), want)}
    say(f"probe of the window pass: kernel {out['served']:.3e}, exact "
        f"attention with a bfloat16 softmax {out['bf16_softmax']:.3e}")
    return out


def compare_longcat(engine, cell, args, t_start) -> int:
    """The comparison above for shortcut-connected layers: the engine's
    own mixed and decode trunks with the head at every position, 32
    rows in every step, against models/reference/longcat_flash.py on
    teacher-forced experts."""
    import jax
    import jax.numpy as jnp

    from cake_tpu.models.moe import glm_dsa
    from cake_tpu.models.reference import longcat_flash as ref
    from cake_tpu.ops.moe import LayerOf

    cfg, params = engine.config, engine.params
    impl = {k: engine._step_impl(k) for k in ("mixed", "decode")}
    say(f"device {jax.devices()[0].device_kind}; attention {impl}; "
        f"engine built in {time.monotonic() - t_start:.1f} s")
    if not args.rehearse and impl != cell["expect_impl"]:
        say(f"FAILED: expected attention {cell['expect_impl']}")
        return 1
    attn = engine.attn_impl["mixed"]

    B, C = engine.max_slots, engine._mixed_chunk
    page, per_row = engine.cache.page_size, engine.cache.table.shape[1]
    jobs = LONGCAT_JOBS if not args.rehearse else ((0, 70), (1, 30))
    n_decode = LONGCAT_DECODE if not args.rehearse else 6
    last = LONGCAT_LAST if not args.rehearse else 12
    rng = np.random.default_rng(args.seed)
    sequences = [rng.integers(0, cfg.vocab_size, p + n_decode)
                 for _, p in jobs]
    prompts = [p for _, p in jobs]
    assert max(prompts) + n_decode <= per_row * page
    # the jobs' rows whole; a filler decodes from position 0 for as many
    # steps as the jobs take
    steps_bound = sum(-(-p // C) for p in prompts) + n_decode + 2
    table = rows_table(engine, whole={slot for slot, _ in jobs},
                       other_pages=-(-steps_bound // page))
    cache = engine.cache._replace(table=jnp.asarray(table))
    engine.cache = None
    Ls, k = len(cfg.shortcut_layers), cfg.num_experts_per_tok

    def compared(i, position):
        """The prompt's last positions, every decode step, the request's
        first positions and those behind the first window edge."""
        return (position >= prompts[i] - last or position < LONGCAT_START
                or C <= position < C + LONGCAT_EDGE)

    got, ffn_in, all_routed, steps, cache = drive_jobs(
        engine, params, cache,
        # the first shortcut sublayer's FFN input: TrunkOut.probe's third
        trunk_steps(glm_dsa, engine, ffn_input=lambda out: out.probe[2]),
        jobs, sequences, prompts, compared, rng)
    zero_share = float(np.mean(np.concatenate(
        [r.reshape(-1) for r in all_routed]) >= cfg.n_routed_experts_total))

    # -- the probes: both latent kernels at this model's head count
    # against exact attention over the pages the served path wrote (the
    # first sublayer, the longest job's row), under DeepSeek-V2's limit
    geo = cfg.geometry(0)
    n_keys = len(sequences[0])
    probe, keys, norm = latent_pages_probe(cache.k, table, jobs[0][0],
                                           n_keys, geo, attn, args.seed)
    probe_window = latent_window_probe(cache.k, table, jobs[0][0], n_keys,
                                       C, geo, attn, args.seed, keys, norm)

    # -- the reference: the served weights leave the device (but for
    # the first layer's MoE reading below), then come back dequantized
    # one sublayer at a time --------------------------------------------
    del cache, keys
    host = jax.device_get(params)
    engine_params, engine.params, params = params, None, None
    ref_cfg = dict(
        {k_: getattr(cfg, k_) for k_ in (
            "num_attention_heads", "hidden_size", "q_lora_rank",
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
            "rms_norm_eps", "rope_theta", "mla_scale_q_lora",
            "mla_scale_kv_lora", "routed_scaling_factor")},
        n_routed_experts=cfg.n_routed_experts_total, moe_topk=k)
    held = (cfg.first_routed_expert, cfg.num_local_experts)
    ref.attend_block = jax.jit(ref.attend_block,
                               static_argnames=("scale", "dtype"))

    def widened(v):
        if isinstance(v, dict):
            return {k_: widened(x) for k_, x in v.items()}
        if isinstance(v, LayerOf):
            v = jax.tree.map(lambda a: a[int(v.layer)], v.stacked)
        return dequantized(jax.tree.map(jnp.asarray, v))

    def layers():
        # from the host copy: one sublayer's leaves cross to the device
        # at a time, as stored, and widen there
        for i in range(cfg.num_hidden_layers):
            yield widened(glm_dsa.layer_leaves(host["blocks"], cfg, i))

    top = {k_: dequantized(jax.tree.map(jnp.asarray, host[k_]))
           for k_ in ("embed", "final_norm", "lm_head")}

    def reference(which, config=ref_cfg):
        """The reference over the jobs `which`, TEACHER-FORCED in its
        experts -> ({job: logits}, {job: its own choice along that
        trajectory})."""
        t0 = time.monotonic()
        seqs = [sequences[i] for i in which]
        routing = [[] for _ in seqs]
        logits = ref.forward(top, seqs, config, layers=layers(), held=held,
                             routing=routing,
                             forced=[list(all_routed[i]) for i in which])
        say(f"  reference over {sum(len(s_) for s_ in seqs)} tokens in "
            f"{time.monotonic() - t0:.1f} s")
        return (dict(zip(which, (np.asarray(x) for x in logits))),
                dict(zip(which, routing)))

    # -- the first layer's MoE on the served path's OWN input: the
    # served ops/moe.moe_mlp over the compared positions' inputs as one
    # batch against the reference's moe_ffn on the same inputs and the
    # same experts, under the plain config and under every altered one
    # (what the logits cannot tell: a weight that is a few per cent off)
    first = next(layers())["shortcut"]
    router = {k_: first[k_] for k_ in ("router", "router_bias")}
    at = [(i, position) for i in range(len(jobs))
          for position in sorted(got[i])]
    h_in = jnp.asarray(np.stack([ffn_in[i][position][0]
                                 for i, position in at]))
    served_moe = np.asarray(jax.jit(
        lambda lp, h: glm_dsa.ffn(lp, h, jnp.ones(h.shape[0], bool),
                                  cfg)[0])(
        glm_dsa.layer_leaves(engine_params["blocks"], cfg, 0)["shortcut"],
        h_in), np.float64)
    chosen = np.stack([all_routed[i][0, position] for i, position in at])
    moe_err = {}
    for name, switch in {"plain": {}, **LONGCAT_NEGATIVES}.items():
        with jax.default_matmul_precision("highest"):
            want_moe = ref.moe_ffn(first, h_in.astype(jnp.float32),
                                   dict(ref_cfg, **switch), held,
                                   forced=chosen)
        moe_err[name] = probe_rel(served_moe, np.asarray(want_moe,
                                                         np.float64))
    say("the first layer's MoE on its own input: " + ", ".join(
        f"{name} {err:.3e}" for name, err in moe_err.items()))
    del first, engine_params, want_moe

    def readings(which, logits_of, routing_of, config=ref_cfg,
                 against=None, name="plain"):
        """Over the compared positions of the jobs `which`, the served
        logits against `logits_of`: mean and worst |error| / range;
        `moe_err`, the first layer's MoE on its own input (above);
        `agree` along the forced trajectory (the least over the layers);
        the router on the served path's own input; against: the plain
        reference's logits (`nearer`, reported)."""
        errs = logit_errors(got, logits_of, which)
        same = np.zeros(Ls)
        to_this = to_plain = 0.0
        for i, position, _ in errs:
            same += [set(all_routed[i][layer, position].tolist())
                     == set(routing_of[i][layer][position].tolist())
                     for layer in range(Ls)]
            if against is not None:
                to_this += float(np.sum(np.square(
                    got[i][position] - logits_of[i][position])))
                to_plain += float(np.sum(np.square(
                    got[i][position] - against[i][position])))
        agree_same, logit_err = same_router_input(
            ref, router, got, ffn_in, all_routed, which, config)
        out = {"mean": float(np.mean(np.concatenate(
                   [e for _, _, e in errs]))),
               "max": max(float(e.max()) for _, _, e in errs),
               "mean_decode": float(np.mean(np.concatenate(
                   [e for i, p_, e in errs if p_ >= prompts[i]]))),
               "agree": float(same.min()) / len(errs),
               "agree_same_input": agree_same,
               "router_logit_err": logit_err, "moe_err": moe_err[name],
               "positions": len(errs)}
        if against is not None:
            out["nearer"] = (to_this / max(to_plain, 1e-300)) ** 0.5
        return out

    def passes(r):
        return (all(r[k_] < limit for k_, limit in LONGCAT_TOL.items())
                and r["agree_same_input"] > LONGCAT_AGREE
                and r["probe"] < DSV2_TOL["probe"]
                and r["probe_window"] < DSV2_TOL["probe"])

    plain = list(range(len(jobs)))
    want, want_routing = reference(plain)
    served = readings(plain, want, want_routing)
    served["probe"], served["probe_window"] = (probe["served"],
                                               probe_window["served"])
    expected = sum(
        len({q for q in range(p + n_decode)
             if q >= p - last or q < LONGCAT_START
             or C <= q < C + LONGCAT_EDGE}) for p in prompts)
    result = {
        "served": served, "expected_positions": expected,
        "tol": dict(LONGCAT_TOL, probe=DSV2_TOL["probe"],
                    agree_same_input_floor=LONGCAT_AGREE),
        "probe": probe, "probe_window": probe_window,
        "zero_pairs_share": round(zero_share, 4), "seed": args.seed,
        "jobs": [list(j) for j in jobs], "rows_a_step": B, "steps": steps,
        "attention": impl, "device": jax.devices()[0].device_kind,
        "heads": geo.heads,
    }
    ok = served["positions"] == expected and passes(served)
    if not ok:
        say("FAILED: the served path is outside the tolerance")

    # -- what must NOT pass: the reference, altered, read as the served
    # path is (on the shorter job; the probes' reading is the served
    # kernels' but for the softmax's own negative) -----------------------
    if args.negatives:
        short = [min(plain, key=lambda i: prompts[i])]
        result["must_fail"] = {}
        for name, switch in LONGCAT_NEGATIVES.items():
            say(f"negative: {name}")
            config = dict(ref_cfg, **switch)
            logits, routing = reference(short, config)
            r = readings(short, logits, routing, config=config,
                         against=want, name=name)
            r["probe"] = probe.get(name, probe["served"])
            r["probe_window"] = probe_window.get(name,
                                                 probe_window["served"])
            result["must_fail"][name] = r
            if passes(r):
                say(f"FAILED: the reference with {name} passes the "
                    "tolerance")
                ok = False
    result["ok"] = bool(ok) or bool(args.rehearse and served["positions"]
                                    == expected)
    result["seconds"] = round(time.monotonic() - t_start, 1)
    with open(os.path.join(OUT_DIR, f"result_longcat_seed{args.seed}.json"),
              "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result), flush=True)
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
