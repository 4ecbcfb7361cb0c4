"""Speculative decoding — spec rows behind the one front door.

Speculation is a ROW KIND of the paged serving engine (--spec-draft).
Draft and target KV both live in paged pools addressed by the engine's
ONE page allocator (same id space, same budget the admission gate
counts); a
stream's gamma-token speculative suffix occupies dedicated suffix
pages that acceptance truncates back to the allocator after every
round; and the acceptance-rate EMA closes the loop through the gamma
tuner (autotune/spec.py), degrading a collapsing stream to plain
decode — never wedging it — with typed spec_round/spec_degraded
events and cake_spec_* metrics.

Layout:
  accept.py — the accept/resample arithmetic;
  round.py  — spec_round_paged, the one-launch batched draft+verify
              round over paged KV;
  state.py  — SpecState (per-stream pages + acceptance EMA) and
              SpecPlane (the engine's optional `_specp` plane), plus
              the cake_spec_* metric families.
"""

from cake_tpu.spec.accept import (
    advance_row_keys, assemble_sampled, greedy_accept, rejection_accept,
)
from cake_tpu.spec.round import spec_round_paged
from cake_tpu.spec.state import (
    SPEC_DEGRADED, SPEC_ROUNDS, SpecPlane, SpecState,
)

__all__ = [
    "advance_row_keys", "assemble_sampled", "greedy_accept",
    "rejection_accept", "spec_round_paged", "SpecPlane", "SpecState",
    "SPEC_DEGRADED", "SPEC_ROUNDS",
]
