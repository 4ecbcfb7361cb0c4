"""What the paged engine has to know of a family of models: ONE
description a family, defined in the family's own module beside its
trunk and reached as `config.family` (each config class names it:
`LlamaConfig._family`, "module:NAME", imported when first read).

The arrow points one way: serve/engine.py reads a `Family`, the family's
module knows what its rows are, nothing under models/ imports serve/,
and the engine names no family and no attribute of its config
(tests/test_family.py). A new family costs its module, its config class
and one `Family`.
"""

from __future__ import annotations

import enum
from dataclasses import dataclass, field
from typing import Callable, Mapping, Optional


class Windows(enum.Enum):
    """How many windows (rows of several tokens) a mixed step takes.
    FIT: as many as the largest packed size holds. DISPATCH: one a
    dispatch, where the window's pass takes the one row whose keys or
    state its queries share; a step of k prompts is then k dispatches
    of which a decode row rides one. STEP: one a step, the prompts
    mid-prefill taking turns in the order they were admitted, so every
    step is ONE dispatch that every decode row rides: with every prompt
    in every step 32 callers moved in convoys of seconds of prefill,
    then seconds of decode (PERF.md §6, PR 33). Which of the last two a
    one-window family should take is measured for two (nemotron_h,
    PR 33; deepseek_v2, PR 45: both STEP; ROADMAP D18)."""

    FIT = "fit"
    DISPATCH = "dispatch"
    STEP = "step"


# What moves K/V pages today and cannot move what else a family's rows
# hold: option -> reason, `{noun}` the family's. Checks on outside
# input: an option named here is refused by its name, never ignored.
_CANNOT_MOVE = {
    "--kv-pages": "serving without --kv-pages (the dense-slot engine "
                  "has no {noun})",
    "topology": "a topology / --tp / --dp / --sp (no step program of a "
                "mesh carries a {noun})",
    "--spec-draft": "--spec-draft (nothing takes back the {noun} a "
                    "rejected draft wrote)",
    "--kv-dtype": "--kv-dtype int8/int4 (the quantized pools hold K/V "
                  "pages, not a {noun})",
    "--kv-host-pages": "--kv-host-pages (host spill and preempt-and-"
                       "restore move K/V pages, not a {noun})",
    "--disagg": "--disagg (the prefill shipment carries K/V pages, not "
                "a {noun})",
    "--auto-prefix": "--auto-prefix (prefix pages map K/V pages, not a "
                     "{noun})",
}


def cannot_move(noun: str, *, register_prefix: str,
                reconfigure: str) -> dict:
    """A family's table: the options above with its noun, and its own
    reasons for the two requests that arrive while it serves."""
    return {**{option: why.format(noun=noun)
               for option, why in _CANNOT_MOVE.items()},
            "register_prefix": register_prefix, "reconfigure": reconfigure}


@dataclass(frozen=True)
class Family:
    """Programs share one signature each across the families
    (models/llama/paged.py states them); `attn` (fold|pallas) is a
    static argument of every one."""

    # config.json's model_type, for the messages
    name: str
    # the synchronous decode step and the mixed step as the family jits
    # them, and the sampled programs built over its ragged forward and
    # its mixed step (models/step_programs)
    decode_step: Callable
    decode_programs: Callable
    mixed_step: Callable
    mixed_sampled: Callable
    # create_cache(config, slots, n_pages, page_size, max_seq_len, width,
    # dtype) -> the pytree the programs carry (width: the mixed step's
    # window, for a pool that is sized by it)
    create_cache: Callable
    # where its cache keeps anything beside the pool the page table
    # maps (cache.beside_bytes()): (what, for the start-up log; the
    # gauge that carries its bytes, a key of
    # obs/steps.BESIDE_POOL_BYTES, or None)
    beside: Optional[tuple] = None
    # the record keys of the vector its trunk returns, in the trunk's
    # order (each has a series: obs/steps.COUNTER_SERIES); () for none
    counters: tuple = ()
    # the prefill-row counts its packed sizes are built for
    # (paged.mixed_token_buckets)
    prefill_rows: tuple = (1, 2)
    windows: Windows = Windows.FIT
    # the flavour of a step record's `impl`
    impl: str = "paged-"
    # its own resolve_attn(config, impl, *, explicit, prefill_chunk,
    # slots, n_pages, page_size, max_seq_len, q_itemsize, kv_itemsize)
    # -> (impl of both step kinds, mixed width); None: the engine's rule
    # for GQA rows (serve/engine._resolve_paged_attn)
    resolve_attn: Optional[Callable] = None
    # the step kinds whose rows go through cake_decode_attn /
    # cake_mixed_attn as they are (the host counts their pages / tiles)
    kernel_rows: tuple = ("decode", "mixed")
    # a family whose windows go through the latent window kernel:
    # window_walk(config, cache, width) -> (a window's last position ->
    # (pages a query tile walks, softmax updates they take), over the
    # layers of a dispatch); the host counts them into the mixed
    # records. None: no such kernel
    window_walk: Optional[Callable] = None
    # a family whose single-token rows walk their latent pages
    # (cake_mla_decode_attn): decode_walk(config, cache) -> (a row's
    # position -> (pages it walks, softmax updates they take), over the
    # layers that run the kernel); the host counts them into the decode
    # and the mixed records. None: no such kernel
    decode_walk: Optional[Callable] = None
    # a family that hands a dispatch's window to cake_mixed_attn in a
    # form of its own (entries of one row's window): mixed_attn_walk(
    # config, cache, width) -> ((the window's first position, its
    # tokens, 0 where the dispatch holds single tokens alone) -> (pages
    # the call walks a layer, its table's entries, softmax updates));
    # the host counts them into the mixed records. None: its rows go as
    # they are (kernel_rows), or the host cannot know the call
    mixed_attn_walk: Optional[Callable] = None
    # says(config) -> one sentence for the start-up log on what of the
    # published model this process holds (a share of its layers or
    # experts); None: nothing to say
    says: Optional[Callable] = None
    # what its rows hold (the message's head) and cannot move yet:
    # option -> reason (cannot_move)
    what: str = ""
    refuses: Mapping[str, str] = field(default_factory=dict)

    def moves(self, option: str) -> bool:
        return option not in self.refuses

    def refusal(self, asked: Mapping[str, bool]) -> Optional[str]:
        """One sentence for the options of `asked` that are on and that
        this family's rows cannot move yet; None where it serves all."""
        named = [self.refuses[option] for option, on in asked.items()
                 if on and not self.moves(option)]
        if not named:
            return None
        return (f"model_type {self.name} ({self.what}) does not serve "
                "yet: " + "; ".join(named)
                + " (ROADMAP.md lists each as left to do)")
