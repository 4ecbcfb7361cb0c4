"""The autotuner's configuration space (cake_tpu/autotune).

BENCH_MEASURED shows the optimal engine configuration is load-dependent
and *moves*: 16 slots was the v5e sweet spot at 408-441 tok/s, then
after continuous batching the peak migrated to 32-64 slots while 32
slots had previously thrashed HBM at 151 tok/s. No static
--max-slots/--decode-scan/--kv-pages choice is right across offered
loads, so the autotuner treats those knobs as a declarative point in a
config space:

  * ``EngineConfig`` — one point: the engine knobs that can be switched
    LIVE (serve/engine.reconfigure) without reloading weights: decode
    slots, decode-scan burst length, page pool geometry, KV storage
    dtype, and the paged attention impl. Everything
    else (model, max_seq_len, sampling defaults, scheduling policy) is
    engine identity and never moves.
  * ``validate_config`` — per-flavor validity rules REUSING args.py
    validation (the CLI and the autotuner cannot drift on what a legal
    config is), plus the engine-level geometry rules.
  * ``switch_guard`` — the legality of a LIVE transition between two
    valid points. The one gated direction: an int8 pool cannot hot-
    switch to a float pool, because the emitted history was sampled
    under quantized KV numerics and the fold-tokens-into-prompt resume
    would re-derive exact-KV logits that need not agree with the tokens
    already streamed — the greedy token-identity contract cannot be
    honored, so the switch is refused loudly instead of silently
    changing mid-stream semantics.
  * ``config_key`` — the canonical comparison key: ``auto`` knobs
    resolve (backend-dependent) and dense-irrelevant paged knobs are
    dropped, so "the same config spelled differently" never triggers a
    pointless switch.
"""

from __future__ import annotations

from dataclasses import asdict, dataclass, fields
from typing import Optional, Tuple

# knob names, in the order operators read them (health/autotune JSON)
CONFIG_KEYS = ("slots", "decode_scan", "kv_pages", "kv_page_size",
               "kv_dtype", "paged_attn")


@dataclass(frozen=True)
class EngineConfig:
    """One switchable engine configuration point.

    ``kv_pages is None`` selects the dense engine (one [L, B, T] cache);
    a value selects the paged engine with that pool geometry. Field
    defaults mirror args.Args so a config built from partial JSON means
    the same thing the CLI flags would."""

    slots: int = 8
    decode_scan: int = 1
    kv_pages: Optional[int] = None
    kv_page_size: int = 128
    kv_dtype: Optional[str] = None
    paged_attn: str = "auto"

    @property
    def paged(self) -> bool:
        return self.kv_pages is not None

    def to_dict(self) -> dict:
        return asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "EngineConfig":
        known = {f.name for f in fields(cls)}
        unknown = sorted(set(d) - known)
        if unknown:
            raise ValueError(
                f"unknown engine config keys {unknown}; the switchable "
                f"knobs are {list(CONFIG_KEYS)}")
        kw = {}
        for f in fields(cls):
            if f.name not in d or d[f.name] is None:
                continue
            v = d[f.name]
            if f.name in ("slots", "decode_scan", "kv_pages",
                          "kv_page_size"):
                v = int(v)
            kw[f.name] = v
        return cls(**kw)


def resolve_paged_attn(paged_attn: Optional[str]) -> str:
    """THE paged_attn auto-resolution rule — pallas on a real TPU,
    fold elsewhere (interpret-mode pallas on CPU is slow) — shared by
    the engine's dispatch setup (serve/engine._resolve_paged_attn,
    which then checks the kernels' shape gates per step kind) and
    config_key, so the comparison key can never resolve "auto"
    differently from the engine. Non-auto names pass through
    unvalidated (the engine validates at dispatch setup). A backend
    that fails to initialise raises here; it is not read as "no TPU"."""
    impl = paged_attn or "auto"
    if impl == "auto":
        import jax
        impl = "pallas" if jax.default_backend() == "tpu" else "fold"
    return impl


def _canon_kv_dtype(name: Optional[str]) -> Optional[str]:
    """Spelling-normalized storage dtype: "f32"/"float32" and friends
    map to one canonical string; the quantized-pool names (int8/int4)
    and None (follow the engine's cache dtype) pass through."""
    if name is None or name in ("int8", "int4"):
        return name
    try:
        import numpy as np

        from cake_tpu.utils.devices import resolve_kv_dtype
        return np.dtype(resolve_kv_dtype(name)).name
    except Exception:  # noqa: BLE001 — comparison key, not dispatch
        return name


def config_key(cfg: EngineConfig,
               default_kv_dtype: Optional[str] = None) -> Tuple:
    """Canonical comparison key: ``auto`` knobs resolved the way the
    engine would resolve them, dtype spellings normalized, paged-only
    knobs dropped for dense points (a dense config's
    kv_page_size/paged_attn/kv_dtype select nothing, so two spellings
    must compare equal).

    default_kv_dtype: what an UNSET kv_dtype resolves to (the engine's
    base cache dtype). The engine passes it so a policy spelling the
    default explicitly ("bf16" on a bf16-cache engine) compares equal
    to one omitting it — without the context, callers that cannot know
    the default (the controller) leave None distinct."""
    if not cfg.paged:
        return ("dense", cfg.slots, cfg.decode_scan)
    kd = _canon_kv_dtype(cfg.kv_dtype)
    if kd is None and default_kv_dtype is not None:
        kd = _canon_kv_dtype(default_kv_dtype)
    return ("paged", cfg.slots, cfg.decode_scan, cfg.kv_pages,
            cfg.kv_page_size, kd,
            resolve_paged_attn(cfg.paged_attn))


def validate_config(cfg: EngineConfig,
                    max_seq_len: Optional[int] = None) -> EngineConfig:
    """Per-flavor validity rules. Deliberately REUSES args.Args.validate
    (the single source of CLI-level config legality) by projecting the
    point onto the matching flags, then adds the engine geometry rules
    args.py leaves to the engine."""
    from cake_tpu.args import Args

    # args.validate covers: the paged_attn enum, kv_dtype name
    # resolution, int8-requires-pages, max_slots/decode_scan >= 1
    Args(model="", max_slots=cfg.slots, decode_scan=cfg.decode_scan,
         kv_pages=cfg.kv_pages, kv_page_size=cfg.kv_page_size,
         kv_dtype=cfg.kv_dtype, paged_attn=cfg.paged_attn).validate()
    if cfg.paged and (cfg.kv_pages < 1 or cfg.kv_page_size < 1):
        raise ValueError(
            f"kv_pages {cfg.kv_pages} / kv_page_size "
            f"{cfg.kv_page_size} must be >= 1")
    # NOTE deliberately NO pool-vs-max_seq_len floor: the engine itself
    # accepts pools smaller than one max-length stream (submit()
    # fail-fasts requests that can never fit), so the autotuner must
    # not be stricter than the CLI — a live switch instead refuses any
    # pool an IN-FLIGHT stream does not fit (engine._reconfigure_sync;
    # max_seq_len is accepted for future geometry rules).
    del max_seq_len
    return cfg


def _dtype_rank(name: Optional[str]) -> int:
    """Precision rank of a KV storage dtype: int4 < int8 < float. A
    live switch may only hold precision or NARROW it — widening would
    re-derive in-flight transcripts at higher-precision KV."""
    return {"int4": 0, "int8": 1}.get(_canon_kv_dtype(name), 2)


def switch_guard(old: EngineConfig, new: EngineConfig) -> Optional[str]:
    """Reason a LIVE old -> new switch is refused, or None when legal.

    Any precision-WIDENING direction (int8 -> float, int4 -> int8,
    int4 -> float) is gated off: streams already served from the
    quantized pool emitted tokens sampled under QUANTIZED KV numerics,
    and the hot-switch resume re-prefills their transcripts at the
    wider KV — the continuation can disagree with the history the
    client already received, so the greedy token-identity contract
    (tests/test_autotune_engine.py pins it for every allowed switch at
    f32 KV) cannot be honored in this direction. Quantizing FORWARD
    (float -> int8 -> int4) is the autotuner's memory-pressure
    response and stays allowed: no identity claim is made for a
    quantized target."""
    ro, rn = _dtype_rank(old.kv_dtype), _dtype_rank(new.kv_dtype)
    if ro < rn:
        names = {0: "int4-pool", 1: "int8-pool", 2: "float-pool"}
        return (
            f"refusing the {names[ro]} -> {names[rn]} hot switch: "
            "in-flight streams were decoded against quantized KV, and "
            "the fold-tokens-into-prompt resume would re-prefill their "
            "transcripts at wider KV — continuations could diverge "
            "from the already-streamed history, breaking the greedy "
            "token-identity contract. Drain the engine and restart "
            f"with the {names[rn]} instead.")
    return None
