"""Master orchestration: model loading + generation driving.

Capability parity with the reference `Master` (cake-core/src/cake/master.rs):
`generate_text` streams each token through a callback, re-times from token 1
so the compile/warmup token doesn't skew throughput, and logs tokens/s
(master.rs:80-124); `generate_image` delegates to the image generator
(master.rs:126-132); `reset()` clears chat state (master.rs:75-77).

There is no worker process: the "cluster" is the device mesh, and model
assembly is sharding (parallel/), so Master is a thin driver over a
Generator.
"""

from __future__ import annotations

import logging
import time
from typing import Callable, Optional

import jax.numpy as jnp

from cake_tpu.args import Args
from cake_tpu.models import Token
from cake_tpu.models.chat import Message
from cake_tpu.ops.sampling import SamplingConfig

log = logging.getLogger(__name__)


class Master:
    """Drives a text and/or image generator (reference master.rs:12-133)."""

    def __init__(self, args: Args, text_generator=None, image_generator=None):
        self.args = args
        self.llm = text_generator
        self.image = image_generator
        self.tokens_per_s: float = 0.0

    # -- construction --------------------------------------------------------

    @classmethod
    def from_args(cls, args: Args, sd_args=None) -> "Master":
        from cake_tpu.startup import STARTUP
        with STARTUP.phase("context"):
            from cake_tpu.context import Context
            ctx = Context.from_args(args, sd_args)
        if args.model_type.value == "image":
            return cls(args, image_generator=ctx.load_image_model())
        return cls(args, text_generator=ctx.load_text_model())

    def make_engine(self, max_slots: Optional[int] = None,
                    **engine_kwargs):
        """Build a continuous-batching engine sharing the loaded LLM's
        params (no weight copy; the engine allocates its own batched KV
        cache). Used by the REST server so N requests decode together
        instead of serialising on a lock like the reference (api/text.rs:67).
        engine_kwargs pass through to InferenceEngine on every flavor
        (e.g. recovery_config for crash-recovery tuning).
        """
        if self.llm is None:
            raise RuntimeError("no text generator loaded")
        from cake_tpu.serve import InferenceEngine
        g = self.llm
        fwd = getattr(g, "_forward_fn", None)
        if fwd is not None and g.parallel is None:
            # custom forward without a (plan, mesh): the --sp adapter.
            # Round-5: plain sp and sp x tp get a REAL engine contract
            # (ring slot-prefill + merged-stats ragged decode,
            # context_parallel.make_sp_engine_step_fns) — long-context
            # serving batches concurrent requests instead of serialising
            # on the legacy locked path. dp x sp shards slots over dp;
            # no text serving mode locks anymore.
            slots = max_slots or getattr(self.args, "max_slots", 8)
            pieces = None
            engine_pieces = getattr(fwd, "engine_pieces", None)
            if engine_pieces is not None:
                pieces = engine_pieces(slots, g.params)
            if pieces is None:
                log.info("no batching engine for this serving mode: "
                         "the API serves requests one at a time through "
                         "the generator")
                return None
            fns, cache, ctx_len, tail_len = pieces
            if getattr(self.args, "kv_pages", None):
                log.warning("--kv-pages ignored: the sp engine's "
                            "ctx/tail cache is not paged (the ctx "
                            "region is sequence-sharded, not "
                            "slot-paged)")
            if (getattr(self.args, "kv_dtype", None) in ("int8", "int4")
                    or getattr(self.args, "kv_host_pages", None)):
                log.warning("--kv-dtype int8/int4 / --kv-host-pages "
                            "ignored: KV tiering (cake_tpu/kv) applies "
                            "to the paged pool, and the sp engine's "
                            "ctx/tail cache is not paged")
            if getattr(self.args, "auto_prefix", False):
                log.warning("--auto-prefix ignored: prefix caching is "
                            "not implemented for the sp engine's "
                            "sequence-sharded ctx cache")
            if getattr(self.args, "autotune", "off") != "off":
                log.warning("--autotune ignored: the sp engine's "
                            "custom step fns own their cache contract; "
                            "only the built-in dense/paged engines can "
                            "hot-switch configs")
            if getattr(self.args, "disagg", None):
                log.warning("--disagg ignored: disaggregated "
                            "prefill/decode ships paged pool pages "
                            "(cake_tpu/kv/transfer.py), and the sp "
                            "engine's ctx/tail cache is not paged")
            log.info("sp engine: %d slots, ctx window %d + decode tail "
                     "%d", slots, ctx_len, tail_len)
            return InferenceEngine(
                g.config, g.params, g.tokenizer,
                max_slots=slots, max_seq_len=ctx_len + tail_len,
                sampling=g.sampling, seed=self.args.seed,
                decode_scan_steps=self.args.decode_scan,
                step_fns=fns, cache=cache,
                prompt_limit=ctx_len, decode_budget=tail_len,
                **self._trace_kwargs(),
                **self._sched_kwargs(),
                **self._fault_kwargs(),
                # passed through so the engine's no-chunk-fn guard WARNS
                # that --prefill-chunk has no sp variant, instead of the
                # flag silently vanishing
                prefill_chunk=getattr(self.args, "prefill_chunk", None),
                **engine_kwargs,
            )
        slots = max_slots or getattr(self.args, "max_slots", 8)
        kwargs = {}
        if getattr(g, "parallel", None) is not None:
            # topology-sharded model: the engine's steps run the same
            # pipelined SPMD program, with its batched cache placed to match
            from cake_tpu.parallel.pipeline import make_engine_step_fns
            from cake_tpu.parallel.sharding import create_sharded_cache
            plan, mesh = g.parallel
            tp = plan.tp > 1
            microbatches = self.args.microbatches
            if slots % microbatches != 0:
                raise ValueError(
                    f"--max-slots {slots} must be divisible by "
                    f"--microbatches {microbatches}")
            # sliding-window model over a topology: ring cache per stage
            # (W slots instead of max_seq), same memory win as the
            # single-device engine's ring path
            ring = (g.config.sliding_window is not None
                    and g.config.sliding_window < g.max_seq_len)
            cache = create_sharded_cache(
                g.config, slots,
                g.config.sliding_window if ring else g.max_seq_len, mesh,
                tp_axis="tp" if tp else None, dp_axis=None,
                stage_axis="stage", dtype=g.cache_dtype,
            )
            kwargs = dict(
                step_fns=make_engine_step_fns(
                    mesh, g.config, num_microbatches=microbatches,
                    tp=tp, params=g.params, ring=ring),
                cache=cache,
                ring=ring,
            )
        return InferenceEngine(
            g.config, g.params, g.tokenizer,
            max_slots=slots,
            max_seq_len=g.max_seq_len,
            sampling=g.sampling,
            seed=self.args.seed,
            decode_scan_steps=self.args.decode_scan,
            cache_dtype=g.cache_dtype,  # follow --kv-dtype
            # honored by the paged (--kv-pages) engine too: prefixes
            # prefill once into pool pages and map shared, and chunked
            # prefill windows scatter into pages at any offset
            auto_prefix_system=getattr(self.args, "auto_prefix", False),
            # pass through unconditionally: the engine's own step_fns
            # guard warns when a pipelined path ignores the knob
            prefill_chunk=getattr(self.args, "prefill_chunk", None),
            kv_pages=getattr(self.args, "kv_pages", None),
            kv_page_size=getattr(self.args, "kv_page_size", 128),
            paged_attn=getattr(self.args, "paged_attn", "auto"),
            # KV tiering (cake_tpu/kv): "int8" selects the quantized
            # page pool; --kv-host-pages arms the host-RAM spill tier
            # (both are paged-pool features — the engine warns/errors
            # when --kv-pages is absent)
            kv_dtype=getattr(self.args, "kv_dtype", None),
            kv_host_pages=getattr(self.args, "kv_host_pages", None),
            # live config hot-switching (cake_tpu/autotune): the
            # engine itself warns and disables on flavors without the
            # fold (ring/custom step fns)
            autotune=getattr(self.args, "autotune", "off"),
            autotune_policy=getattr(self.args, "autotune_policy", None),
            # disaggregated prefill/decode (cake_tpu/kv/transfer.py):
            # role + channel peer; the shared token rides
            # $CAKE_DISAGG_TOKEN (validated loudly at startup)
            disagg=getattr(self.args, "disagg", None),
            disagg_peer=getattr(self.args, "disagg_peer", None),
            disagg_timeout_s=getattr(self.args, "disagg_timeout", 30.0),
            **self._spec_kwargs(),
            **self._trace_kwargs(),
            **self._sched_kwargs(),
            **self._fault_kwargs(),
            **kwargs,
            **engine_kwargs,
        )

    def _spec_kwargs(self) -> dict:
        """Speculative decoding (cake_tpu/spec): load the draft model
        behind --spec-draft and hand the engine its params + config
        (the engine builds the paged draft pool itself, sized by the
        target pool's page geometry). A directory without a
        config.json is the tiny config; the draft stays unquantized
        (--quant targets the big model — a draft is small by
        construction)."""
        d_dir = getattr(self.args, "spec_draft", None)
        if not d_dir:
            return {}
        import dataclasses
        import os

        from cake_tpu.context import _resolve_flash
        from cake_tpu.models import load_text_params
        from cake_tpu.models.llama.config import LlamaConfig, load_config
        from cake_tpu.utils.devices import resolve_dtype
        g = self.llm
        if os.path.exists(os.path.join(d_dir, "config.json")):
            d_cfg = load_config(d_dir)
        else:
            d_cfg = LlamaConfig.tiny()
        d_cfg = dataclasses.replace(
            d_cfg, use_flash_attention=_resolve_flash(self.args))
        if d_cfg.vocab_size != g.config.vocab_size:
            raise ValueError(
                f"spec draft vocab {d_cfg.vocab_size} != target vocab "
                f"{g.config.vocab_size}: the verify pass scores draft "
                "token ids directly, so the models must share a "
                "tokenizer")
        d_params = load_text_params(d_cfg, d_dir,
                                    resolve_dtype(self.args.dtype))
        log.info("speculative serving: gamma=%d draft=%s",
                 self.args.spec_gamma, d_dir)
        return dict(spec_draft_params=d_params,
                    spec_draft_config=d_cfg,
                    spec_gamma=self.args.spec_gamma)

    def _trace_kwargs(self) -> dict:
        """Request-lifecycle tracing + step-telemetry + event-bus +
        SLO-accounting knobs, plumbed to every engine flavor
        identically (--trace-events / --trace-ring / --step-log /
        --step-ring / --event-log / --event-ring / --slo-targets)."""
        return dict(
            trace_events=getattr(self.args, "trace_events", None),
            trace_ring=getattr(self.args, "trace_ring", 256),
            step_log=getattr(self.args, "step_log", None),
            step_ring=getattr(self.args, "step_ring", 512),
            event_log=getattr(self.args, "event_log", None),
            event_ring=getattr(self.args, "event_ring", 1024),
            slo_targets=getattr(self.args, "slo_targets", None),
            # online regression sentinel (--sentinel, obs/sentinel.py)
            sentinel=getattr(self.args, "sentinel", False),
            sentinel_interval=getattr(self.args, "sentinel_interval",
                                      2.0),
            # closed-loop actuation + black-box forensics (ISSUE 16,
            # obs/actions.py): --sentinel-act / --postmortem-dir
            sentinel_act=getattr(self.args, "sentinel_act", False),
            postmortem_dir=getattr(self.args, "postmortem_dir", None),
        )

    def _sched_kwargs(self) -> dict:
        """SLO scheduling knobs (--priority-classes / --preemption /
        --shed), plumbed to every engine flavor; the engine itself
        warns and degrades when a flavor cannot preempt (windowed
        ctx+tail layouts)."""
        return dict(
            priority_classes=getattr(self.args, "priority_classes",
                                     False),
            preemption=getattr(self.args, "preemption", None),
            shed=getattr(self.args, "shed", False),
        )

    def telemetry_settings(self) -> tuple:
        """(enabled, interval_s) for fleet telemetry federation
        (--telemetry-export / --telemetry-interval, obs/federation.py).
        Resolves the auto default here — ONE place — so the
        coordinator's collector and every follower's exporter agree on
        whether the plane is armed: None = on exactly when serving
        spans processes (followers are otherwise observability black
        holes), an explicit True/False is honored as given."""
        enabled = getattr(self.args, "telemetry_export", None)
        if enabled is None:
            import jax
            enabled = jax.process_count() > 1
        return (bool(enabled),
                float(getattr(self.args, "telemetry_interval", 2.0)))

    def _fault_kwargs(self) -> dict:
        """Fault-injection + crash-recovery + durability knobs
        (--fault-plan / --recovery / --journal / --journal-fsync),
        plumbed to every engine flavor; the engine warns and keeps the
        legacy fail-all path where the resume fold does not exist
        (windowed ctx+tail layouts — the journal still records and
        replays there, through the same resume path checkpoints
        use)."""
        return dict(
            fault_plan=getattr(self.args, "fault_plan", None),
            recovery=getattr(self.args, "recovery", None),
            journal=getattr(self.args, "journal", None),
            journal_fsync=getattr(self.args, "journal_fsync", "batch"),
        )

    # -- text ----------------------------------------------------------------

    def reset(self) -> None:
        if self.llm is not None:
            self.llm.reset()

    def add_message(self, message: Message) -> None:
        self.llm.add_message(message)

    def generate_text(self, stream: Callable[[Token], None],
                      sample_len: Optional[int] = None) -> str:
        """Generate up to sample_len tokens, streaming each through `stream`.

        Timing matches the reference (master.rs:93-121): the clock restarts
        after the first token so one-off compile cost is excluded from the
        reported tokens/s.
        """
        sample_len = sample_len or self.args.sample_len
        pieces = []
        start = time.perf_counter()
        generated = 0
        for index in range(sample_len):
            token = self.llm.next_token(index)
            if index == 0:
                start = time.perf_counter()  # exclude warmup token
            else:
                generated += 1
            if token.is_end_of_stream:
                if token.text:
                    # EOS carries the flushed UTF-8 tail (generator
                    # parity with the buffered decode)
                    pieces.append(token.text)
                    stream(token)
                break
            pieces.append(token.text)
            stream(token)
        dt = time.perf_counter() - start
        self.tokens_per_s = generated / dt if dt > 0 else 0.0
        log.info("%d tokens generated (%.2f token/s)",
                 generated + 1, self.tokens_per_s)
        return "".join(pieces)

    # -- image ---------------------------------------------------------------

    def attach_image_control(self, control) -> None:
        """Multi-host image serving: publish each generation's args
        before dispatching it, so follower processes replay the
        identical jit sequence (cli._run_image_follower)."""
        self._image_control = control

    def generate_image(self, image_args, callback) -> None:
        if self.image is None:
            raise RuntimeError("no image generator loaded")
        control = getattr(self, "_image_control", None)
        if control is not None:
            if image_args.sd_img2img:
                # the path is coordinator-local; a follower replaying it
                # would fail AFTER its first collectives and desync the
                # SPMD dispatch, wedging the cluster — reject up front
                # with a clean client error instead
                raise ValueError(
                    "img2img is unavailable under multi-host serving: "
                    "the init image exists on the coordinator only; "
                    "serve img2img on one host")
            control.publish({"op": "image", "args": image_args.to_json()})
        self.image.generate_image(image_args, callback)

    def run(self) -> None:
        """One-shot CLI generation (reference master.rs:33-72)."""
        if self.llm is not None:
            self.add_message(Message.system(self.args.system_prompt))
            self.add_message(Message.user(self.args.prompt))
            print(f"[{self.args.system_prompt}] {self.args.prompt}\n")
            self.generate_text(lambda t: print(t.text, end="", flush=True))
            print()
