"""Context: process-wide shared state built once from Args.

Capability parity with the reference `Context` (cake-core/src/cake/mod.rs:39-100):
parsed args, dtype policy, topology, device, model config, weight source.
On TPU it additionally owns the mesh and sharding plan (parallel/).
"""

from __future__ import annotations

import functools
import logging
import os
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp

from cake_tpu.args import Args, SDArgs
from cake_tpu.topology import Topology
from cake_tpu.utils.devices import resolve_dtype

log = logging.getLogger(__name__)


def _resolve_flash(args: Args) -> bool:
    """--flash-attention / --no-flash-attention; default on iff real TPU."""
    if args.flash_attention is not None:
        return args.flash_attention
    return jax.default_backend() == "tpu"


@functools.lru_cache(maxsize=8)
def _sharded_init(cfg, dtype, bits, mesh, tp: bool):
    """jit(random init, out_shardings=the pipeline plan's specs) for one
    (config, dtype, quantization, mesh): the same program for every
    server of a placement, so it is built — and compiles — once per
    process. bits: draw the int8/int4 leaves directly (the dense
    family's init_params_quantized, the MoE family's init_params),
    None = full precision."""
    from functools import partial

    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from cake_tpu.ops.quant import expand_specs_for_quant
    from cake_tpu.parallel.pipeline import pipeline_param_specs

    if cfg.is_moe:
        from cake_tpu.models.moe.params import init_params
        init = partial(init_params, bits=bits)
    elif bits:
        from cake_tpu.models.llama.params import init_params_quantized
        init = partial(init_params_quantized, bits=bits)
    else:
        from cake_tpu.models.llama.params import init_params as init

    def make():
        return init(cfg, jax.random.PRNGKey(0), dtype=dtype)

    shapes = jax.eval_shape(make)
    specs = expand_specs_for_quant(shapes, pipeline_param_specs(
        shapes["blocks"].keys(), "tp" if tp else None))
    shardings = jax.tree.map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, P))
    return jax.jit(make, out_shardings=shardings)


@dataclass
class Context:
    args: Args
    sd_args: Optional[SDArgs]
    dtype: object
    topology: Optional[Topology] = None
    llama_config: Optional[object] = None

    @classmethod
    def from_args(cls, args: Args, sd_args: Optional[SDArgs] = None) -> "Context":
        dtype = resolve_dtype(args.dtype)
        topology = Topology.from_path(args.topology) if args.topology else None

        llama_config = None
        if args.model_type.value == "text" and args.model:
            import dataclasses

            from cake_tpu.models.llama.config import load_config
            cfg_path = os.path.join(args.model, "config.json")
            if os.path.exists(cfg_path):
                llama_config = dataclasses.replace(
                    load_config(args.model),
                    use_flash_attention=_resolve_flash(args),
                )

        log.info("context: devices=%s dtype=%s topology=%s",
                 [f"{d.platform}/{d.device_kind}" for d in jax.devices()],
                 args.dtype, list(topology.keys()) if topology else None)
        return cls(args=args, sd_args=sd_args, dtype=dtype,
                   topology=topology, llama_config=llama_config)

    # -- model loading -------------------------------------------------------

    def load_text_model(self):
        """Build a LlamaGenerator; with a multi-stage topology (or tp/dp > 1)
        the params/cache are placed on a ("dp","stage","tp") mesh per the
        ParallelPlan and the generator drives the pipelined forward — the
        reference's topology-driven serving (topology.rs:43-91 feeding
        llama.rs:203-220), as one SPMD program instead of TCP hops."""
        import dataclasses

        from cake_tpu.startup import STARTUP

        a = self.args
        with STARTUP.phase("config"):
            # the model's modules are imported here for the first time
            from cake_tpu.models import load_text_params
            from cake_tpu.models.llama.config import LlamaConfig
            from cake_tpu.models.llama.generator import (
                ByteTokenizer, LlamaGenerator, load_tokenizer,
            )
            from cake_tpu.ops.sampling import SamplingConfig
            from cake_tpu.parallel.plan import ParallelPlan

            cfg = self.llama_config or dataclasses.replace(
                LlamaConfig.tiny(), use_flash_attention=_resolve_flash(a)
            )
            if a.model and os.path.exists(
                    os.path.join(a.model, "tokenizer.json")):
                tokenizer = load_tokenizer(a.model)
            else:
                tokenizer = ByteTokenizer(cfg.vocab_size)

            plan = ParallelPlan.from_topology(cfg, self.topology, args=a)
            refusal = cfg.family.refusal({
                "topology": (plan.stages > 1 or plan.tp > 1 or plan.dp > 1
                             or a.sp > 1)})
            if refusal:
                raise ValueError(refusal)

        # stage-local load (reference worker.rs:106-127 parity, per
        # shard): with a sharded placement the tree is BORN on its mesh
        # shards (_params_on_mesh: streamed from disk, or random-
        # initialised under the plan's shardings) — no full-model
        # host/device copy ever exists, which is what lets a 70B (or
        # Mixtral-8x22B) topology actually load instead of dying at the
        # eager full-tree load.
        born_sharded = (
            (plan.stages > 1 or plan.tp > 1 or plan.dp > 1)
            and (a.sp <= 1 or plan.stages > 1)
        )
        if born_sharded:
            params = None   # built inside the topology branch, post-mesh
        else:
            with STARTUP.phase("weights"):
                params = load_text_params(cfg, a.model, self.dtype,
                                          quant=a.quant)
            if a.quant in ("int8", "int4"):
                log.info("weights quantized to %s as they loaded "
                         "(weight-only)", a.quant)

        # --repeat-penalty unset -> reference default 1.1 (llama.rs:311)
        penalty = a.repeat_penalty
        if penalty is None:
            penalty = 1.1
        sampling = SamplingConfig(
            temperature=a.temperature, top_k=a.top_k, top_p=a.top_p,
            repeat_penalty=penalty, repeat_last_n=a.repeat_last_n,
        )
        max_seq = min(a.max_seq_len, cfg.max_position_embeddings)
        from cake_tpu.utils.devices import resolve_kv_dtype
        if a.kv_dtype == "int8":
            # int8 KV is the PAGED ENGINE's quantized pool (cake_tpu/kv;
            # master.make_engine passes --kv-dtype through): the
            # sequential generator's dense cache keeps the compute
            # dtype — scales are per page, and the dense cache has none
            kv_dtype = self.dtype
        else:
            kv_dtype = (resolve_kv_dtype(a.kv_dtype) if a.kv_dtype
                        else self.dtype)

        kwargs = {}
        if a.sp > 1:
            # sequence/context parallelism: ring-attention prefill +
            # merged-stats decode over an ("sp",) / ("sp","tp") /
            # ("stage","sp"[,"tp"]) mesh — the long-context serving mode
            # (prompt sharded over chips, optionally with Megatron head
            # sharding within each shard and/or layer ranges over stages
            # for models too big for one chip's HBM)
            if plan.dp > 1 and plan.stages > 1:
                raise ValueError(
                    "--sp composes with --dp OR topology stages, not "
                    "both in one mesh")
            if plan.dp > 1 and a.batch_size % plan.dp != 0:
                raise ValueError(
                    f"--batch-size {a.batch_size} must be divisible by "
                    f"--dp {plan.dp}")
            if plan.tp > 1 and a.quant == "int4":
                # int4 group-wise weights CAN shard their contract dim
                # over tp (wo/w_down are contract-sharded Megatron-style)
                # as long as every tp shard holds whole groups — the
                # packed nibble layout and the per-group scales are then
                # self-contained per shard (ops/quant.expand_spec already
                # gives the scale's group dim the q spec). Misaligned
                # dims would split a group across devices, so reject
                # exactly those.
                from cake_tpu.ops.quant import pick_group
                for name, dim in (
                        ("wo", cfg.num_attention_heads * cfg.head_dim),
                        ("w_down", cfg.intermediate_size)):
                    g = pick_group(dim)
                    if (dim // g) % plan.tp:
                        raise ValueError(
                            f"--sp with --tp {plan.tp} and --quant int4: "
                            f"{name}'s contract dim {dim} has {dim // g} "
                            f"groups of {g}, not divisible over tp — a "
                            "tp shard would split a quantization group. "
                            "Use int8, drop --tp, or pick a tp that "
                            "divides the group count")
            if cfg.sliding_window is not None:
                raise ValueError(
                    "--sp (ring attention) does not implement "
                    "sliding-window attention; serve this model without "
                    "--sp")
            import numpy as np
            from jax.sharding import Mesh

            from cake_tpu.parallel.context_parallel import SPGeneratorForward
            devices = jax.devices()
            tp = plan.tp
            dp = plan.dp
            stages = plan.stages
            need = stages * dp * a.sp * tp
            if need > len(devices):
                raise ValueError(
                    f"stages {stages} x --dp {dp} x --sp {a.sp} x --tp "
                    f"{tp} needs {need} devices, have {len(devices)}")
            if jax.process_count() > 1 and need != len(devices):
                # multi-host: a mesh over a device subset could land
                # entirely on one process; the other processes would
                # replay programs with no addressable shards. Spanning
                # ALL global devices keeps every process a participant.
                raise ValueError(
                    f"multi-host --sp meshes must span every device: "
                    f"sp x tp (x dp/stages) = {need} != "
                    f"{len(devices)} global devices")
            if tp > 1 and cfg.num_key_value_heads % tp != 0:
                raise ValueError(
                    f"--tp {tp} must divide kv heads "
                    f"{cfg.num_key_value_heads}")
            if stages > 1 and cfg.num_hidden_layers % stages != 0:
                raise ValueError(
                    f"topology stages {stages} must divide layer count "
                    f"{cfg.num_hidden_layers}")
            # split the window: context (sharded) + decode tail (replicated);
            # the tail MUST hold sample_len generated tokens — a too-small
            # tail would clamp cache writes over live entries
            tail = max(a.sample_len, 16)
            ctx_len = ((max_seq - tail) // a.sp) * a.sp
            if ctx_len <= 0:
                raise ValueError(
                    f"--max-seq-len {max_seq} leaves no context window for "
                    f"sp={a.sp} after a {tail}-token decode tail; raise "
                    "--max-seq-len or lower --sample-len")
            if stages > 1:
                axes = (["stage", "sp"] + (["tp"] if tp > 1 else []))
                mesh = Mesh(
                    np.array(devices[:need]).reshape(
                        *(stages, a.sp) + ((tp,) if tp > 1 else ())),
                    tuple(axes))
                from cake_tpu.parallel.sp_pipeline import (
                    place_sp_stage_params,
                )
                if params is None:
                    params = self._params_on_mesh(cfg, mesh, tp > 1)
                params = place_sp_stage_params(mesh, cfg, params,
                                               tp=tp > 1)
            elif dp > 1 or tp > 1:
                # ("dp",)? x "sp" x ("tp",)? — batch over dp groups, each
                # running its own sp ring (collectives name "sp"/"tp"
                # only, so shard_map scopes them per group)
                shape = (((dp,) if dp > 1 else ())
                         + (a.sp,) + ((tp,) if tp > 1 else ()))
                axes = ((("dp",) if dp > 1 else ())
                        + ("sp",) + (("tp",) if tp > 1 else ()))
                mesh = Mesh(np.array(devices[:need]).reshape(shape), axes)
                if tp > 1:
                    # place the block params on their tp shards up front
                    # so every sp call doesn't pay a reshard
                    from cake_tpu.parallel.context_parallel import (
                        place_sp_params,
                    )
                    params = place_sp_params(mesh, cfg, params, tp=True)
            else:
                mesh = Mesh(np.array(devices[:a.sp]), ("sp",))
            fwd = SPGeneratorForward(
                mesh, cfg, ctx_len, max_seq - ctx_len, kv_dtype=kv_dtype,
                tp=tp > 1, params=params, stages=stages, dp=dp > 1)
            # placeholder cache: the SP prefill allocates its own sharded
            # SPCache; the generator's default dense [L,B,max_seq,...]
            # buffer would be dead weight at exactly the context lengths
            # SP exists for
            from cake_tpu.models.llama.cache import KVCache
            kwargs = dict(forward_fn=fwd,
                          cache=KVCache.create(cfg, a.batch_size, 1,
                                               dtype=kv_dtype))
            log.info("sp serving: ring prefill over sp=%d%s, ctx=%d "
                     "tail=%d", a.sp,
                     f" x stages={stages}" if stages > 1 else "",
                     ctx_len, max_seq - ctx_len)
        elif plan.stages > 1 or plan.tp > 1 or plan.dp > 1:
            from cake_tpu.parallel.pipeline import (
                make_pipeline_forward, place_for_pipeline,
            )
            if a.batch_size % plan.dp != 0:
                raise ValueError(
                    f"--batch-size {a.batch_size} must be divisible by "
                    f"--dp {plan.dp}")
            if (a.batch_size // plan.dp) % a.microbatches != 0:
                raise ValueError(
                    f"per-replica batch {a.batch_size // plan.dp} must be "
                    f"divisible by --microbatches {a.microbatches} "
                    "(GPipe slices the batch into microbatches)")
            tp, dp = plan.tp > 1, plan.dp > 1
            with STARTUP.phase("mesh"):
                mesh = plan.build_mesh()
                from cake_tpu.parallel.sharding import create_sharded_cache
                cache = create_sharded_cache(
                    cfg, a.batch_size, max_seq, mesh,
                    tp_axis="tp" if tp else None,
                    dp_axis="dp" if dp else None,
                    stage_axis="stage", dtype=kv_dtype,
                )
            if params is None:
                params = self._params_on_mesh(cfg, mesh, tp)
            with STARTUP.phase("place"):
                params, cache = place_for_pipeline(params, cache, mesh,
                                                   tp=tp, dp=dp)
                fwd = make_pipeline_forward(
                    mesh, cfg,
                    num_microbatches=a.microbatches,
                    tp=tp, dp=dp, params=params,
                )
            kwargs = dict(forward_fn=fwd, cache=cache,
                          parallel=(plan, mesh))
            log.info("topology-sharded serving:\n%s", plan.describe())

        with STARTUP.phase("generator"):
            gen = LlamaGenerator(
                cfg, params, tokenizer,
                max_seq_len=max_seq,
                batch_size=a.batch_size, sampling=sampling, seed=a.seed,
                cache_dtype=kv_dtype, prefill_chunk=a.prefill_chunk,
                **kwargs,
            )
            from cake_tpu.utils.profiling import log_memory
            log_memory("model loaded")  # reference llama.rs:233-236
        return gen

    def _params_on_mesh(self, cfg, mesh, tp: bool):
        """The (maybe quantized) param tree born on its pipeline shards:
        streamed from disk when weights exist, else random-initialised
        under jit with the plan's shardings as out_shardings — each
        device generates its own shard, so a weightless 8B topology
        never builds the tree on device 0 first."""
        from cake_tpu.startup import STARTUP
        from cake_tpu.utils.loading import has_weights

        if has_weights(self.args.model):
            with STARTUP.phase("weights"):
                params = self._load_params_streamed(cfg, mesh, tp)
            with STARTUP.phase("quantize"):
                return self._maybe_quantize(params)
        log.warning("no weights at %r; using random init",
                    self.args.model)
        bits = {"int8": 8, "int4": 4}.get(self.args.quant)
        with STARTUP.phase("weights"):
            return _sharded_init(cfg, self.dtype, bits, mesh, tp)()

    def _load_params_streamed(self, cfg, mesh, tp: bool):
        """Stream weights from disk directly onto their pipeline shards
        (models/llama/params.load_params_sharded) — each tensor is read
        once per addressable shard slice and never exists as a full
        host/device array. Quantization (_maybe_quantize) then runs
        shard-wise on the placed tree, so peak per-device HBM is
        ~1.5x one shard, not 1.5x the model."""
        import jax
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        from cake_tpu.models.llama.params import block_param_keys
        from cake_tpu.parallel.pipeline import pipeline_param_specs

        if cfg.is_moe:
            from cake_tpu.models.moe.params import load_params_sharded
        else:
            from cake_tpu.models.llama.params import load_params_sharded

        specs = pipeline_param_specs(block_param_keys(cfg),
                                     tp_axis="tp" if tp else None)
        shardings = jax.tree.map(
            lambda s: NamedSharding(mesh, s), specs,
            is_leaf=lambda x: isinstance(x, P))
        log.info("streaming stage-local weight load from %s",
                 self.args.model)
        return load_params_sharded(self.args.model, cfg, shardings,
                                   dtype=self.dtype)

    def _maybe_quantize(self, params):
        """Apply --quant to a param tree without 1.5x peak HBM: int8
        donates the tree (fp buffers free as quantized copies
        materialise); int4 quantizes leaf-at-a-time (packed outputs can't
        alias donated buffers, so donation would warn and hold fp leaves
        to computation end)."""
        a = self.args
        if a.quant not in ("int8", "int4"):
            return params
        from functools import partial

        from cake_tpu.ops.quant import (
            quantize_params, quantize_params_leafwise,
        )
        if a.quant == "int8":
            params = jax.jit(partial(quantize_params, bits=8),
                             donate_argnums=0)(params)
        else:
            # int4 outputs (packed uint8 + group scales) can never alias
            # a donated fp buffer; the leafwise path frees fp leaves
            # incrementally without unusable-donation warnings
            params = quantize_params_leafwise(params, bits=4)
        log.info("weights quantized to %s (weight-only, %s)", a.quant,
                 "per-channel" if a.quant == "int8" else "group-wise")
        return params

    def load_image_model(self):
        from cake_tpu.models.sd.sd import SDGenerator
        gen = SDGenerator.load(self)
        a = self.args
        if a.dp > 1 or jax.process_count() > 1:
            # whole-pipeline SPMD over a ("dp",) mesh: --dp N splits the
            # UNet batch (guidance pair / multi-image) over N devices;
            # under multi-host every process must dispatch, so the mesh
            # spans ALL devices and cli._serve_multihost replays
            # generation ops to the followers
            if self.topology is not None:
                why = ("--dp" if a.dp > 1
                       else "multi-host image serving (which meshes the "
                            "whole pipeline)")
                raise ValueError(
                    f"{why} and an SD component topology are mutually "
                    "exclusive: one SPMD program cannot mix mesh-sharded "
                    "and committed-to-device components")
            import numpy as np
            from jax.sharding import Mesh
            devices = jax.devices()
            if jax.process_count() > 1:
                # multi-host: the mesh MUST span every process (each one
                # dispatches the same SPMD program); a --dp that asks
                # for anything else is an error, not silently ignored
                if a.dp > 1 and a.dp != len(devices):
                    raise ValueError(
                        f"multi-host image serving meshes over ALL "
                        f"{len(devices)} devices; --dp {a.dp} cannot be "
                        "honored (drop the flag or set it to the total "
                        "device count)")
                n = len(devices)
            else:
                n = a.dp
                if n > len(devices):
                    raise ValueError(
                        f"--dp {n} needs {n} devices, have "
                        f"{len(devices)}")
            gen.shard_for_mesh(Mesh(np.array(devices[:n]), ("dp",)))
        return gen
