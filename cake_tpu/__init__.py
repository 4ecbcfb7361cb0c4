"""cake-tpu: a TPU-native distributed inference framework.

A ground-up JAX/XLA/Pallas re-design of the capabilities of `shurizzle/cake`
(distributed Llama-3 + Stable Diffusion inference): instead of a master/worker
TCP pipeline shipping hidden states between heterogeneous devices, cake-tpu
compiles the whole model as SPMD programs over a `jax.sharding.Mesh`, with
`topology.yml` mapping contiguous transformer-block ranges onto pipeline
stages and XLA collectives (ICI) doing the transport.

Layer map (bottom → top), mirroring SURVEY.md §1:
  ops/       — RoPE, RMSNorm, attention (XLA + Pallas flash), sampling
  models/    — Llama-3 family, Stable Diffusion, chat templating
  parallel/  — mesh construction, stage assignment, pjit/shard_map pipelines
  utils/     — device + dtype policy, safetensors loading
  topology   — YAML topology with `model.layers.N-M` range expansion
  api/       — OpenAI-compatible REST serving
  tools/     — weight splitting, introspection
"""

__version__ = "0.1.0"

from cake_tpu.topology import Topology, Node  # noqa: F401
from cake_tpu.args import Args, SDArgs, ImageGenerationArgs  # noqa: F401
