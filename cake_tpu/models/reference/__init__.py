"""Plain float32 references: a model's forward pass in straightforward
`jax.numpy`, no cache, no kernels, no batching, independent of
`cake_tpu.ops` and `cake_tpu.models.llama`. What the served path is
compared with (tests/test_olmoe_reference.py on the CPU,
`chip_smoke.py --compare` on the chip)."""
