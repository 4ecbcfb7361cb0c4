"""The benchmark's own code: everything the yardstick is made of.

Stdlib only, and never JAX: the server child holds the chip. The one
module that imports JAX (`trace_reduce`) runs as a short-lived process
of its own after the server has exited.
"""
