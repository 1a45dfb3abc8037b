"""Pallas TPU kernels for the framework's compute hot-spots.

flash_attention — blockwise online-softmax attention (GQA, causal, sliding)
ssd_scan        — Mamba2 SSD fused chunked scan (state carried in VMEM)
skewed_bucket   — paper Algorithm 1 skewed hash partitioner (shuffle/MoE)

``ops`` holds the jit wrappers (model layouts; ``interpret=True`` off-TPU);
``ref`` holds the pure-jnp oracles used by the allclose test sweeps.
"""
from repro.kernels import ops, ref  # noqa: F401
