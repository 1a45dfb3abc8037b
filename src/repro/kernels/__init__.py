"""Pallas TPU kernels for the framework's compute hot-spots.

flash_attention — blockwise online-softmax attention (GQA, causal, sliding)
ssd_scan        — Mamba2 SSD fused chunked scan (state carried in VMEM)
skewed_bucket   — paper Algorithm 1 skewed hash partitioner (shuffle/MoE)

``ops`` holds the jit wrappers (model layouts; ``interpret=True`` off-TPU);
``ref`` holds the pure-jnp oracles used by the allclose test sweeps.

flash_attention and ssd_scan are standalone: their tests check them
against ``ref``, and no model calls them. The models take the XLA paths
(``models.attention.chunked_attention``, ``models.ssm.ssd_chunked``) until
a chip measurement puts a kernel on the path.
"""
from repro.kernels import ops, ref  # noqa: F401
