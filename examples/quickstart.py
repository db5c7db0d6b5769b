"""Quickstart: the paper's technique in five minutes on CPU.

1. Build a sparse feature map (deep-layer statistics: dead channels + ReLU).
2. Convolve it three ways: dense, ECR (paper §IV), fused PECR (paper §V) —
   all numerically identical.
3. Show the paper's metric (skipped MACs) and the TPU kernel's metric
   (skipped channel blocks after ECR compaction).

Run: PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp

from repro.core import conv2d, conv_pool, synth_feature_map, window_stats
from repro.kernels.ecr_conv.ops import channel_block_occupancy

key = jax.random.PRNGKey(0)

# a deep-layer-like feature map: 256 channels, 14x14, 80% zeros
x = synth_feature_map(key, (256, 14, 14), sparsity=0.8)
kernels = jax.random.normal(jax.random.PRNGKey(1), (128, 256, 3, 3)) * 0.05

dense = conv2d(x, kernels, stride=1, impl="dense")
ecr = conv2d(x, kernels, stride=1, impl="ecr")  # paper Algorithm 1+2
pallas = conv2d(x, kernels, stride=1, impl="ecr_pallas")  # Pallas kernel: Mosaic on TPU, interpreted on CPU
print(f"ECR    vs dense max err: {float(jnp.abs(ecr - dense).max()):.2e}")
print(f"Pallas vs dense max err: {float(jnp.abs(pallas - dense).max()):.2e}")

fused = conv_pool(x, kernels, impl="pecr")  # conv+ReLU+maxpool in one pass
unfused = conv_pool(x, kernels, impl="unfused")
print(f"PECR   vs unfused max err: {float(jnp.abs(fused - unfused).max()):.2e}")

st = window_stats(jax.device_get(x), 3, 3, 1)
print(f"\npaper metric  — multiplications skipped: {st.mul_reduction:.0%} "
      f"(additions: {st.add_reduction:.0%})")
occ = channel_block_occupancy(x, 8, compact=True)
print(f"TPU kernel    — channel blocks skipped after compaction: {1-occ:.0%}")
print(f"                (MXU MACs and HBM->VMEM DMAs both drop by this factor)")
