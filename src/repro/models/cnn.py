"""CNNs for the paper's own evaluation: VGG-19 (+ reduced variants), with the
conv stack runnable through every implementation the paper compares:

  impl = "dense"       lax.conv + separate ReLU + separate maxpool (cuDNN stand-in)
  impl = "im2col"      materialized extension + GEMM (paper §VII baseline)
  impl = "ecr"         ECR sparse conv (paper §IV), unfused pooling
  impl = "pecr"        ECR conv for in-stage layers + PECR fused conv+ReLU+pool
                       for the stage-final layer (paper §V)
  impl = "ecr_pallas" / "pecr_pallas"  same, through the Pallas TPU kernels

Since the LayerGraph refactor this module holds no dispatch of its own: a
`CNNConfig` lowers onto the IR via `repro.configs.vgg19_sparse.vgg19_graph`
and executes through `repro.graph.executor` (the registry resolves every
(kind, impl) pair, including which stage-final layers fuse into PECR). Other
networks (`repro.configs.lenet` / `.alexnet`) use `repro.graph.run_graph` /
`init_graph` directly — VGG-19 is one graph constructor among several.

Also holds the whisper conv frontend (a STUB for the assigned shapes; the
dry-run feeds precomputed frame embeddings — this exists so the ECR conv has a
real consumer in the audio arch and is exercised by unit tests).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.vgg19_sparse import CNNConfig, vgg19_graph
from repro.graph.executor import maxpool2d, pad2d, run_graph, run_unit, walk_graph
from repro.graph.ir import PoolSpec, graph_weights


def init_cnn(key, ccfg: CNNConfig, dtype=jnp.float32) -> dict:
    """Random VGG-style params in the legacy {"stages", "fc1", "fc2"} layout
    (graph-native callers use `repro.graph.init_graph` instead). Classifier
    dims come from the graph's static shape inference — no trace needed."""
    graph = vgg19_graph(ccfg)
    keys = jax.random.split(key, 64)
    ki = iter(keys)
    stages = []
    c_in = ccfg.in_channels
    k = ccfg.kernel_size
    for c_out, n_convs in ccfg.plan:
        convs = []
        for _ in range(n_convs):
            w = jax.random.normal(next(ki), (c_out, c_in, k, k), dtype) * (c_in * k * k) ** -0.5
            convs.append(w)
            c_in = c_out
        stages.append(convs)
    flat = graph.flat_dim()
    fc1 = jax.random.normal(next(ki), (flat, 512), dtype) * flat ** -0.5
    fc2 = jax.random.normal(next(ki), (512, ccfg.n_classes), dtype) * 512 ** -0.5
    return {"stages": stages, "fc1": fc1, "fc2": fc2}


def _pad1(x):
    """1-pixel spatial padding, single image (C,H,W) or batch (N,C,H,W)."""
    return pad2d(x, 1)


def _maxpool(x, p, stride: int = 0, mode: str = "valid"):
    """p x p max-pool over the trailing two (spatial) dims.

    mode="valid" (default) RAISES when the windows do not tile the map — the
    old behaviour silently truncated the tail (`x[..., :oh//p*p, :ow//p*p]`),
    which AlexNet/LeNet shapes actually hit; pass mode="floor" to truncate
    deliberately or mode="ceil" to keep a -inf-padded partial window."""
    return maxpool2d(x, PoolSpec(p, stride=stride, mode=mode))


def cnn_forward(params, img, impl: str = "dense", ccfg: CNNConfig = CNNConfig()):
    """(C,H,W) -> class logits, or a batch (N,C,H,W) -> (N, n_classes).

    The batch flows through the conv stack as whole-batch layer calls (not a
    python loop over samples); see `cnn_forward_batch` for the explicit API.
    Every conv/conv_pool call carries the whole batch, so each layer is ONE
    jitted op (batched Pallas grid for the *_pallas impls, native lax /
    vmapped oracle batching otherwise). Impl resolution — which units fuse,
    which conv family backs a fused request — is the registry's `unit_impl`
    rule, not local string matching.
    """
    return run_graph(vgg19_graph(ccfg), params, img, impl)


def cnn_forward_batch(params, imgs, impl: str = "dense", ccfg: CNNConfig = CNNConfig()):
    """Batched inference entry point: (N,C,H,W) -> (N, n_classes) logits.

    Each conv layer runs once over the whole batch: the dense path uses lax's
    native NCHW batching, the ECR/PECR oracles carry the batch dim through the
    compressed formats, and the Pallas paths use the (n_ob, N, n_cb) batched
    grid with per-sample channel-block schedules (DESIGN.md §2.4).
    """
    assert imgs.ndim == 4, f"expected (N,C,H,W), got {imgs.shape}"
    return cnn_forward(params, imgs, impl=impl, ccfg=ccfg)


def shift_dead_channels(params, rate: float = 0.04, shift: float = 0.12):
    """Emulate trained-net activation statistics on random-init params.

    Trained VGG nets lose whole filters to ReLU + BN shift, growing with depth
    (paper Fig. 2); random init does not. Shift a depth-growing fraction of
    each conv's output filters negative so ReLU kills those channels — used by
    `benchmarks/fig2_sparsity.py` and the planner demo to produce realistic
    channel-block occupancy without trained weights. Works on both the legacy
    {"stages"} layout and the graph-native {"conv", "dense"} layout.
    """
    conv_ws, _ = graph_weights(params)
    shifted_ws = []
    for depth, w in enumerate(conv_ws):
        key = jax.random.PRNGKey(depth)
        bias_mask = (jax.random.uniform(key, (w.shape[0], 1, 1, 1)) <
                     rate * depth).astype(w.dtype)
        shifted_ws.append(w * (1.0 - bias_mask) - shift * bias_mask * jnp.abs(w))
    if "stages" in params:
        out = {"stages": [], "fc1": params["fc1"], "fc2": params["fc2"]}
        it = iter(shifted_ws)
        for convs in params["stages"]:
            out["stages"].append([next(it) for _ in convs])
        return out
    return {"conv": shifted_ws, "dense": list(params["dense"])}


def cnn_feature_maps(params, img, ccfg: CNNConfig = CNNConfig()):
    """The paper's data set (§VI-A): every feature map ENTERING a conv layer."""
    conv_ws, _ = graph_weights(params)
    maps = []

    def on_unit(unit, x):
        maps.append(x)
        return run_unit(x, conv_ws[unit.index], unit, "conv", "dense")

    walk_graph(vgg19_graph(ccfg), img, on_unit)
    return maps


# ---------------------------------------------------------------------------
# whisper conv frontend (STUB consumer of the ECR conv; not in the dry-run path)
# ---------------------------------------------------------------------------


def init_whisper_frontend(key, n_mels: int, d_model: int, dtype=jnp.float32):
    k1, k2 = jax.random.split(key)
    return {
        "conv1": jax.random.normal(k1, (d_model, n_mels, 3), dtype) * (n_mels * 3) ** -0.5,
        "conv2": jax.random.normal(k2, (d_model, d_model, 3), dtype) * (d_model * 3) ** -0.5,
    }


def whisper_frontend(params, mel, stride2: bool = True):
    """mel: (n_mels, T) -> (T//2, d_model) frame embeddings (gelu conv x2)."""
    x = mel[None]  # (1, n_mels, T)
    x = jax.lax.conv_general_dilated(
        x, params["conv1"], window_strides=(1,), padding=((1, 1),),
        dimension_numbers=("NCH", "OIH", "NCH"))
    x = jax.nn.gelu(x)
    x = jax.lax.conv_general_dilated(
        x, params["conv2"], window_strides=((2,) if stride2 else (1,)), padding=((1, 1),),
        dimension_numbers=("NCH", "OIH", "NCH"))
    x = jax.nn.gelu(x)
    return x[0].T  # (T', d_model)
