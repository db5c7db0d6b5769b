"""A configuration's sizes, its dense-equivalent work, its weights and its
plain reference, from the configuration file alone.

Nothing here imports the system under test. A configuration file is a JSON
object whose `layers` list is a linear CNN:

    {"op": "conv", "out": 64, "k": 3, "stride": 1, "pad": 1}
    {"op": "relu"}
    {"op": "pool", "p": 2, "stride": 2}        # max-pool, windows tile exactly
    {"op": "flatten"}
    {"op": "dense", "out": 4096, "relu": true}

over an input of `in_channels` x `image_size` x `image_size`, with no biases.

A configuration that is not such a chain names instead a model module of
its own, `"module": "chipbench/models/<name>.py"`, a path under the
benchmark's root, and needs no `layers` or `weights`. The module provides

    layer_shapes(cfg)        a tuple of `Layer`: every conv, in the order the
                             program scopes them `conv<i>`, then the dense
                             layers
    make_weights(cfg, seed)  the params the program's Engine takes, made on
                             the device from the seed in one jitted call
    forward(cfg, params, x, operand_dtype=None)
                             the plain reference: logits of a batch in
                             jax.numpy, f32 at the highest matmul precision,
                             every conv's and dense layer's operands rounded
                             by `operand(..., operand_dtype, ...)` first
    layer_graph(cfg)         the configuration as the program's LayerGraph

and `layer_shapes`, `make_weights`, `forward` and everything they reach
here delegate to it. Only `layer_graph` may import the system under test
(`repro`); the rest, the reference above all, imports nothing of it and
takes nothing the program has made.
"""
from __future__ import annotations

import functools
import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path

F32_BYTES = 4
# the benchmark's root, where a configuration's module path starts by default
ROOT = Path(__file__).resolve().parents[1]
# where `load_config` keeps the resolved path of a configuration's module
MODULE_PATH = "module_path"


def load_config(path, root=ROOT) -> dict:
    """The configuration file at `path`; a model module it names is resolved
    against the benchmark root `root` and must lie under it."""
    with open(path) as f:
        cfg = json.load(f)
    own = ("module",) if "module" in cfg else ("layers", "weights")
    for key in ("name", "in_channels", "image_size", "serving") + own:
        if key not in cfg:
            raise ValueError(f"{path}: configuration lacks {key!r}")
    if "module" in cfg:
        base = Path(root).resolve()
        mod = (base / cfg["module"]).resolve()
        if base not in mod.parents:
            raise ValueError(f"{path}: module {cfg['module']!r} lies outside "
                             f"the benchmark root {base}")
        cfg[MODULE_PATH] = str(mod)
    layer_shapes(cfg)  # validates the layer list
    return cfg


@functools.cache
def _load_module(path: str):
    spec = importlib.util.spec_from_file_location(
        f"chipbench_model_{Path(path).stem}", path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def model_module(cfg):
    """The configuration's own model module, loaded once; None for a
    configuration given as a `layers` list."""
    path = cfg.get(MODULE_PATH)
    return None if path is None else _load_module(path)


def in_shape(cfg) -> tuple:
    return (cfg["in_channels"], cfg["image_size"], cfg["image_size"])


@dataclass(frozen=True)
class Layer:
    """One conv or dense layer with the shapes around it: `in_shape` is what
    enters it and `out_shape` what leaves its unit, after the ReLU and the
    pool that follow it directly (what any implementation has to write)."""

    op: str  # "conv" | "dense"
    index: int  # position among layers of the same op
    in_shape: tuple
    conv_shape: tuple  # the conv's or dense layer's own output shape
    out_shape: tuple
    weight_shape: tuple

    @property
    def macs(self) -> int:
        """Dense multiply-accumulates per image."""
        w = 1
        for d in self.weight_shape:
            w *= d
        if self.op == "dense":
            return w
        return w * self.conv_shape[1] * self.conv_shape[2]

    def bytes(self, batch: int) -> int:
        """f32 bytes a dense implementation of the unit must move at least,
        for `batch` images: every input once, the weights once, the unit's
        output once."""
        n_in = n_out = 1
        for d in self.in_shape:
            n_in *= d
        for d in self.out_shape:
            n_out *= d
        n_w = 1
        for d in self.weight_shape:
            n_w *= d
        return F32_BYTES * (batch * (n_in + n_out) + n_w)


def _pool_len(n: int, p: int, s: int) -> int:
    if n < p or (n - p) % s:
        raise ValueError(f"pool {p}/{s} does not tile a map of {n}")
    return (n - p) // s + 1


def layer_shapes(cfg) -> tuple:
    """Shape inference over `cfg["layers"]`, or the model module's: a tuple
    of `Layer`s."""
    mod = model_module(cfg)
    if mod is not None:
        return tuple(mod.layer_shapes(cfg))
    c, h, w = in_shape(cfg)
    flat = None
    out = []
    n_conv = n_dense = 0
    for node in cfg["layers"]:
        op = node["op"]
        if op == "conv":
            if flat is not None:
                raise ValueError("conv after flatten")
            k, s, p = node["k"], node.get("stride", 1), node.get("pad", 0)
            oh, ow = (h + 2 * p - k) // s + 1, (w + 2 * p - k) // s + 1
            shape = (node["out"], oh, ow)
            out.append(Layer("conv", n_conv, (c, h, w), shape, shape,
                             (node["out"], c, k, k)))
            n_conv += 1
            c, h, w = shape
        elif op == "relu":
            if not out:
                raise ValueError("relu before any layer")
        elif op == "pool":
            p, s = node["p"], node.get("stride", node["p"])
            h, w = _pool_len(h, p, s), _pool_len(w, p, s)
            last = out[-1]
            out[-1] = Layer(last.op, last.index, last.in_shape, last.conv_shape,
                            (c, h, w), last.weight_shape)
        elif op == "flatten":
            flat = c * h * w
        elif op == "dense":
            if flat is None:
                raise ValueError("dense before flatten")
            out.append(Layer("dense", n_dense, (flat,), (node["out"],),
                             (node["out"],), (flat, node["out"])))
            n_dense += 1
            flat = node["out"]
        else:
            raise ValueError(f"unknown layer op {op!r}")
    if flat is None or not out or out[-1].op != "dense":
        raise ValueError("a configuration ends in flatten and a dense head")
    return tuple(out)


def macs_per_image(cfg, op: str | None = None) -> int:
    return sum(lyr.macs for lyr in layer_shapes(cfg) if op in (None, lyr.op))


def n_params(cfg) -> int:
    total = 0
    for lyr in layer_shapes(cfg):
        n = 1
        for d in lyr.weight_shape:
            n *= d
        total += n
    return total


def roofline_s(cfg, batch: int, peaks: dict, op: str = "conv") -> float:
    """Least time the chip could take for one batch of `batch` images over
    every `op` layer: per layer, the larger of its dense FLOPs over peak
    FLOP/s and its bytes over peak bytes/s."""
    return sum(max(2 * lyr.macs * batch / peaks["flops"],
                   lyr.bytes(batch) / peaks["hbm_bytes_per_s"])
               for lyr in layer_shapes(cfg) if lyr.op == op)


# ---------------------------------------------------------------------------
# weights, on the device, from the seed
# ---------------------------------------------------------------------------


def seed_key(seed: int):
    """A threefry key from a seed of up to 64 bits: equal to
    `jax.random.PRNGKey(seed)` below 2**32, and keeping the high bits above
    it, where `PRNGKey` drops them."""
    import jax.numpy as jnp
    import numpy as np

    seed &= (1 << 64) - 1
    return jnp.asarray(np.array([seed >> 32, seed & 0xFFFFFFFF], np.uint32))


def base_weights(cfg, key):
    """Fan-in-scaled normal weights from `key`, then a depth-growing share of
    each conv's filters shifted negative so ReLU kills their channels (the
    trained-network statistic the planner exploits); traceable.
    {"conv": [OIHW], "dense": [(d_in, d_out)]}."""
    import jax

    layers = layer_shapes(cfg)
    rate = cfg["weights"]["dead_filter_rate"]
    shift = cfg["weights"]["dead_filter_shift"]
    keys = iter(jax.random.split(key, len(layers)))
    conv, dense = [], []
    for lyr in layers:
        s = lyr.weight_shape
        if lyr.op == "conv":
            w = jax.random.normal(next(keys), s) * (s[1] * s[2] * s[3]) ** -0.5
            dead = (jax.random.uniform(jax.random.PRNGKey(lyr.index),
                                       (s[0], 1, 1, 1))
                    < rate * lyr.index).astype(w.dtype)
            conv.append(w * (1.0 - dead) - shift * dead * abs(w))
        else:
            dense.append(jax.random.normal(next(keys), s) * s[0] ** -0.5)
    return {"conv": conv, "dense": dense}


def make_weights(cfg, seed: int):
    """The weights a run serves, in one jitted call on the default device.

    The values are `base_weights` from the configuration's fixed
    `weights.base_seed`; the seed draws a permutation of every conv's output
    channels, applied to that conv's filters and to the input of the layer
    after it. Every seed thus serves the same function with its channels in
    another order: the planner's occupancy counts live channels, not their
    places, so every seed gets the same plan and the same work, with the
    weights laid out differently. A model module makes its own."""
    import jax

    mod = model_module(cfg)
    if mod is not None:
        return mod.make_weights(cfg, seed)
    layers = layer_shapes(cfg)
    convs = [lyr for lyr in layers if lyr.op == "conv"]

    def build(seed_k):
        p = base_weights(cfg, jax.random.PRNGKey(cfg["weights"]["base_seed"]))
        keys = jax.random.split(seed_k, len(convs))
        conv, prev = [], None
        for k, lyr, w in zip(keys, convs, p["conv"]):
            perm = jax.random.permutation(k, lyr.weight_shape[0])
            w = w[perm]
            conv.append(w if prev is None else w[:, prev])
            prev = perm
        c, h, w_ = convs[-1].out_shape
        d0 = p["dense"][0]
        first = d0.reshape(c, h * w_, d0.shape[1])[prev].reshape(d0.shape)
        return {"conv": conv, "dense": [first] + list(p["dense"][1:])}

    return jax.jit(build)(seed_key(seed))


# ---------------------------------------------------------------------------
# the plain reference, and its lower-precision control
# ---------------------------------------------------------------------------

def operand(a, dtype, axes):
    """`a` as a lower-precision path would feed it to the matrix unit
    (None: unchanged). int8 is symmetric, one scale of absmax / 127 over
    `axes`: per sample for activations, per output channel for weights.
    Any other dtype is a plain rounding."""
    import jax.numpy as jnp

    if dtype is None:
        return a
    if jnp.dtype(dtype) == jnp.dtype(jnp.int8):
        amax = jnp.max(jnp.abs(a), axis=axes, keepdims=True)
        s = jnp.maximum(amax, 1e-30) / 127.0
        return jnp.clip(jnp.round(a / s), -127.0, 127.0) * s
    return a.astype(dtype).astype(jnp.float32)


def forward(cfg, params, x, operand_dtype=None):
    """Logits of a batch (N, C, H, W) in straightforward jax.numpy: f32
    throughout at the highest matmul precision. `operand_dtype` rounds every
    conv's and dense layer's operands to a lower precision first (the
    control); accumulation stays f32. A model module gives its own."""
    import jax
    import jax.numpy as jnp

    mod = model_module(cfg)
    if mod is not None:
        return mod.forward(cfg, params, x, operand_dtype)
    hi = jax.lax.Precision.HIGHEST
    ci = di = 0
    for node in cfg["layers"]:
        op = node["op"]
        if op == "conv":
            w = params["conv"][ci]
            ci += 1
            s, p = node.get("stride", 1), node.get("pad", 0)
            x = jax.lax.conv_general_dilated(
                operand(x, operand_dtype, (1, 2, 3)),
                operand(w, operand_dtype, (1, 2, 3)),
                window_strides=(s, s), padding=((p, p), (p, p)),
                dimension_numbers=("NCHW", "OIHW", "NCHW"), precision=hi)
        elif op == "relu":
            x = jnp.maximum(x, 0.0)
        elif op == "pool":
            p, s = node["p"], node.get("stride", node["p"])
            x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 1, p, p),
                                      (1, 1, s, s), "VALID")
        elif op == "flatten":
            x = x.reshape(x.shape[0], -1)
        else:  # dense
            w = params["dense"][di]
            di += 1
            x = jnp.dot(operand(x, operand_dtype, (1,)),
                        operand(w, operand_dtype, (0,)), precision=hi)
            if node.get("relu"):
                x = jnp.maximum(x, 0.0)
    return x
