"""GoogLeNet (Szegedy et al., arXiv:1409.4842, Table 1 and Figure 3) as a
configuration's own model module: its shapes, weights, plain reference and
the program's graph of it.

The configuration file gives the widths: `stem` (conv1, the 1x1 reduce and
the 3x3 conv), `inception` (Table 1's rows: module, #1x1, #3x3 reduce,
#3x3, #5x5 reduce, #5x5, pool proj), `pool_after` (the modules a 3x3/2
max-pool follows), `classes` and `lrn`. The network: a 7x7/2 conv, a 3x3/2
max-pool and an LRN; a 1x1 and a 3x3 conv, an LRN and a 3x3/2 max-pool;
the inception modules, each four paths from one input joined by a channel
concat (1x1; 1x1 then 3x3; 1x1 then 5x5; a 3x3/1 max-pool padded by 1 then
a 1x1), with ReLU after every conv; an average pool over the last map; one
FC layer to the logits. Max-pools are in ceil mode (a partial last window
counts, and a window starts inside the map). No biases; dropout is the
identity; the auxiliary classifiers (training only) are left out.

Convs are listed in program order: the stem, then for each module its
paths in Table 1's column order.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import cnn

# main-path depth of the stem's convs, and of a module's first and second
# conv of a path; module m adds 2 m (22 layers along the main path)
STEM_DEPTH = (0, 1, 2)
# the stem convs whose outputs enter an LRN: never permuted, because LRN
# mixes neighbouring channels
LRN_FED = (0, 2)


def _pool_len(n: int, p: int, s: int, pad: int = 0) -> int:
    """Ceil-mode pooled length: the last window starts inside the map or
    its leading padding."""
    out = -(-(n + 2 * pad - p) // s) + 1
    if (out - 1) * s >= n + pad:
        out -= 1
    return out


def _module_convs(row):
    """[(out, k, pad, reads)] of one module's six convs, Table 1's column
    order; `reads` is "in" (the module's input, the pool path's after its
    3x3/1 max-pool) or the position of the conv it follows."""
    _, n1, r3, n3, r5, n5, proj = row
    return [(n1, 1, 0, "in"), (r3, 1, 0, "in"), (n3, 3, 1, 1),
            (r5, 1, 0, "in"), (n5, 5, 2, 3), (proj, 1, 0, "in")]


def layer_shapes(cfg) -> tuple:
    c, h, _ = cnn.in_shape(cfg)
    c1, c2r, c2 = cfg["stem"]
    out = []

    def conv(cin, hw, o, k, s, p, pool=None):
        """Append one conv; returns the side of the map leaving it (after
        `pool`, a (size, stride) max-pool of its own)."""
        oh = (hw + 2 * p - k) // s + 1
        after = _pool_len(oh, *pool) if pool else oh
        out.append(cnn.Layer("conv", len(out), (cin, hw, hw), (o, oh, oh),
                             (o, after, after), (o, cin, k, k)))
        return after

    h = conv(c, h, c1, 7, 2, 3, pool=(3, 2))
    conv(c1, h, c2r, 1, 1, 0)
    conv(c2r, h, c2, 3, 1, 1)
    c, h = c2, _pool_len(h, 3, 2)
    for row in cfg["inception"]:
        convs = _module_convs(row)
        for o, k, p, reads in convs:
            conv(c if reads == "in" else convs[reads][0], h, o, k, 1, p)
        c = row[1] + row[3] + row[5] + row[6]
        if row[0] in cfg["pool_after"]:
            h = _pool_len(h, 3, 2)
    out.append(cnn.Layer("dense", 0, (c,), (cfg["classes"],),
                         (cfg["classes"],), (c, cfg["classes"])))
    return tuple(out)


def conv_depths(cfg) -> list:
    """Each conv's depth along the main path (the dead-filter recipe)."""
    depths = list(STEM_DEPTH)
    for m, row in enumerate(cfg["inception"]):
        depths += [3 + 2 * m + (reads != "in")
                   for *_, reads in _module_convs(row)]
    return depths


def base_weights(cfg, rng):
    """Fan-in-scaled normal weights from the numpy generator `rng`; conv i at
    main-path depth d has about `dead_filter_rate` x d of its filters
    shifted negative, so ReLU kills their channels. conv1's weights are
    multiplied by `pixel_scale`: the network then sees the benchmark's
    [0, 1) pixels as Caffe's 0-255 ones, the range its LRN constants were
    set for."""
    layers = layer_shapes(cfg)
    wcfg = cfg["weights"]
    rate, shift = wcfg["dead_filter_rate"], wcfg["dead_filter_shift"]
    depths = conv_depths(cfg)
    conv, dense = [], []
    for lyr in layers:
        s = lyr.weight_shape
        w = rng.standard_normal(s, dtype=np.float32)
        if lyr.op == "conv":
            w *= np.float32((s[1] * s[2] * s[3]) ** -0.5)
            dead = rng.random(s[0]) < rate * depths[lyr.index]
            w[dead] = -shift * np.abs(w[dead])
            if lyr.index == 0:
                w *= np.float32(wcfg["pixel_scale"])
            conv.append(w)
        else:
            dense.append(w * np.float32(s[0] ** -0.5))
    return {"conv": conv, "dense": dense}


def make_weights(cfg, seed: int):
    """`base_weights` from the configuration's `weights.base_seed`, with every
    conv's output channels permuted by the seed (but the LRN-fed stem convs)
    and the inputs of its consumers to match: the next conv of its path,
    each concat segment for the next module's four paths, the FC's rows.
    Every seed serves the same function, plan and work. Drawn on the host in
    numpy, so no program is compiled for them, and put on the device in one
    transfer."""
    p = base_weights(cfg, np.random.default_rng(cfg["weights"]["base_seed"]))
    rng = np.random.default_rng([seed & ((1 << 64) - 1), 2])
    perms = [None if i in LRN_FED else rng.permutation(w.shape[0])
             for i, w in enumerate(p["conv"])]
    reads_in = [None, perms[0], perms[1]]  # the stem's input orders
    module_in = perms[2]
    for row in cfg["inception"]:
        base = len(reads_in)
        convs = _module_convs(row)
        reads_in += [module_in if r == "in" else perms[base + r]
                     for *_, r in convs]
        segs, off = [], 0
        for j in (0, 2, 4, 5):  # the paths' last convs, concat order
            segs.append(perms[base + j] + off)
            off += convs[j][0]
        module_in = np.concatenate(segs)
    conv = []
    for w, perm, src in zip(p["conv"], perms, reads_in):
        w = w if perm is None else w[perm]
        conv.append(w if src is None else w[:, src])
    return jax.device_put({"conv": conv,
                           "dense": [p["dense"][0][module_in]]})


def _maxpool(x, p, s, pad=0):
    n = x.shape[-1]
    tail = (_pool_len(n, p, s, pad) - 1) * s + p - n - 2 * pad
    return jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, 1, p, p), (1, 1, s, s),
        ((0, 0), (0, 0), (pad, pad + max(tail, 0)), (pad, pad + max(tail, 0))))


def _lrn(x, size, alpha, beta, k):
    half = size // 2
    sq = jnp.pad(x * x, ((0, 0), (half, size - 1 - half), (0, 0), (0, 0)))
    total = sum(sq[:, i:i + x.shape[1]] for i in range(size))
    return x / (k + alpha / size * total) ** beta


def forward(cfg, params, x, operand_dtype=None):
    """Logits of a batch (N, C, H, W) in straightforward jax.numpy: f32 at
    the highest matmul precision, every conv's and the FC's operands rounded
    by `cnn.operand` first."""
    hi = jax.lax.Precision.HIGHEST
    ws = iter(params["conv"])
    lrn = cfg["lrn"]

    def conv(x, s=1):
        w = next(ws)
        k = w.shape[-1]
        y = jax.lax.conv_general_dilated(
            cnn.operand(x, operand_dtype, (1, 2, 3)),
            cnn.operand(w, operand_dtype, (1, 2, 3)),
            window_strides=(s, s), padding=((k // 2, k // 2),) * 2,
            dimension_numbers=("NCHW", "OIHW", "NCHW"), precision=hi)
        return jnp.maximum(y, 0.0)

    x = _maxpool(conv(x, 2), 3, 2)
    x = _lrn(x, **lrn)
    x = _lrn(conv(conv(x)), **lrn)
    x = _maxpool(x, 3, 2)
    for row in cfg["inception"]:
        outs = [conv(x), conv(conv(x)), conv(conv(x)),
                conv(_maxpool(x, 3, 1, pad=1))]
        x = jnp.concatenate(outs, axis=1)
        if row[0] in cfg["pool_after"]:
            x = _maxpool(x, 3, 2)
    p = x.shape[-1]
    x = jax.lax.reduce_window(x, 0.0, jax.lax.add, (1, 1, p, p), (1, 1, 1, 1),
                              "VALID") / (p * p)
    x = x.reshape(x.shape[0], -1)
    return jnp.dot(cnn.operand(x, operand_dtype, (1,)),
                   cnn.operand(params["dense"][0], operand_dtype, (0,)),
                   precision=hi)


def layer_graph(cfg):
    from repro.configs.googlenet import googlenet_graph
    from repro.graph.ir import LRN

    return googlenet_graph(
        img_size=cfg["image_size"], in_channels=cfg["in_channels"],
        n_classes=cfg["classes"], stem=tuple(cfg["stem"]),
        modules=tuple(tuple(row) for row in cfg["inception"]),
        pool_after=tuple(cfg["pool_after"]), lrn=LRN(**cfg["lrn"]),
        name=cfg["name"])
