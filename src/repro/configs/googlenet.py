"""googlenet [cnn] — GoogLeNet on the LayerGraph IR (paper Table III rows 4-10).

Szegedy et al., "Going Deeper with Convolutions" (arXiv:1409.4842), Table 1
widths and Figure 3 layout, at the published 224x224: a 7x7/2 conv, a 3x3/2
max-pool and an LRN; a 1x1 and a 3x3 conv and another LRN and max-pool; then
nine inception modules, each four paths that read the same input — a 1x1
conv; a 1x1 reduce and a 3x3 conv; a 1x1 reduce and a 5x5 conv; a padded
3x3/1 max-pool and a 1x1 projection — joined by a channel concat
(`repro.graph.ir.Branches`), with 3x3/2 max-pools after 3b and 4e; then a
7x7 average pool and one FC layer, 1024 -> 1000. The max-pools run in ceil
mode (112 -> 56 -> 28 -> 14 -> 7). Dropout is the identity at inference, the
two auxiliary classifiers are training-only and left out, and the convs
carry no biases (the LayerGraph has none).

No pool here has stride == size, so PECR never fuses; the planner's sparse
choice is plain ECR on the deep 1x1/3x3/5x5 convs, and the padded pool
branch is exactly what `fusion_eligible` refuses.

`GOOGLENET_REDUCED` is the CPU-scale variant with every node kind: LRN, ceil
pools, a padded pool branch, three inception modules (two at 4x4, one at
2x2 after a ceil pool), an average pool and the head.
"""
from __future__ import annotations

from repro.graph.ir import (
    LRN,
    Branches,
    ConvSpec,
    DenseSpec,
    Flatten,
    LayerGraph,
    PoolSpec,
    ReLU,
    conv_out_hw,
    pool_out_len,
)

# Table 1: (module, #1x1, #3x3 reduce, #3x3, #5x5 reduce, #5x5, pool proj)
INCEPTION = (
    ("3a", 64, 96, 128, 16, 32, 32),
    ("3b", 128, 128, 192, 32, 96, 64),
    ("4a", 192, 96, 208, 16, 48, 64),
    ("4b", 160, 112, 224, 24, 64, 64),
    ("4c", 128, 128, 256, 24, 64, 64),
    ("4d", 112, 144, 288, 32, 64, 64),
    ("4e", 256, 160, 320, 32, 128, 128),
    ("5a", 256, 160, 320, 32, 128, 128),
    ("5b", 384, 192, 384, 48, 128, 128),
)
# the max-pool (3x3/2, ceil) that follows these modules
POOL_AFTER = ("3b", "4e")


def inception(name: str, n1: int, r3: int, n3: int, r5: int, n5: int,
              proj: int) -> Branches:
    """One inception module: Table 1's four paths in its column order."""
    return Branches(name=f"inception_{name}", paths=(
        (ConvSpec(n1, k=1, pad=0), ReLU()),
        (ConvSpec(r3, k=1, pad=0), ReLU(), ConvSpec(n3, k=3, pad=1), ReLU()),
        (ConvSpec(r5, k=1, pad=0), ReLU(), ConvSpec(n5, k=5, pad=2), ReLU()),
        (PoolSpec(3, stride=1, pad=1), ConvSpec(proj, k=1, pad=0), ReLU()),
    ))


def googlenet_graph(*, img_size: int = 224, in_channels: int = 3,
                    n_classes: int = 1000, stem=(64, 64, 192),
                    modules=INCEPTION, pool_after=POOL_AFTER, lrn=LRN(),
                    name: str = "googlenet") -> LayerGraph:
    pool = PoolSpec(3, stride=2, mode="ceil")
    c1, c2r, c2 = stem
    nodes = [
        ConvSpec(c1, k=7, stride=2, pad=3), ReLU(), pool, lrn,
        ConvSpec(c2r, k=1, pad=0), ReLU(),
        ConvSpec(c2, k=3, pad=1), ReLU(), lrn, pool,
    ]
    for mod in modules:
        nodes.append(inception(*mod))
        if mod[0] in pool_after:
            nodes.append(pool)
    feat = conv_out_hw(img_size, img_size, nodes[0])[0]
    for _ in range(2 + sum(mod[0] in pool_after for mod in modules)):
        feat = pool_out_len(feat, pool)  # the map the last module runs on
    nodes += [PoolSpec(feat, stride=1, kind="avg"), Flatten(),
              DenseSpec(n_classes)]
    return LayerGraph(name=name, in_shape=(in_channels, img_size, img_size),
                      nodes=tuple(nodes))


GOOGLENET = googlenet_graph()
GOOGLENET_REDUCED = googlenet_graph(
    img_size=32, n_classes=10, stem=(8, 8, 16),
    modules=(("3a", 8, 8, 16, 8, 8, 8), ("3b", 16, 8, 16, 8, 8, 8),
             ("4a", 16, 8, 16, 8, 8, 8)),
    pool_after=("3b",), name="googlenet-tiny")
