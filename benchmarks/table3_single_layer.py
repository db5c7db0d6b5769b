"""Paper Table III: single-conv-layer ECR vs dense on the extracted layers.

Claim checked: ECR wins on single extracted layers from LeNet / AlexNet /
GoogLeNet at their published input sparsities (0.90-0.95) — i.e. the
technique is not VGG-specific.

The rows are EXTRACTED FROM THE REAL NETWORK GRAPHS (`repro.configs.lenet`
/ `.alexnet` / `.googlenet`): each row is a `ConvUnit` pulled out of the
graph, carrying its true input shape, kernel size, stride and padding — the
5x5 LeNet conv, AlexNet's 3x3 mid-stack and GoogLeNet's inception 3x3s run
exactly as the full network runs them. A GoogLeNet row of
`_util.TABLE3_LAYERS` whose (input channels, map, output channels, k) match
no conv of Table 1 (Incep4a.1, Incep5a.1 and Incep4a.7 name 3x3 convs where
Table 1 has a 1x1 or another map) keeps the row's own shape, unpadded, and
says so in its `derived` column.

Each layer's input carries the published sparsity twice over: element-level
(the paper's metric — MAC reduction from zero skipping) and as a dead-channel
band (the trained-net ReLU channel death of Fig. 2 — what the block-ECR
schedule can actually skip). Columns: measured CPU wall time of the dense
path vs the Pallas block-ECR path (interpret mode, NOT comparable to the
paper's GTX1080 numbers), the paper's own MAC-reduction metric, and the
modeled-TPU block-ECR speedup from the roofline constants.
"""
from __future__ import annotations

from functools import partial

import jax

from benchmarks._util import TABLE3_LAYERS, modeled_tpu_us, time_fn
from repro.core import conv2d, synth_feature_map, window_stats
from repro.graph.executor import pad2d
from repro.kernels.ecr_conv.ops import channel_block_occupancy

# (graph, {unit name -> published Table III input sparsity})
def _network_layers():
    from repro.configs.alexnet import ALEXNET
    from repro.configs.alexnet import TABLE3_SPARSITY as ALEXNET_SP
    from repro.configs.lenet import LENET
    from repro.configs.lenet import TABLE3_SPARSITY as LENET_SP

    return ((LENET, LENET_SP), (ALEXNET, ALEXNET_SP))


def _seed(name: str) -> jax.Array:
    """Deterministic per-row key (`hash()` is salted per process — rows must
    not change between runs of the same commit)."""
    import zlib

    return jax.random.PRNGKey(zlib.crc32(name.encode()))


def _layer_input(key, shape, sparsity):
    """Element-sparse feature map with a dead-channel band: the published
    sparsity applied at both granularities — pure element sparsity from
    `synth_feature_map` (the paper's MAC metric) plus a deterministic
    trailing band of dead channels (the block schedule the TPU kernel
    skips). channel_dead_frac=0 keeps the two contributions separable: the
    band is the only channel-level death, so the surviving channels stay
    live and the row never degenerates to an all-zero input."""
    from repro.core import dead_channel_band

    x = synth_feature_map(key, shape, sparsity, channel_dead_frac=0.0)
    return dead_channel_band(x, min(sparsity, 1.0 - 1.0 / shape[0]))


def _bench_layer(name, x, conv, o):
    """One Table III row: dense vs block-ECR on a single extracted conv."""
    c = x.shape[0]
    key = jax.random.PRNGKey(1)
    kern = jax.random.normal(key, (o, c, conv.k, conv.k)) * 0.1
    xp = pad2d(x, conv.pad)
    dense = jax.jit(partial(conv2d, stride=conv.stride, impl="dense"))
    ecr = jax.jit(partial(conv2d, stride=conv.stride, impl="ecr_pallas"))
    t_dense = time_fn(dense, xp, kern, iters=2, warmup=1)
    t_ecr = time_fn(ecr, xp, kern, iters=2, warmup=1)
    st = window_stats(jax.device_get(xp), conv.k, conv.k, conv.stride)
    occ_raw = channel_block_occupancy(x, 8)  # without compaction
    occ = channel_block_occupancy(x, 8, compact=True)  # the kernel's schedule
    m = modeled_tpu_us(c, xp.shape[1], xp.shape[2], o, conv.k, conv.k,
                       conv.stride, occ)
    return {
        "name": name,
        "us_per_call": t_ecr,
        "derived": (f"dense_us={t_dense:.0f} k={conv.k} stride={conv.stride} "
                    f"mac_red={st.mul_reduction:.2f} occ_raw={occ_raw:.2f} "
                    f"occ_compacted={occ:.2f} "
                    f"tpu_model_speedup={m['speedup']:.2f}"),
    }


def rows():
    out = []
    # LeNet / AlexNet: units extracted from the real graphs
    for graph, published in _network_layers():
        for unit in graph.units():
            layer = f"conv{unit.index + 1}"
            if layer not in published:
                continue
            sp = published[layer]
            x = _layer_input(_seed(f"{graph.name}.{layer}"), unit.in_shape, sp)
            row = _bench_layer(f"table3/{graph.name}.{layer}", x, unit.conv,
                               unit.conv.c_out)
            row["derived"] = f"sparsity={sp} in={unit.in_shape} " + row["derived"]
            out.append(row)
    # GoogLeNet: the inception conv whose shape the row names, else the row
    from repro.configs.googlenet import GOOGLENET
    from repro.graph.ir import ConvSpec

    for net, layer, size, sp, c, o, k in TABLE3_LAYERS:
        if not net.startswith("GoogLeNet"):
            continue
        unit = next((u for u in GOOGLENET.units()
                     if u.in_shape == (c, size, size) and u.conv.c_out == o
                     and u.conv.k == k), None)
        where = (f"conv{unit.index + 1} of the graph" if unit is not None
                 else "the row's shape (it disagrees with Table 1)")
        conv = unit.conv if unit is not None else ConvSpec(o, k=k, pad=0)
        x = _layer_input(_seed(f"{net}.{layer}"), (c, size, size), sp)
        row = _bench_layer(f"table3/{net}.{layer}", x, conv, o)
        row["derived"] = (f"sparsity={sp} in=({c}, {size}, {size}) "
                          f"from={where} " + row["derived"])
        out.append(row)
    return out


def main():
    for r in rows():
        print(f"{r['name']},{r['us_per_call']:.1f},{r['derived']}")


if __name__ == "__main__":
    main()
