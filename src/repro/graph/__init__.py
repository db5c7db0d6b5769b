"""LayerGraph IR + op registry: the model-agnostic spine (DESIGN.md §5).

`repro.graph` holds the three pieces every stage of the pipeline shares:

- `ir`: the typed network description (`ConvSpec`/`ReLU`/`PoolSpec`/`LRN`/
  `Branches`/`Flatten`/`DenseSpec` nodes in a `LayerGraph`), static shape
  inference, and the weight-layout plumbing (`graph_weights`, `init_graph`).
- `registry`: the ONE impl-dispatch site — (kind, impl) -> forward + cost hook
  + fusion metadata — and the PECR fusion rule (`fusion_eligible`).
- `executor`: the one structural walk (`walk_graph`), `run_unit`/`run_head`/
  `run_graph`, plus the structural primitives (`pad2d`, mode-aware
  `maxpool2d`, `lrn`).

Network builders live with their configs (`repro.configs.vgg19_sparse.
vgg19_graph`, `repro.configs.lenet`, `repro.configs.alexnet`,
`repro.configs.googlenet`); `as_graph`
bridges the legacy `CNNConfig`-shaped call sites onto the IR.
"""
from repro.graph.executor import (
    lrn,
    maxpool2d,
    pad2d,
    run_graph,
    run_head,
    run_unit,
    walk_graph,
)
from repro.graph.ir import (
    LRN,
    Branches,
    ConvSpec,
    ConvUnit,
    DenseSpec,
    Flatten,
    LayerGraph,
    PoolSpec,
    ReLU,
    graph_weights,
    init_graph,
    weight_shapes,
)
from repro.graph.registry import (
    OpImpl,
    conv_impl,
    fused_impl,
    fusion_eligible,
    get_op,
    list_ops,
    register_op,
    unit_impl,
)


def as_graph(graph_or_cfg) -> LayerGraph:
    """Normalize a `LayerGraph` | `CNNConfig` | None to a `LayerGraph` —
    the bridge that keeps every pre-IR call site (planner, engine, autotune,
    examples) working unchanged."""
    if isinstance(graph_or_cfg, LayerGraph):
        return graph_or_cfg
    from repro.configs.vgg19_sparse import CNNConfig, vgg19_graph

    if graph_or_cfg is None:
        graph_or_cfg = CNNConfig()
    if isinstance(graph_or_cfg, CNNConfig):
        return vgg19_graph(graph_or_cfg)
    raise TypeError(
        f"expected a LayerGraph or CNNConfig, got {type(graph_or_cfg).__name__}")


__all__ = [
    "LRN",
    "Branches",
    "ConvSpec",
    "ConvUnit",
    "DenseSpec",
    "Flatten",
    "LayerGraph",
    "OpImpl",
    "PoolSpec",
    "ReLU",
    "as_graph",
    "conv_impl",
    "fused_impl",
    "fusion_eligible",
    "get_op",
    "graph_weights",
    "init_graph",
    "list_ops",
    "lrn",
    "maxpool2d",
    "pad2d",
    "register_op",
    "run_graph",
    "run_head",
    "run_unit",
    "unit_impl",
    "walk_graph",
    "weight_shapes",
]
