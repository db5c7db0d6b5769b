"""LayerGraph IR: the typed, model-agnostic network description every stage of
the pipeline (planner -> executor -> serving -> autotune) consumes.

The paper's Table III point is that sparsity-aware convolution is not
VGG-specific — it extracts layers from LeNet, AlexNet and GoogLeNet — so the
spine must not be either. A `LayerGraph` is a sequence of typed nodes
(`ConvSpec`, `ReLU`, `PoolSpec`, `LRN`, `Branches`, `Flatten`, `DenseSpec`)
plus an input shape; everything else (which impl runs each conv, whether a
conv+ReLU+pool triple fuses into PECR) is decided downstream by the op
registry and the planner, never by the graph itself.

`Branches` is the one composite node: several linear paths that read the same
input, joined by a channel concat (a GoogLeNet inception module,
`repro.configs.googlenet`). A pool or LRN that no conv unit absorbs stands
alone, after a unit, a concat or another such node.

Shape inference is static python (shapes are compile-time facts for the Pallas
kernels anyway), so a graph knows every intermediate (C, H, W) without tracing.
`body()` is the parsed structure the executor walks (`repro.graph.executor.
walk_graph`): plannable conv units — one conv, its trailing ReLU if adjacent,
and its trailing pool if adjacent, the structural precondition of the PECR
fusion rule (`repro.graph.registry.fusion_eligible`) — stand-alone `Step`s and
`Join`s. `units()` flattens the conv units into one tuple in program order
(inside a `Branches`, path by path), the order of `params["conv"]`, of a
plan's layers and of the `conv<i>` scopes.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import cached_property

# ---------------------------------------------------------------------------
# Node types
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ConvSpec:
    """2-D convolution node: `c_out` filters of k x k at `stride`, with
    `pad` pixels of explicit zero padding on each spatial edge."""

    c_out: int
    k: int = 3
    stride: int = 1
    pad: int = 1


@dataclass(frozen=True)
class ReLU:
    """Element-wise max(x, 0)."""


@dataclass(frozen=True)
class PoolSpec:
    """p x p pool at `stride` (0 = p, the non-overlapping default).

    `kind` is "max" (default) or "avg". `pad` pixels go on each spatial edge
    first: -inf for a max-pool (a padded average pool is refused).

    `mode` governs what happens when the windows do not tile the map exactly
    (the (ih + 2 pad - p) % stride != 0 tail):
      - "valid" (default): REQUIRE exact coverage; shape inference raises.
        This is the guard against the silent `x[..., :oh//p*p, ...]`
        truncation the VGG-only code used to do.
      - "floor": drop the tail explicitly (the classic cuDNN default).
      - "ceil": pad with -inf so a partial tail window still contributes
        (max-pools only).
    """

    p: int = 2
    stride: int = 0  # 0 == p
    mode: str = "valid"  # valid | floor | ceil
    pad: int = 0
    kind: str = "max"  # max | avg

    @property
    def s(self) -> int:
        return self.stride or self.p


@dataclass(frozen=True)
class LRN:
    """Local response normalization across channels:
    b_c = a_c / (k + alpha / size * sum_{|c' - c| <= size // 2} a_c'^2) ** beta."""

    size: int = 5
    alpha: float = 1e-4
    beta: float = 0.75
    k: float = 1.0


@dataclass(frozen=True)
class Branches:
    """Linear paths (tuples of nodes) that all read this node's input; the
    output is their channel concat, in path order. `name` scopes the
    module's ops in a compiled program."""

    paths: tuple
    name: str = "branches"


@dataclass(frozen=True)
class Flatten:
    """(C, H, W) -> (C*H*W,) — the conv-stack / classifier seam."""


@dataclass(frozen=True)
class DenseSpec:
    """Fully-connected layer to `d_out` features, optional fused ReLU."""

    d_out: int
    relu: bool = False


# fields added after graph signatures existed: left out of `signature()` at
# their defaults, so every earlier graph keeps its plan-cache key
_LATE_FIELDS = {PoolSpec: {"pad": 0, "kind": "max"}}


def _node_sig(node) -> tuple:
    if isinstance(node, Branches):
        return ("Branches", node.name,
                tuple(tuple(_node_sig(n) for n in path) for path in node.paths))
    late = _LATE_FIELDS.get(type(node), {})
    return (type(node).__name__,) + tuple(
        v for f, v in vars(node).items() if f not in late or v != late[f])


# ---------------------------------------------------------------------------
# Shape inference
# ---------------------------------------------------------------------------


def conv_out_hw(h: int, w: int, conv: ConvSpec) -> tuple:
    oh = (h + 2 * conv.pad - conv.k) // conv.stride + 1
    ow = (w + 2 * conv.pad - conv.k) // conv.stride + 1
    if oh < 1 or ow < 1:
        raise ValueError(f"conv {conv} produces empty output from ({h}, {w})")
    return oh, ow


def pool_out_len(n: int, pool: PoolSpec) -> int:
    """Pooled length of one spatial dim; raises on an unintended tail
    (`mode="valid"` is the explicit-truncation guard of PoolSpec)."""
    if pool.kind not in ("max", "avg"):
        raise ValueError(f"unknown pool kind {pool.kind!r}")
    if pool.kind == "avg" and (pool.pad or pool.mode == "ceil"):
        raise ValueError("an average pool takes no padding and no ceil mode")
    span = n + 2 * pool.pad
    if span < pool.p:
        raise ValueError(f"pool window p={pool.p} larger than input dim {n}"
                         + (f" padded by {pool.pad}" if pool.pad else ""))
    tail = (span - pool.p) % pool.s
    if pool.mode == "valid":
        if tail:
            raise ValueError(
                f"pool p={pool.p} stride={pool.s} would silently drop a "
                f"{tail}-wide tail of a {span}-wide map; use mode='floor' to "
                f"truncate or mode='ceil' to keep a partial window")
        return (span - pool.p) // pool.s + 1
    if pool.mode == "floor":
        return (span - pool.p) // pool.s + 1
    if pool.mode == "ceil":
        out = -(-(span - pool.p) // pool.s) + 1
        # standard ceil_mode rule (cuDNN/PyTorch): the last window must START
        # inside the input or its leading padding — a window lying entirely
        # in the trailing padding would pool nothing but -inf and leak it
        # into the feature map
        if (out - 1) * pool.s >= n + pool.pad:
            out -= 1
        return out
    raise ValueError(f"unknown pool mode {pool.mode!r}")


def pool_out_hw(h: int, w: int, pool: PoolSpec) -> tuple:
    return pool_out_len(h, pool), pool_out_len(w, pool)


# ---------------------------------------------------------------------------
# The parsed body: conv units (the planner's granularity), steps and joins
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ConvUnit:
    """One plannable unit: a conv, its adjacent ReLU, its adjacent pool.

    `stage`/`slot` mirror the classic VGG indexing (stage = number of pools
    crossed so far on the main path, slot = conv index within the stage) so
    plans stay human-readable across architectures. `reads` is the index of
    the unit whose output this conv reads unchanged, or -1 when it reads the
    graph's input or the output of a stand-alone step or a concat."""

    index: int
    stage: int
    slot: int
    conv: ConvSpec
    relu: bool
    pool: PoolSpec | None
    in_shape: tuple  # (C, H, W) entering the conv (pre-padding)
    out_shape: tuple  # (C, H, W) leaving the unit (post-pool if any)
    reads: int = -1

    @property
    def conv_out_shape(self) -> tuple:
        """(C, H, W) after the conv itself (pre-pool)."""
        oh, ow = conv_out_hw(self.in_shape[1], self.in_shape[2], self.conv)
        return (self.conv.c_out, oh, ow)


@dataclass(frozen=True)
class Step:
    """A pool or LRN that no conv unit absorbed, run under `scope`
    (`pool<j>`, `avgpool` or `lrn<j>`)."""

    node: object
    scope: str
    in_shape: tuple
    out_shape: tuple


@dataclass(frozen=True)
class Join:
    """A parsed `Branches`: each path a tuple of `ConvUnit`/`Step`/`Join`
    from the same input, the output their channel concat."""

    name: str
    paths: tuple
    in_shape: tuple
    out_shape: tuple


class _Parser:
    """One pass over a graph's body nodes: shape inference, unit grouping,
    scope names and each unit's producer."""

    def __init__(self, name: str):
        self.name = name
        self.units: list = []
        self.stage = self.slot = 0
        self.n_pool = self.n_lrn = 0

    def chain(self, nodes, shape: tuple, reads: int, top: bool):
        """Parse a linear node sequence from a value of `shape` produced by
        unit `reads` (-1: the graph input, a step's or a concat's output);
        `top` is the graph's main path. Returns (steps, shape)."""
        steps = []
        c, h, w = shape
        cur: dict | None = None  # open conv unit being grouped
        fresh = top  # still reading the raw graph input

        def close():
            nonlocal cur, reads
            if cur is not None:
                unit = ConvUnit(**cur)
                self.units.append(unit)
                steps.append(unit)
                reads = unit.index
                cur = None

        for node in nodes:
            if isinstance(node, ConvSpec):
                close()
                oh, ow = conv_out_hw(h, w, node)
                cur = dict(index=len(self.units), stage=self.stage,
                           slot=self.slot, conv=node, relu=False, pool=None,
                           in_shape=(c, h, w), out_shape=(node.c_out, oh, ow),
                           reads=reads)
                c, h, w = node.c_out, oh, ow
                self.slot += 1
                fresh = False
            elif isinstance(node, ReLU):
                if cur is None or cur["pool"] is not None:
                    raise ValueError(f"{self.name}: ReLU must follow a conv")
                cur["relu"] = True
            elif isinstance(node, PoolSpec) and cur is not None:
                h, w = pool_out_hw(h, w, node)
                cur["pool"] = node
                cur["out_shape"] = (c, h, w)
                close()
                if top:
                    self.stage, self.slot = self.stage + 1, 0
            elif isinstance(node, (PoolSpec, LRN)):
                what = "pool" if isinstance(node, PoolSpec) else "LRN"
                if fresh:
                    raise ValueError(
                        f"{self.name}: {what} must follow a conv unit, a "
                        f"concat, a pool or an LRN, not the graph input")
                close()
                if isinstance(node, LRN):
                    self.n_lrn += 1
                    scope, out = f"lrn{self.n_lrn}", (c, h, w)
                else:
                    if node.kind == "avg":
                        scope = "avgpool"
                    else:
                        self.n_pool += 1
                        scope = f"pool{self.n_pool}"
                    out = (c,) + pool_out_hw(h, w, node)
                    if top:
                        self.stage, self.slot = self.stage + 1, 0
                steps.append(Step(node, scope, (c, h, w), out))
                (c, h, w), reads = out, -1
            elif isinstance(node, Branches):
                close()
                if not node.paths:
                    raise ValueError(f"{self.name}: {node.name} has no paths")
                paths, outs = [], []
                for k, path in enumerate(node.paths):
                    if not path:
                        raise ValueError(
                            f"{self.name}: {node.name} path {k} is empty")
                    sub, out = self.chain(path, (c, h, w), reads, top=False)
                    paths.append(tuple(sub))
                    outs.append(out)
                if len({o[1:] for o in outs}) != 1:
                    raise ValueError(
                        f"{self.name}: {node.name} concatenates maps of "
                        f"different sizes {[o[1:] for o in outs]}")
                out = (sum(o[0] for o in outs),) + outs[0][1:]
                steps.append(Join(node.name, tuple(paths), (c, h, w), out))
                (c, h, w), reads = out, -1
            elif isinstance(node, (Flatten, DenseSpec)) and not top:
                raise ValueError(f"{self.name}: {type(node).__name__} inside "
                                 "a branch path")
            else:
                raise ValueError(f"{self.name}: unknown node {node!r}")
        close()
        return tuple(steps), (c, h, w)


@dataclass(frozen=True)
class LayerGraph:
    """A CNN: a conv body (units, stand-alone pools and LRNs, branches
    joined by a concat), then Flatten, then a dense head."""

    name: str
    in_shape: tuple  # (C, H, W)
    nodes: tuple  # ConvSpec | ReLU | PoolSpec | LRN | Branches | Flatten | DenseSpec

    def body(self) -> tuple:
        """The parsed conv body: `ConvUnit`s, `Step`s and `Join`s in program
        order (validates the topology)."""
        return self._parsed[0]

    def units(self) -> tuple:
        """Every `ConvUnit`, in program order."""
        return self._parsed[1]

    def joins(self) -> tuple:
        """((name, unit indices), ...) for each `Branches`, in program order."""
        out = []

        def units_in(steps) -> list:
            idx = []
            for st in steps:
                if isinstance(st, ConvUnit):
                    idx.append(st.index)
                elif isinstance(st, Join):
                    pos = len(out)
                    out.append(None)
                    inner = [i for path in st.paths for i in units_in(path)]
                    out[pos] = (st.name, tuple(inner))
                    idx += inner
            return idx

        units_in(self.body())
        return tuple(out)

    def head(self) -> tuple:
        """The dense head: tuple[DenseSpec, ...] after the Flatten."""
        return self._parsed[2]

    def feature_shape(self) -> tuple:
        """(C, H, W) leaving the conv body (what Flatten sees)."""
        return self._parsed[3]

    def flat_dim(self) -> int:
        c, h, w = self.feature_shape()
        return c * h * w

    def n_classes(self) -> int:
        return self.head()[-1].d_out

    def signature(self) -> tuple:
        """Hashable structural identity (plan-cache key material): two graphs
        with the same shapes and node parameters share compiled programs."""
        return (tuple(self.in_shape), tuple(_node_sig(n) for n in self.nodes))

    @cached_property
    def _parsed(self):
        cut = next((i for i, n in enumerate(self.nodes)
                    if isinstance(n, Flatten)), None)
        if cut is None:
            body_nodes, head_nodes = self.nodes, ()
        else:
            body_nodes, head_nodes = self.nodes[:cut], self.nodes[cut + 1:]
        for node in head_nodes:
            if not isinstance(node, DenseSpec):
                raise ValueError(
                    f"{self.name}: only DenseSpec may follow Flatten, got {node}")
        parser = _Parser(self.name)
        body, shape = parser.chain(body_nodes, tuple(self.in_shape), -1,
                                   top=True)
        if cut is None or not head_nodes:
            raise ValueError(f"{self.name}: graph needs Flatten + a dense head")
        return body, tuple(parser.units), tuple(head_nodes), shape


# ---------------------------------------------------------------------------
# Weight plumbing (the one flat_weights helper — shared by planner + executor)
# ---------------------------------------------------------------------------


def graph_weights(params) -> tuple:
    """Normalize a params dict to (conv_weights, dense_weights) flat lists.

    Accepts both the graph-native layout {"conv": [...], "dense": [...]} and
    the legacy VGG layout {"stages": [[w, ...], ...], "fc1": w, "fc2": w}.
    This is the single zip seam `validate_plan` and `run_plan` share — the
    length/shape checks live in `validate_plan`, the walk in the executor."""
    if "stages" in params:
        return ([w for convs in params["stages"] for w in convs],
                [params["fc1"], params["fc2"]])
    return list(params["conv"]), list(params["dense"])


def weight_shapes(graph: LayerGraph) -> tuple:
    """((conv weight shapes), (dense weight shapes)) implied by the graph."""
    conv_shapes = []
    for u in graph.units():
        conv_shapes.append((u.conv.c_out, u.in_shape[0], u.conv.k, u.conv.k))
    d_in = graph.flat_dim()
    dense_shapes = []
    for spec in graph.head():
        dense_shapes.append((d_in, spec.d_out))
        d_in = spec.d_out
    return tuple(conv_shapes), tuple(dense_shapes)


def init_graph(key, graph: LayerGraph, dtype=None):
    """Fan-in-scaled random params for a graph, in the graph-native layout."""
    import jax
    import jax.numpy as jnp

    dtype = dtype or jnp.float32
    conv_shapes, dense_shapes = weight_shapes(graph)
    keys = iter(jax.random.split(key, len(conv_shapes) + len(dense_shapes)))
    conv = [jax.random.normal(next(keys), s, dtype) * (s[1] * s[2] * s[3]) ** -0.5
            for s in conv_shapes]
    dense = [jax.random.normal(next(keys), s, dtype) * s[0] ** -0.5
             for s in dense_shapes]
    return {"conv": conv, "dense": dense}
