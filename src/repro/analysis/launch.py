"""Launch-geometry contracts: verify a resolved Pallas launch descriptor
(`repro.kernels.tiles.ConvLaunch` / `BsrLaunch`) WITHOUT compiling it.

The descriptors store every geometry field the ops execute with (the ops
read their block sizes back out of the record — one derivation); the checks
here re-derive every expectation from the primitive extents and flag any
disagreement. That is the division of labor that makes corruption
representable: a mutated descriptor field cannot silently re-derive itself
back to consistency.

Checks (DESIGN.md §12):
  RPA101  grid x block tiles each output element exactly once — pads are
          the minimal fill to a block multiple, block counts match, output
          spatial dims match the conv arithmetic.
  RPA102  every index-map gather stays in bounds — the last conv window
          must fit the (already spatially padded) input; block sizes are
          positive so no zero-size BlockSpec divides anything.
  RPA103  the launch's modeled scoped VMEM (`vmem_bytes`: padded,
          double-buffered blocks + accumulator + temporaries) fits
          `VMEM_LIMIT_BYTES`, the limit every kernel asks Mosaic for. At
          the default blocks only a huge spatial map can exceed it — that
          is a warn (it needs a row-band kernel); blocks above the default
          come from an explicit request — that is an error.
  RPA104  int8 kernels accumulate in int32 and carry per-output-channel
          scales (fp32 accumulation would silently saturate; a single
          tensor scale loses the per-channel dynamic range the quantizer
          calibrated).
  RPA105  a fused pool epilogue tiles the conv output exactly (the kernel
          floors, so a remainder would silently truncate rows/cols).
"""
from __future__ import annotations

from repro.analysis.diagnostics import DiagnosticSink
from repro.kernels.tiles import (
    VMEM_LIMIT_BYTES,
    BsrLaunch,
    ConvLaunch,
    resolve_conv_tile,
)


def _pad_ok(extent: int, pad: int, block: int, n_blocks: int) -> bool:
    """pad is the minimal fill of `extent` to a multiple of `block`, and
    `n_blocks` covers it exactly once."""
    return (block > 0 and 0 <= pad < block
            and (extent + pad) % block == 0
            and n_blocks * block == extent + pad)


def check_conv_launch(L: ConvLaunch, sink: DiagnosticSink, *,
                      layer: int | None = None, kind: str = "",
                      impl: str = "") -> None:
    loc = dict(layer=layer, kind=kind, impl=impl)
    is_int8 = L.dtype_bytes == 1 or L.kernel.endswith("_int8")

    # --- RPA102: positive extents / in-bounds gathers --------------------
    if min(L.block_c, L.block_o, L.batch, L.stride) <= 0 or \
            min(L.c, L.h, L.w, L.o, L.kh, L.kw) <= 0:
        sink.add("RPA102",
                 f"{L.kernel}: non-positive launch dimension "
                 f"(c={L.c} h={L.h} w={L.w} o={L.o} k={L.kh}x{L.kw} "
                 f"stride={L.stride} block_c={L.block_c} block_o={L.block_o} "
                 f"batch={L.batch})",
                 hint="every extent and block size must be >= 1", **loc)
        return  # the remaining arithmetic would divide by zero
    oh = (L.h - L.kh) // L.stride + 1
    ow = (L.w - L.kw) // L.stride + 1
    if oh < 1 or ow < 1:
        sink.add("RPA102",
                 f"{L.kernel}: kernel {L.kh}x{L.kw} does not fit the padded "
                 f"{L.h}x{L.w} input (conv output {oh}x{ow})",
                 hint="the ConvSpec padding must leave >= one window", **loc)
        return
    last_h = (oh - 1) * L.stride + L.kh
    last_w = (ow - 1) * L.stride + L.kw
    if last_h > L.h or last_w > L.w:
        sink.add("RPA102",
                 f"{L.kernel}: last window reads row {last_h}/col {last_w} "
                 f"of a {L.h}x{L.w} input (index map out of bounds)", **loc)

    # --- RPA101: grid x block covers the output exactly once -------------
    if not _pad_ok(L.c, L.c_pad, L.block_c, L.n_cb):
        sink.add("RPA101",
                 f"{L.kernel}: channel blocking c={L.c}+{L.c_pad} pad != "
                 f"{L.n_cb} x block_c={L.block_c}",
                 hint="n_cb must equal ceil(c / block_c) with minimal pad",
                 **loc)
    if not _pad_ok(L.o, L.o_pad, L.block_o, L.n_ob):
        sink.add("RPA101",
                 f"{L.kernel}: output blocking o={L.o}+{L.o_pad} pad != "
                 f"{L.n_ob} x block_o={L.block_o}",
                 hint="n_ob must equal ceil(o / block_o) with minimal pad",
                 **loc)
    if (L.oh, L.ow) != (oh, ow):
        sink.add("RPA101",
                 f"{L.kernel}: descriptor says conv output {L.oh}x{L.ow} but "
                 f"(h,w,k,stride)=({L.h},{L.w},{L.kh},{L.kw},{L.stride}) "
                 f"gives {oh}x{ow}",
                 hint="oh/ow must be (h - kh) // stride + 1", **loc)

    # --- RPA105: fused pool tiles the conv output exactly ----------------
    if L.pool:
        if L.pool < 0 or L.oh % L.pool or L.ow % L.pool:
            sink.add("RPA105",
                     f"{L.kernel}: pool {L.pool}x{L.pool} does not tile the "
                     f"{L.oh}x{L.ow} conv output exactly — the fused "
                     f"epilogue floors, silently truncating the remainder",
                     hint="run the unit unfused (conv + pool) instead", **loc)

    # --- RPA103: VMEM limit ----------------------------------------------
    if L.vmem_bytes > VMEM_LIMIT_BYTES:
        default_bc, default_bo = resolve_conv_tile(L.c, L.o)
        explicit = L.block_c > default_bc or L.block_o > default_bo
        sink.add("RPA103",
                 f"{L.kernel}: {L.vmem_bytes} B modeled VMEM "
                 f"(x {L.h}x{L.w}x{L.block_c} + acc {L.oh}x{L.ow}x"
                 f"{L.block_o}) exceeds the {VMEM_LIMIT_BYTES} B VMEM "
                 f"limit",
                 severity="error" if explicit else "warn",
                 hint=("shrink the requested tile" if explicit else
                       "spatial map too large for a full-map tile (needs a "
                       "row-band kernel)"),
                 **loc)

    # --- RPA104: int8 accumulation / scale contract ----------------------
    if is_int8:
        if L.acc_dtype != "int32":
            sink.add("RPA104",
                     f"{L.kernel}: int8 operands accumulate in "
                     f"{L.acc_dtype!r}, must be int32",
                     hint="int8 MACs overflow anything narrower", **loc)
        if L.weight_scales != "per_output_channel":
            sink.add("RPA104",
                     f"{L.kernel}: int8 weight scales are "
                     f"{L.weight_scales!r}, must be per_output_channel",
                     hint="quantize_weight calibrates one scale per output "
                          "channel", **loc)


def check_bsr_launch(L: BsrLaunch, sink: DiagnosticSink, *,
                     layer: int | None = None, kind: str = "",
                     impl: str = "") -> None:
    loc = dict(layer=layer, kind=kind, impl=impl)
    is_int8 = L.dtype_bytes == 1 or L.kernel.endswith("_int8")

    # --- RPA102: positive extents ----------------------------------------
    if min(L.bt, L.bf, L.bd) <= 0 or min(L.t, L.f, L.d) <= 0:
        sink.add("RPA102",
                 f"{L.kernel}: non-positive launch dimension "
                 f"(t={L.t} f={L.f} d={L.d} blocks {L.bt}x{L.bf}x{L.bd})",
                 hint="every extent and block size must be >= 1", **loc)
        return

    # --- RPA101: blocking covers each operand exactly once ---------------
    for name, ext, pad, blk, n in (("t", L.t, L.t_pad, L.bt, L.nt),
                                   ("f", L.f, L.f_pad, L.bf, L.nf),
                                   ("d", L.d, L.d_pad, L.bd, L.nd)):
        if not _pad_ok(ext, pad, blk, n):
            sink.add("RPA101",
                     f"{L.kernel}: {name}={ext}+{pad} pad != {n} x "
                     f"block={blk} — the grid would tile dimension "
                     f"{name!r} {'short' if n * blk < ext + pad else 'over'}",
                     hint=f"n{name} must equal ceil({name} / b{name}) with "
                          "minimal pad", **loc)

    # --- RPA103: VMEM limit (defaults are tiny; over the limit => explicit)
    if L.vmem_bytes > VMEM_LIMIT_BYTES:
        sink.add("RPA103",
                 f"{L.kernel}: {L.vmem_bytes} B modeled VMEM "
                 f"({L.bt}x{L.bf} + {L.bf}x{L.bd} operands + {L.bt}x{L.bd} "
                 f"acc) exceeds the {VMEM_LIMIT_BYTES} B VMEM limit",
                 hint="shrink the requested (bt, bf, bd)", **loc)

    # --- RPA104: int8 contract -------------------------------------------
    if is_int8:
        if L.acc_dtype != "int32":
            sink.add("RPA104",
                     f"{L.kernel}: int8 operands accumulate in "
                     f"{L.acc_dtype!r}, must be int32", **loc)
        if L.weight_scales != "per_output_channel":
            sink.add("RPA104",
                     f"{L.kernel}: int8 weight scales are "
                     f"{L.weight_scales!r}, must be per_output_channel",
                     **loc)


def check_launch(L, sink: DiagnosticSink, *, layer: int | None = None,
                 kind: str = "", impl: str = "") -> None:
    """Dispatch on descriptor type (the registry's `unit_launch` returns
    either family, or None for impls with no Pallas grid)."""
    if L is None:
        return
    if isinstance(L, ConvLaunch):
        check_conv_launch(L, sink, layer=layer, kind=kind, impl=impl)
    elif isinstance(L, BsrLaunch):
        check_bsr_launch(L, sink, layer=layer, kind=kind, impl=impl)
    else:
        raise TypeError(f"unknown launch descriptor {type(L).__name__}")
