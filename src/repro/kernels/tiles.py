"""TileConfig: kernel tile geometry as a first-class, searched quantity.

Every Pallas kernel in this repo tiles its operands — the ECR/PECR conv
grids over (block_c input-channel, block_o output-channel) blocks, the BSR
matmul over (bt, bf, bd) blocks — and until now every one of those sizes was
a hard-coded constant (`block_o=128` everywhere, BSR pinned at
`(8, 128, 128)`, `_pick_block_c` a static fp32-only heuristic). The paper's
own results say that is always wrong somewhere: which geometry wins is
shape- and occupancy-dependent (Figs 9/11), so geometry must be a *planned*
quantity like the impl choice itself.

This module is the single owner of that geometry:

- `TileConfig` — one frozen, hashable record of every tile knob (0 = "use
  the current default"), threaded from `obs.tilesearch` winners through
  `CalibrationDB` -> `plan_network` -> `LayerPlan.tile` -> `run_unit` ->
  the kernel ops. An all-zero TileConfig is falsy and means "defaults",
  so legacy `block_c`-only call paths stay bit-identical.
- `resolve_conv_tile` — THE (bc, bo) defaulting rule the ECR and PECR ops
  used to duplicate, now shared.
- `resolve_bsr_tile` — the (bt, bf, bd) rule for the BSR lowering, with the
  same contract.

Divisibility fallback contract: a requested tile dimension that does not
conform to the operand (larger than the dimension it tiles, or <= 0) falls
back to the CURRENT default for that dimension — never an error, and never
a silently different schedule than the default path would run. Dimensions
the requested tile *does* conform to are honored exactly; the ops pad the
operand up to a block multiple, so conforming means "no more than one
block of padding", the same rule the hand-fixed defaults satisfy. This is
also the rule `planner.occupancy_stat` and `channel_block_occupancy`
resolve through, so the measured statistic and the executed schedule can
never disagree about the block size (the geometry bug this file fixed).

Every block size is legal for Mosaic: the kernels take their operands in
blocked HBM layouts (channels split as (n_cb, ..., bc), see
`kernels/ecr_conv/kernel.py`), so the last two dimensions of every block are
the array's own. What a block costs in VMEM is another matter: Mosaic pads
the minor dimension of a VMEM tile to the 128-lane width and the one above it
to the sublane tile, so a block narrower than 128 channels takes the VMEM of
a full lane width. `ConvLaunch.vmem_bytes` / `BsrLaunch.vmem_bytes` model the
padded, double-buffered need of one launch; RPA103 holds it to
`VMEM_LIMIT_BYTES`, the scoped limit every kernel asks Mosaic for.

Stdlib-only (no jax import): sits below kernels/, graph/ and obs/ in the
import graph so every layer can share it.
"""
from __future__ import annotations

from dataclasses import dataclass

LANES = 128  # minor dimension of a VMEM tile
SUBLANES = 8  # 32-bit rows of a VMEM tile (packed types hold 4 // bytes x more)
# Scoped VMEM every kernel asks Mosaic for (`kernels.platform`) and the
# ceiling RPA103 holds a launch's modeled need to. A v5e core has 128 MiB of
# VMEM and a 16 MiB default scoped limit, which VGG's 96x96 first-stage tiles
# exceed (~24 MiB for the fused conv1_2 + pool).
VMEM_LIMIT_BYTES = 64 * 1024 * 1024
# Mosaic's internal scratch on top of the modeled buffers: the 6x6,
# 512-channel stage-5 conv needs up to ~40 KiB more than its double-buffered
# blocks and accumulator
VMEM_SLACK_BYTES = 256 * 1024


def _ceil_to(n: int, m: int) -> int:
    return -(-n // m) * m


def _slab_bytes(rows: int, cols: int, dtype_bytes: int) -> int:
    """VMEM bytes of a (rows, cols) slab as Mosaic tiles it: cols padded to
    the lane width, rows to the sublane tile of the dtype."""
    sub = SUBLANES * 4 // dtype_bytes
    return _ceil_to(rows, sub) * _ceil_to(cols, LANES) * dtype_bytes


@dataclass(frozen=True)
class TileConfig:
    """One kernel-geometry choice. 0 anywhere = the current default.

    block_c / block_o: ECR/PECR conv input- and output-channel block sizes.
    bt / bf / bd:      BSR matmul row- / reduction- / column-block sizes
                       (weight output-channel blocks, K-tap blocks, patch
                       blocks in the conv lowering).
    An all-zero config is falsy ("all defaults") so `tile or fallback`
    composes with the legacy block_c-only plumbing.
    """

    block_c: int = 0
    block_o: int = 0
    bt: int = 0
    bf: int = 0
    bd: int = 0

    def key(self) -> tuple:
        """The hashable 5-tuple the CalibrationDB / PlanKey key on."""
        return (self.block_c, self.block_o, self.bt, self.bf, self.bd)

    def __bool__(self) -> bool:
        return any(self.key())

    @classmethod
    def from_key(cls, key) -> "TileConfig":
        bc, bo, bt, bf, bd = (int(v) for v in key)
        return cls(block_c=bc, block_o=bo, bt=bt, bf=bf, bd=bd)


DEFAULT_TILE = TileConfig()


def as_tile(tile=None, block_c: int = 0) -> TileConfig:
    """Normalize the (tile, legacy block_c) pair every threaded call site
    carries: an explicit non-default tile wins, else block_c lifts into one."""
    if tile:
        return tile
    return TileConfig(block_c=int(block_c)) if block_c else DEFAULT_TILE


def resolve_block_c(c: int, tile: TileConfig | None = None) -> int:
    """The ECR/PECR channel-block size actually run for a C-channel input.

    A requested block_c is honored iff 0 < block_c <= max(8, c) (at most one
    block of channel padding — the same bound the default satisfies);
    anything else falls back to the default: one lane width of channels, or
    one block holding every channel of a narrower layer. The default does
    not shrink for large maps: a narrower block takes the same lane-padded
    VMEM, so a map whose 128-channel tile misses the limit needs a row-band
    kernel instead (RPA103 warns)."""
    bc = tile.block_c if tile is not None else 0
    if bc <= 0 or bc > max(8, c):
        bc = min(LANES, max(8, c))
    return bc


def resolve_conv_tile(c: int, o: int, tile: TileConfig | None = None) -> tuple:
    """(bc, bo) for the ECR / PECR conv ops — the one defaulting rule both
    `ecr_conv` and `fused_conv_pool` resolve through (they used to carry
    duplicated copies). bo is clamped into [.., max(8, o)] like the
    hand-fixed default always was; a non-positive request means default."""
    bc = resolve_block_c(c, tile)
    bo = tile.block_o if tile is not None and tile.block_o > 0 else LANES
    bo = min(bo, max(8, o))
    return bc, bo


@dataclass(frozen=True)
class ConvLaunch:
    """Resolved launch geometry of one ECR / PECR conv kernel call.

    Built by `ecr_conv_launch` / `conv_pool_launch` (and their int8 siblings)
    from the SAME `resolve_conv_tile` resolution the op then executes with —
    the ops read their block sizes and paddings back out of this record, so
    the geometry the static checker (`repro.analysis.launch`) sees is by
    construction the geometry the Pallas grid runs. All fields are stored
    (not derived on access) so a corrupted descriptor is representable: the
    checker re-derives every expectation from the primitive extents and
    flags any disagreement.

    c/h/w are the input extents as the kernel sees them (h/w already carry
    the ConvSpec's spatial padding; c is pre-channel-pad), `pool` is the
    fused pool window (0 = unfused), `acc_dtype`/`weight_scales` record the
    accumulation/scale contract the int8 kernels must satisfy.
    """

    kernel: str  # "ecr_conv" | "conv_pool" | "ecr_conv_int8"
    batch: int
    c: int
    h: int
    w: int
    o: int
    kh: int
    kw: int
    stride: int
    pool: int  # fused pool window (0 = no fused epilogue)
    block_c: int
    block_o: int
    c_pad: int  # channel padding up to a block_c multiple
    o_pad: int  # output-channel padding up to a block_o multiple
    n_cb: int  # input-channel blocks = schedule length
    n_ob: int  # output-channel blocks = grid dim 0
    oh: int  # conv output spatial dims (pre-pool)
    ow: int
    dtype_bytes: int
    acc_dtype: str = "float32"
    weight_scales: str = "none"  # "none" | "per_output_channel"

    @property
    def grid(self) -> tuple:
        """(n_ob, batch, n_cb) — the batched Pallas grid."""
        return (self.n_ob, self.batch, self.n_cb)

    @property
    def vmem_bytes(self) -> int:
        """Modeled scoped VMEM of one launch, an upper bound of what Mosaic
        allocates (tests/test_tpu_compile.py compiles the VGG-19 launches
        with the limit set to this number): the double-buffered x, weight
        and output blocks, the fp32/int32 accumulator scratch plus one
        accumulator-sized dot result and one patch per tap, and for a fused
        pool the ReLU'd and reshaped conv tile of the epilogue."""
        db = self.dtype_bytes
        poh, pow_ = ((self.oh // self.pool, self.ow // self.pool)
                     if self.pool else (self.oh, self.ow))
        x = self.h * _slab_bytes(self.w, self.block_c, db)
        w = self.kh * self.kw * _slab_bytes(self.block_c, self.block_o, db)
        out = poh * _slab_bytes(pow_, self.block_o, 4)
        acc = _slab_bytes(self.oh * self.ow, self.block_o, 4)
        patch = _slab_bytes(self.oh * self.ow, self.block_c, db)
        epilogue = 2 * acc if self.pool else 0
        return (2 * (x + w + out) + 2 * acc + patch + epilogue
                + VMEM_SLACK_BYTES)


@dataclass(frozen=True)
class BsrLaunch:
    """Resolved launch geometry of one BSR matmul kernel call: a (t, f)
    sparse left operand against (f, d), tiled (bt, bf, bd). Built by
    `sparse_weights.conv.bsr_conv_launch` (t = output channels, f = K taps,
    d = patches) from the same `resolve_bsr_tile` call the op executes with;
    same stored-fields-vs-rederived-expectations contract as `ConvLaunch`."""

    kernel: str  # "bsr_matmul" | "bsr_matmul_int8"
    t: int
    f: int
    d: int
    bt: int
    bf: int
    bd: int
    t_pad: int
    f_pad: int
    d_pad: int
    nt: int  # row blocks (per-row-block (ids, cnt) schedules)
    nf: int  # reduction blocks = schedule width
    nd: int  # column blocks
    dtype_bytes: int
    acc_dtype: str = "float32"
    weight_scales: str = "none"

    @property
    def grid(self) -> tuple:
        """(nt, nd, nf) — reduction innermost, like the kernel."""
        return (self.nt, self.nd, self.nf)

    @property
    def vmem_bytes(self) -> int:
        """Modeled scoped VMEM of one launch (same padding and double
        buffering as `ConvLaunch.vmem_bytes`): both operand blocks and the
        output block twice, the accumulator scratch and one dot result."""
        db = self.dtype_bytes
        operands = (_slab_bytes(self.bt, self.bf, db)
                    + _slab_bytes(self.bf, self.bd, db))
        acc = _slab_bytes(self.bt, self.bd, 4)
        return 2 * (operands + acc) + 2 * acc + VMEM_SLACK_BYTES


def resolve_bsr_tile(o: int, k_taps: int, p: int,
                     tile: TileConfig | None = None) -> tuple:
    """(bt, bf, bd) for the BSR conv lowering of an (O, K) weight against
    (K, P) patches. Defaults are `sparse_weights.format.weight_block` for
    (bt, bf) — the geometry the pruner aligned its zeros to — and the
    largest power of two <= min(128, P) for bd. Each requested dimension is
    honored iff 0 < dim <= max(8, its operand extent); a non-conforming
    dimension falls back to ITS default independently (a good bf request
    must not be discarded because bd was silly)."""
    from repro.sparse_weights.format import _pow2_le, weight_block

    dbt, dbf = weight_block(o, k_taps)
    dbd = _pow2_le(min(128, max(1, p)))
    if tile is None:
        return dbt, dbf, dbd
    bt = tile.bt if 0 < tile.bt <= max(8, o) else dbt
    bf = tile.bf if 0 < tile.bf <= max(8, k_taps) else dbf
    bd = tile.bd if 0 < tile.bd <= max(8, p) else dbd
    return bt, bf, bd
