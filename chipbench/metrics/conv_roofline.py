"""Share of the conv units' roofline: over every batch served in the traced
stretch, each conv layer's least time at the chip's peaks (dense-equivalent
FLOPs and f32 bytes of its real images, `cnn.roofline_s`), divided by the
device time of every op of the conv units (`trace.in_conv_unit`: all but
the dense head's matmuls and the asynchronous copies' markers)."""
from chipbench import cnn


def read(run):
    tr = run.trace
    if tr is None or run.peaks is None or tr["conv_s"] <= 0:
        return None
    batches = run.batches_between(tr["host_t0"], tr["host_t1"])
    if not batches:
        return None
    least = sum(cnn.roofline_s(run.cell.cfg, n, run.peaks) for n in batches)
    return 100.0 * least / tr["conv_s"]
