"""The whole step's share of the chip's peak: 2 x dense MACs of every conv
and dense layer per image, times the images whose logits reached the host
in the traced stretch, over the stretch's length x chips x peak FLOP/s."""
from chipbench import cnn


def read(run):
    tr = run.trace
    if tr is None or run.peaks is None or tr["window_s"] <= 0:
        return None
    images = len(run.done_between(tr["host_t0"], tr["host_t1"]))
    flops = 2 * cnn.macs_per_image(run.cell.cfg) * images
    return 100.0 * flops / (tr["window_s"] * run.cell.chips * run.peaks["flops"])
