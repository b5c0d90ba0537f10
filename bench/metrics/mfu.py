"""mfu: images answered in the traced window times the least FLOPs of an
image (every conv and dense layer, bench/flops.py), over the window's
seconds times the chip's bf16 peak: the whole step's share of the peak."""

from bench import flops


def read(run):
    if not run.trace or not run.completed:
        return None
    work = run.completed * flops.image_flops_min(run.layers)
    return 100.0 * work / (run.trace["window_s"]
                           * run.peak["bf16_flops"])
