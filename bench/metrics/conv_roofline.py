"""conv_roofline: the conv layers' least time (bench/flops.py) summed over
the batches dispatched in the traced window, as a share of the device's
busy time in that window. The batches count every row the device ran,
padding included."""

from bench import flops


def read(run):
    if not run.trace or not run.trace["busy_s"] or not run.batches:
        return None
    least = sum(n * flops.least_time_s(run.layers, b, run.peak)
                for b, n in run.batches.items())
    return 100.0 * least / run.trace["busy_s"]
