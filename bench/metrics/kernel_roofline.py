"""kernel_roofline: the conv layers' least time (bench/flops.py) summed
over the batches dispatched in the traced window -- `conv_roofline`'s
numerator -- as a share of the device time of the ops that belong to the
conv-kind nodes (conv2d, separable, inverted_residual) by their kernel
names or named scopes. Pools, the dense head and ops outside every node
stay out of the denominator."""

from bench import flops

CONV_KINDS = ("conv2d", "separable", "inverted_residual")


def read(run):
    nodes = (run.trace or {}).get("node_device_s")
    if not nodes or not run.batches:
        return None
    conv_s = sum(s for n, s in nodes.items()
                 if n.split(":", 1)[0] in CONV_KINDS)
    if not conv_s:
        return None
    least = sum(n * flops.least_time_s(run.layers, b, run.peak)
                for b, n in run.batches.items())
    return 100.0 * least / conv_s
