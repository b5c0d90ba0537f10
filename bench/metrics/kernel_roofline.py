"""kernel_roofline: the least time (bench/flops.py) of the layers whose
program node has device time in the traced window, summed over the
window's batches, as a share of those nodes' device time (their kernels by
name, their ops by named scope). A layer whose node the trace does not
attribute, such as one that runs on XLA's own ops, leaves both sides, and
so do pools and ops outside every node."""

from bench import flops, trace_reduce


def read(run):
    nodes = (run.trace or {}).get("node_device_s")
    if not nodes or not run.batches:
        return None
    device = {trace_reduce.node_id(k): s for k, s in nodes.items()}
    least = flops.least_time_by_node(run.layers, run.batches, run.peak)
    hit = [n for n in least if device.get(n)]
    if not hit:
        return None
    return 100.0 * sum(least[n] for n in hit) / sum(device[n] for n in hit)
