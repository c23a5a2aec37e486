"""Share of its roofline that the state-space scan reaches.

Least time: three times the forward's (the backward needs each product's two
transposes, as everywhere), which is the larger of the scan's FLOPs over the
chip's peak and of its least bytes over its bandwidth, both from the
configuration's ``ssd_cost`` (a layer and step), times the state-space layers.
``ssd_cost`` counts what the equations need whatever computes them: the four
products over the visible pairs inside a chunk and the chunks' states; x in
the compute dtype, y out in float32, B and C in the compute dtype, the step
size in float32, each once. No recomputation is counted. Measured time:
device time a step of the ops in the scope ``ssm_scan``, forward and backward.
"""

from benchmark.lib import scope_times

NAME = "ssm_scan_roofline.train"
UNIT = "%"
LAYER = "kernels"
MOVES = "train_records_per_s_per_chip"
SOURCE = "device_trace"


def read(run):
    cost = getattr(run.forward, "ssd_cost", None)
    taken = scope_times.scope_ms(run, "ssm_scan")
    if cost is None or taken is None:
        return None
    flops, nbytes = cost()
    layers = sum(kind == "mamba" for kind in run.forward.layer_kinds)
    compute = layers * flops / run.peaks.flops_per_s
    memory = layers * nbytes / run.peaks.hbm_bytes_per_s
    least_ms = 3 * max(compute, memory) * 1e3
    run.log(ssm_scan_roofline_bound="compute" if compute >= memory else "memory",
            ssm_scan_least_ms_per_step=least_ms)
    return 100.0 * least_ms / taken
