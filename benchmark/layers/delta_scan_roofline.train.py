"""Share of its roofline that the gated delta rule reaches.

Least time: three times the forward's (the backward needs each product's two
transposes, as everywhere), which is the larger of the rule's FLOPs over the
chip's peak and of its least bytes over its bandwidth, both from the
configuration's ``delta_cost`` (a layer and step), times the linear-attention
layers. ``delta_cost`` counts the chunked form's products (K K^T and Q K^T
inside the chunks, the triangular solve, the chunks' states and outputs) and
the least bytes whatever implements it: q, k and v in the compute dtype, log
alpha and beta in float32, o out in float32, each once. No recomputation is
counted. Measured time: device time a step of the ops in the scope
``delta_scan``, forward and backward. A program without the scope, or a
configuration without ``delta_cost``, gives nothing.
"""

from benchmark.lib import scope_times

NAME = "delta_scan_roofline.train"
UNIT = "%"
LAYER = "kernels"
MOVES = "train_records_per_s_per_chip"
SOURCE = "device_trace"
DELTA_SCOPES = ("delta_proj", "delta_conv", "delta_scan")


def read(run):
    cost = getattr(run.forward, "delta_cost", None)
    taken = scope_times.scope_ms(run, "delta_scan", DELTA_SCOPES)
    if cost is None or taken is None:
        return None
    flops, nbytes = cost()
    layers = sum(kind == "linear_attention" for kind in run.forward.layer_kinds)
    compute = layers * flops / run.peaks.flops_per_s
    memory = layers * nbytes / run.peaks.hbm_bytes_per_s
    least_ms = 3 * max(compute, memory) * 1e3
    run.log(delta_scan_roofline_bound="compute" if compute >= memory
            else "memory", delta_scan_least_ms_per_step=least_ms)
    return 100.0 * least_ms / taken
