"""Share of their roofline that the step's convolutions reach.

Least time: the larger of the forward pass's convolution and matrix-product
FLOPs over the chip's peak and of their least bytes over its bandwidth, both
from shapes (``benchmark/lib/flops.py``), times three: each of the two
backward convolutions does the forward's work on two of its three tensors and
writes the third. Measured time: the device time of the trace's
``convolution fusion`` category (where XLA puts both) per step.
"""

from benchmark.lib import flops

NAME = "conv_roofline.train"
UNIT = "%"
LAYER = "kernels"
MOVES = "train_records_per_s_per_chip"
SOURCE = "device_trace"

CATEGORY = "convolution fusion"


def read(run):
    t = run.trace
    if t is None or not t.category_s.get(CATEGORY):
        return None
    least, bound = flops.least_seconds(
        run.forward_costs(), run.peaks.flops_per_s, run.peaks.hbm_bytes_per_s)
    run.log(conv_roofline_bound=bound, conv_least_ms_per_step=3 * least * 1e3)
    return 100.0 * 3 * least / (t.category_s[CATEGORY] / t.steps)
