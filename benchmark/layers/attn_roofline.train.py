"""Share of their roofline that the attention kernels reach.

Least time: three times the forward's, which is the larger of the FLOPs of
Q.K^T and P.V over the VISIBLE pairs of every layer over the chip's peak and
of their least bytes (q, k, v and the output once each) over its bandwidth,
both from the configuration's ``attention_cost``; the backward pass needs
four products of the same size (dP, dV, dQ, dK), so 3 x. No masked tile and
no recomputation is counted. Measured time: device time a step of the ops in
the scopes ``attn_window`` and ``attn_full``, forward and backward.
"""

from benchmark.lib import scopes

NAME = "attn_roofline.train"
UNIT = "%"
LAYER = "kernels"
MOVES = "train_records_per_s_per_chip"
SOURCE = "device_trace"


def read(run):
    cost = getattr(run.forward, "attention_cost", None)
    taken = [scopes.scope_ms(run, s) for s in ("attn_window", "attn_full")]
    if cost is None or all(t is None for t in taken):
        return None
    flops = nbytes = 0.0
    for kind in run.forward.layer_kinds:
        f, b = cost(kind)
        flops, nbytes = flops + f, nbytes + b
    compute = flops / run.peaks.flops_per_s
    memory = nbytes / run.peaks.hbm_bytes_per_s
    least_ms = 3 * max(compute, memory) * 1e3
    run.log(attn_roofline_bound="compute" if compute >= memory else "memory",
            attn_least_ms_per_step=least_ms)
    return 100.0 * least_ms / sum(t or 0.0 for t in taken)
