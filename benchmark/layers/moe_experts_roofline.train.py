"""Share of their roofline that the experts' grouped products reach.

Least time: three times the forward's (each backward product does the
forward's work on two of its three tensors), which is the larger of the three
grouped products' FLOPs over ``moe_pairs_local`` routed pairs over the chip's
peak and of their least bytes over its bandwidth (the configuration's
``experts_cost``); the pairs are the counter's own mean over the TRACED
steps' records (the routing drifts during a run, so the window's mean would
set other steps' pairs against these steps' time), not the expectation.
Measured time: device time a step of the ops in the scope ``moe_experts`` (the
grouped products and the gate), forward and backward, recomputation
included."""

from benchmark.lib import scopes

NAME = "moe_experts_roofline.train"
UNIT = "%"
LAYER = "kernels"
MOVES = "train_records_per_s_per_chip"
SOURCE = "device_trace"


def read(run):
    cost = getattr(run.forward, "experts_cost", None)
    taken = scopes.scope_ms(run, "moe_experts")
    pairs = scopes.counter_mean(run, "moe_pairs_local", traced=True)
    if cost is None or taken is None or pairs is None:
        return None
    flops, nbytes = cost(pairs)
    compute = flops / run.peaks.flops_per_s
    memory = nbytes / run.peaks.hbm_bytes_per_s
    least_ms = 3 * max(compute, memory) * 1e3
    run.log(moe_experts_roofline_bound="compute" if compute >= memory else "memory",
            moe_experts_least_ms_per_step=least_ms,
            moe_pairs_local_traced_steps_mean=pairs)
    return 100.0 * least_ms / taken
