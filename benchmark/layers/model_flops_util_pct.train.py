"""Model-FLOP utilisation: the forward pass's convolution and matrix-product
FLOPs per step (from shapes) times three, over the steady steps' mean wall,
over chips times peak. No recomputation is counted."""

NAME = "model_flops_util_pct.train"
UNIT = "%"
LAYER = "kernels"
MOVES = "train_records_per_s_per_chip"
SOURCE = "host_clock"


def read(run):
    if not run.walls:
        return None
    step_flops = 3 * sum(c.flops for c in run.forward_costs())
    step_s = sum(run.walls) / len(run.walls)
    return 100.0 * step_flops / step_s / (run.chips * run.peaks.flops_per_s)
