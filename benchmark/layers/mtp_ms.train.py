"""Device time a step of every op under the scope ``mtp``: the
multi-token-prediction module whole (the shifted embedding, its two norms and
``eh_proj``, its block's attention and experts, its norm, the head's second
use and the second cross-entropy), forward and backward, recomputation
included. The name is looked for alone, so the module's inner scopes count
towards it (they count towards their own metrics too)."""

from benchmark.lib import scope_times

NAME = "mtp_ms.train"
UNIT = "ms"
LAYER = "multi-token prediction"
MOVES = "train_records_per_s_per_chip"
SOURCE = "device_trace"


def read(run):
    return scope_times.scope_ms(run, "mtp", ("mtp",))
