"""Device time a step of the ops that hold none of the step parts' names
(``lib/step_parts.PARTS``): the coverage of the tracing itself. The input's
copies (``copy-done``) are expected here and nothing else; the log's
``longest_unowned_ops`` names what is."""

from benchmark.lib import step_parts

NAME = "unowned_ms.train"
UNIT = "ms"
LAYER = "device"
MOVES = "train_records_per_s_per_chip"
SOURCE = "device_trace"


def read(run):
    return step_parts.part_ms(run, (step_parts.UNOWNED,))
