"""The telemetry ``compile`` record's seconds: compile (or load from the
persistent cache) plus the first dispatch of the train step."""

NAME = "compile_first_dispatch_s"
UNIT = "s"
LAYER = "jitted train step"
MOVES = "setup_s"
SOURCE = "program_span"


def read(run):
    secs = [r["seconds"] for r in run.records if r.get("type") == "compile"]
    return sum(secs) if secs else None
