"""Mean of the ``prefetch`` span over the window: convert plus the
host-to-device hop of one batch. Step records carry span aggregates
``{name: {"n", "s"}}``, so the mean is the sum of ``s`` over the sum of ``n``."""

NAME = "prefetch_place_ms.train"
UNIT = "ms"
LAYER = "dataset + _prefetch"
MOVES = "train_records_per_s_per_chip"
SOURCE = "program_span"


def read(run):
    spans = [r["spans"]["prefetch"] for r in run.steps
             if "prefetch" in (r.get("spans") or {})]
    n = sum(s["n"] for s in spans)
    return sum(s["s"] for s in spans) / n * 1e3 if n else None
