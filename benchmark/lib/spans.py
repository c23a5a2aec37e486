"""Span aggregates out of step records. A step record's ``spans`` maps a
span's nested path to ``{"n": samples, "s": seconds}`` drained since the
previous record; a program without the span leaves the name out."""

from __future__ import annotations

from typing import Dict, List, Sequence


def aggregates(steps: Sequence[dict], name: str) -> List[Dict[str, float]]:
    """The ``{"n", "s"}`` of ``name`` in every step record that has it."""
    return [r["spans"][name] for r in steps if name in (r.get("spans") or {})]


def seconds(steps: Sequence[dict], name: str) -> List[float]:
    """Seconds of ``name`` per step record that has it."""
    return [a["s"] for a in aggregates(steps, name)]
