"""Per-round reductions of the program's host spans (``obsv/trace.py``)
over the harness-timed rounds of a traced run."""

from __future__ import annotations


def in_rounds(ctx: dict, names) -> float | None:
    """Seconds per round of the spans called any of ``names`` that start
    inside the traced rounds; ``None`` where nothing was recorded."""
    rounds = ctx["rounds"]
    if not rounds or not ctx["spans"]:
        return None
    lo, hi = rounds[0][0], rounds[-1][1]
    total = sum(dur for name, t0, dur in ctx["spans"]
                if name in names and lo <= t0 <= hi)
    return total / len(rounds)


def round_wall(ctx: dict) -> float | None:
    """Harness-timed seconds per traced round."""
    rounds = ctx["rounds"]
    if not rounds:
        return None
    return sum(b - a for a, b in rounds) / len(rounds)
