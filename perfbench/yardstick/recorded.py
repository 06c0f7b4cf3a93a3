"""Per-round seconds of one program span, and nothing where the program
does not record that span at all (a program older than the span)."""

from __future__ import annotations

from perfbench.yardstick.spans import in_rounds


def span_in_rounds(ctx: dict, name: str) -> float | None:
    """:func:`in_rounds` of the spans called ``name``; ``None`` where the
    run recorded none of them, in the rounds or outside."""
    if not any(s[0] == name for s in ctx["spans"]):
        return None
    return in_rounds(ctx, (name,))
