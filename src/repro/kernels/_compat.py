"""Normalization of ``compiled.cost_analysis()``.

:func:`first_cost_analysis` is the one place that turns whatever the
compiled object reports into a plain dict (``{}`` when nothing).
"""

from __future__ import annotations


def first_cost_analysis(compiled) -> dict:
    """Normalized ``compiled.cost_analysis()``: the (first device's) cost
    dict, or ``{}`` when the backend reports nothing.  Exceptions from the
    underlying call propagate — callers decide whether costs are optional."""
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else None
    return dict(cost) if cost else {}


__all__ = ["first_cost_analysis"]
