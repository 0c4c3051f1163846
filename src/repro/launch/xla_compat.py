"""Compiled-artifact introspection.

``Compiled.cost_analysis()`` returns a ``dict`` of metric -> value, or
``None`` (or raises) on backends without cost-analysis support.
:func:`xla_cost_analysis` turns all of these into one dict so callers
can do ``xla_cost_analysis(compiled).get("flops", 0.0)`` unconditionally.
"""
from __future__ import annotations

from typing import Any, Dict


def xla_cost_analysis(compiled: Any) -> Dict[str, float]:
    """``compiled.cost_analysis()`` as a fresh dict; {} when the backend
    offers no cost analysis."""
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return {}
    return dict(ca) if ca else {}
