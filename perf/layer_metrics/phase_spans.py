"""Span arithmetic shared by the phase readers (not a metric: no entry in
``BENCHMARK.json`` names it). The readers beside it import it as
``layer_metrics.phase_spans``: ``perf/`` is on the path, and a directory
without an ``__init__`` is a namespace package.

A phase is a span the mesh engine opens under ``mesh-execute``
(``parallel/mesh_engine.py:execute_lowered_many``): ``mesh-lookup``,
``decode`` (children ``batch-read``, ``batch-stack``), ``mesh-group``,
``mesh-pad``, ``mesh-place``, ``mesh-dispatch``, ``mesh-fetch``,
``mesh-assemble``. A batch-cache hit opens none of the first five.
"""
from measure import median

ENGINE = "mesh-execute"


def total_ms(entry: dict, names: tuple, inside: str | None = None):
    """Summed whole duration (children included) of the entry's spans with
    one of ``names`` — restricted, where ``inside`` is given, to those with
    an ancestor of that name; None where it has none."""
    spans = entry.get("spans") or []
    by_id = {s["span_id"]: s for s in spans}

    def within(s) -> bool:
        while (s := by_id.get(s["parent_id"])) is not None:
            if s["name"] == inside:
                return True
        return False

    mine = [s["duration_ms"] for s in spans
            if s["name"] in names and (inside is None or within(s))]
    return sum(mine) if mine else None


def phase_median(entries: list, names: tuple, inside: str | None = ENGINE):
    """Median over the recorded queries that have the phase of its summed
    duration over a query's extents."""
    return median(v for e in entries
                  if (v := total_ms(e, names, inside)) is not None)
