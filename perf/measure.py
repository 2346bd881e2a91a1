"""Arithmetic shared by the per-layer readers: the program's counters from
its own Prometheus text, and the program's spans from its flight recorder.

A reader gets ``(spans, counters, trace, run)``:

- ``spans``: the flight recorder's entries written inside the measured
  window, oldest first, as the program records them (``duration_ms``,
  ``stats``, ``spans``: a list of ``{name, depth, duration_ms, span_id,
  parent_id, tags}``). Empty unless the run was traced. Only a query that
  the front door ran alone is recorded with spans; members of a batch leave
  none.
- ``counters``: ``{"window": (before, after), "slice": (before, after) or
  None}``, each a ``{series: value}`` dict of every counter and gauge.
- ``trace``: ``trace_reduce.reduce``'s result for the traced slice, or None.
- ``run``: what the harness itself measured (see ``run.py``).
"""

from __future__ import annotations

import statistics


def parse_prometheus(text: str) -> dict:
    out = {}
    for line in text.splitlines():
        if line and line[0] != "#":
            series, _, value = line.rpartition(" ")
            try:
                out[series] = float(value)
            except ValueError:
                pass
    return out


def delta(pair, family: str, **tags) -> float:
    """Growth of every series of ``family`` whose tags include ``tags``."""
    before, after = pair
    want = [f'{k}="{v}"' for k, v in tags.items()]
    total = 0.0
    for series, value in after.items():
        name, _, rest = series.partition("{")
        if name == family and all(w in rest for w in want):
            total += value - before.get(series, 0.0)
    return total


def share(hit: float, miss: float):
    return 100.0 * hit / (hit + miss) if hit + miss > 0 else None


def median(values):
    values = list(values)
    return statistics.median(values) if values else None


def self_ms(entry: dict, names: tuple) -> float | None:
    """Summed self time (own duration less its children's) of the entry's
    spans with one of ``names``; None where it has none."""
    spans = entry.get("spans") or []
    mine = [s for s in spans if s["name"] in names]
    if not mine:
        return None
    total = 0.0
    for s in mine:
        kids = sum(c["duration_ms"] for c in spans
                   if c["parent_id"] == s["span_id"])
        total += s["duration_ms"] - kids
    return total


def mesh_dispatches(pair) -> float:
    return delta(pair, "filodb_mesh_dispatch_total")
