"""From the profiler's ``.xplane.pb`` to numbers: device busy seconds, the
device operations that took most time, and the longest idle gaps named by
what the host was doing.

The traced slice is what lies between the two host annotations the harness
writes, ``perf_trace_start`` (which carries the wall clock, so that the
program's spans can be laid on the trace's clock) and ``perf_trace_end``.
A device is a plane named ``/device:TPU:<n>``; its ``XLA Ops`` line holds
one event for each operation that ran, its ``XLA Modules`` line one for each
jitted program. An operation is named ``<jit name>/<op>`` after the program
whose event holds it. Busy time is the union of the operation intervals, so
a ``while`` and the fusions of its body are not counted twice there; in the
list of operations each is given with its own duration, body included.
"""

from __future__ import annotations

import bisect
import glob
import gzip
import os

START, END = "perf_trace_start", "perf_trace_end"
DEVICE_PREFIX = "/device:TPU:"


def find_xplane(trace_dir: str) -> str | None:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def _union(intervals: list) -> list:
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _short(op: str) -> str:
    # "%fusion.19 = f32[...] fusion(...)" → "fusion.19"
    return op.split(" ", 1)[0].lstrip("%")


def reduce(path: str, host_spans: list = (), wall_ns_at_start=None,
           top: int = 10) -> dict | None:
    """``host_spans``: ``(name, depth, wall_start_s, wall_end_s)`` of the
    program's spans and the client's requests. Returns None where the trace
    holds no annotated slice."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    marks = {}
    devices = {}
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            lines = {ln.name: ln for ln in plane.lines}
            if "XLA Ops" in lines:
                devices[plane.name] = lines
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in (START, END):
                    marks[e.name] = e
    if START not in marks or END not in marks:
        return None
    t0 = marks[START].start_ns
    t1 = marks[END].start_ns
    wall = dict(marks[START].stats).get("wall_ns", wall_ns_at_start)
    busy_all, ops = [], {}
    busy_s = []
    for lines in devices.values():
        mods = sorted((m.start_ns, m.start_ns + m.duration_ns,
                       m.name.split("(", 1)[0])
                      for m in lines["XLA Modules"].events) \
            if "XLA Modules" in lines else []
        starts = [m[0] for m in mods]
        mine = []
        for e in lines["XLA Ops"].events:
            a, b = max(e.start_ns, t0), min(e.start_ns + e.duration_ns, t1)
            if b <= a:
                continue
            mine.append((a, b))
            i = bisect.bisect_right(starts, e.start_ns) - 1
            prog = mods[i][2] if i >= 0 and e.start_ns < mods[i][1] else "?"
            name = f"{prog}/{_short(e.name)}"
            ops[name] = ops.get(name, 0.0) + (b - a) / 1e9
        busy_s.append(sum(b - a for a, b in _union(mine)) / 1e9)
        busy_all.extend(mine)
    # a gap: no operation running on any device
    gaps, at = [], t0
    for a, b in _union(busy_all) + [[t1, t1]]:
        if a > at:
            gaps.append((at, a))
        at = max(at, b)
    gaps.sort(key=lambda g: g[0] - g[1])

    def doing(t_ns: float) -> str:
        if wall is None:
            return "unaligned"
        t = (int(wall) + (t_ns - t0)) / 1e9
        best = None
        for name, depth, a, b in host_spans:
            if a <= t < b and (best is None or depth > best[1]):
                best = (name, depth)
        return best[0] if best else "idle"

    return {
        "window_s": (t1 - t0) / 1e9,
        "devices": len(devices),
        "busy_s": sum(busy_s) / len(busy_s) if busy_s else 0.0,
        "device_ops": [[n, s] for n, s in sorted(
            ops.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[doing((a + b) / 2), (b - a) / 1e9]
                      for a, b in gaps[:top]],
    }
