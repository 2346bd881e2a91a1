"""Device: share of the traced slice in which the host sat in a
``mesh-fetch`` span, blocked on the device. Each span is laid on the wall
clock from its entry's ``t0_unix_ns`` and its own ``start_ms`` and clipped
to the slice (``run["slice_wall"]``). Beside ``100 - device_idle_share``
(the device busy) it says whether host and device ever overlap: where the
two are equal, the host does nothing while the device works."""


def read(spans, counters, trace, run):
    wall = run.get("slice_wall")
    if not wall or wall[1] <= wall[0]:
        return None
    a, b = wall
    held = []
    for e in spans:
        t0 = e.get("t0_unix_ns")
        for s in (e.get("spans") or []) if t0 is not None else []:
            if s["name"] == "mesh-fetch" and "start_ms" in s:
                s0 = t0 / 1e9 + s["start_ms"] / 1e3
                held.append((s0, s0 + s["duration_ms"] / 1e3))
    if not held:
        return None
    blocked, at = 0.0, a
    for s0, s1 in sorted(held):  # union, should two threads ever fetch
        s0, s1 = max(s0, at), min(s1, b)
        if s1 > s0:
            blocked += s1 - s0
            at = s1
    return 100.0 * blocked / (b - a)
