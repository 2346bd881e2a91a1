"""Device: share of the traced slice in which no operation ran on it."""


def read(spans, counters, trace, run):
    if not trace or not trace["busy_s"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
