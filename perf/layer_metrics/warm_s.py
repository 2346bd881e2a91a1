"""Start-up: seconds of warm-up, compilation included."""


def read(spans, counters, trace, run):
    return run["warm_s"]
