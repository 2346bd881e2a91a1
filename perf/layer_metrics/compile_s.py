"""Start-up: seconds JAX spent building programs before the window (close to
zero once the persistent compile cache holds them)."""


def read(spans, counters, trace, run):
    return run["compile_before"]["build_s"]
