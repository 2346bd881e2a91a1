"""Start-up: programs JAX built inside the measured window. Should be 0."""


def read(spans, counters, trace, run):
    return float(run["compile_in_window"]["programs_built"])
