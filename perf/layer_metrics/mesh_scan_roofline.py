"""Kernels: the least time the chip could take to read the samples the mesh
dispatches of the traced slice scanned — one pass over them as the engine
places them, an int32 time offset and an f32 value a sample, at the chip's
peak HBM bandwidth (``peaks.json``) — over the device seconds measured in
the slice. HBM-bound, as ``scan_roofline`` beside it, but it reads the
program's own count (``filodb_mesh_samples_scanned_total``, moved once a
dispatch) and no recorded query's stats, so it holds where requests run in
batches and differ in size. It counts the work, not a program: whatever
runs, the samples of the placed batch are read at least once. Nothing where
the program has no such counter."""
from measure import delta

BYTES_PER_SAMPLE = 4 + 4


def read(spans, counters, trace, run):
    if not trace or not counters.get("slice") or not trace["busy_s"]:
        return None
    scanned = delta(counters["slice"], "filodb_mesh_samples_scanned_total")
    if not scanned:
        return None
    least_s = scanned * BYTES_PER_SAMPLE / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / trace["busy_s"]
