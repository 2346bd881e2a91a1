"""Kernels: the least time the chip could take to read the samples each
executed query scanned — one pass over them as the engine places them, an
int32 time offset and an f32 value a sample, at the chip's peak HBM
bandwidth (``peaks.json``) — over the device seconds measured in the slice.
HBM-bound: a window function does a handful of operations a sample. The
samples a dispatch scans are the mean over the queries recorded in the
window, so list this metric only for cells whose queries are all recorded
(one client) and alike; a batch's members leave no stats."""
from measure import mesh_dispatches

BYTES_PER_SAMPLE = 4 + 4


def read(spans, counters, trace, run):
    if not trace or not counters.get("slice") or not trace["busy_s"]:
        return None
    scanned = [n for e in spans
               if (n := (e.get("stats") or {}).get("samples_scanned"))]
    if not scanned:
        return None
    least_s = sum(scanned) / len(scanned) * BYTES_PER_SAMPLE \
        * mesh_dispatches(counters["slice"]) \
        / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / trace["busy_s"]
