"""Kernels: ``mesh_scan_roofline`` for a batch that has buckets — the least
time the chip could take to read the SCALAR ROWS the device programs of the
traced slice evaluated (a histogram sample is one row a bucket once the
engine has flattened ``[P, S, B]`` to ``[P·B, S]``), one pass over them as
they are placed, an int32 time offset and an f32 value a row sample, at the
chip's peak HBM bandwidth (``peaks.json``), over the device seconds
measured in the slice. HBM-bound. It reads the program's own count,
``filodb_mesh_bucket_samples_scanned_total``: samples × buckets, moved once
a program that evaluates the placed rows (an eval-cache miss of the split
lane, a fused dispatch) and NOT where a dispatch finds the evaluation
cached and runs the group reduce alone, as two of a dashboard's three
panels do. So it counts the work and not a program: whatever later
implements the scan reads the placed rows at least once where this moves,
and more cache hits leave it where it is. Nothing where the program has no
such counter."""
from measure import delta

BYTES_PER_SAMPLE = 4 + 4


def read(spans, counters, trace, run):
    if not trace or not counters.get("slice") or not trace["busy_s"]:
        return None
    scanned = delta(counters["slice"],
                    "filodb_mesh_bucket_samples_scanned_total")
    if not scanned:
        return None
    least_s = scanned * BYTES_PER_SAMPLE / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / trace["busy_s"]
