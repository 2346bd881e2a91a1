"""Mesh engine: series a request answered in the window had read ONE AT A
TIME — growth of ``filodb_batch_rows_total{path="fallback"}`` over the
window (``query/engine/batch.py``: the rows of a build that no native
shard core's one call a shard filled, each through ``read_samples``) over
the requests answered. A histogram series is such a row today
(``NativeShardCore.batch_count`` declines it), so a dashboard of three
panels over 100 series, one build an extent on the first panel and two
batch-cache hits, reads about 100 × extents ÷ 3. It falls to 0 when the
native fill learns the histogram column. Nothing where the program has no
such counter."""
from measure import delta


def read(spans, counters, trace, run):
    window = counters["window"]
    answered = len(run.get("latencies_ms") or ())
    if not answered or not any(
            s.startswith("filodb_batch_rows_total") for s in window[1]):
        return None
    return delta(window, "filodb_batch_rows_total", path="fallback") \
        / answered
