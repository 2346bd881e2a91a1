"""Tag index: span ``mesh-lookup`` — every shard's ``lookup_partitions``,
the partition fetch and on-demand paging."""
from layer_metrics.phase_spans import phase_median


def read(spans, counters, trace, run):
    return phase_median(spans, ("mesh-lookup",))
