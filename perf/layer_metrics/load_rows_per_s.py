"""WAL + native append: rows ingested over the ingest seconds of the load."""


def read(spans, counters, trace, run):
    return run["load"]["rows"] / run["load"]["ingest_s"]
