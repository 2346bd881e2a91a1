"""Front door: the client's median less the median duration of the query's
root trace (``utils/tracing.traced_query``) — HTTP parsing, the response
cache, rendering the Prom JSON and the socket."""
from measure import median


def read(spans, counters, trace, run):
    root = median(e["duration_ms"] for e in spans if e.get("spans"))
    client = median(run["latencies_ms"])
    return None if root is None or client is None else client - root
