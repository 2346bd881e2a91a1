"""HTTP server exposing the Prometheus API + cluster admin routes.

Counterpart of reference ``FiloHttpServer.scala`` route composition
(endpoints per ``doc/http_api.md``):

- ``GET /promql/{dataset}/api/v1/query_range?query=&start=&end=&step=``
- ``GET/POST /promql/{dataset}/api/v1/query?query=&time=``
- ``GET /promql/{dataset}/api/v1/series?match[]=&start=&end=``
- ``GET /promql/{dataset}/api/v1/labels``
- ``GET /promql/{dataset}/api/v1/label/{name}/values``
- ``GET /api/v1/cluster/{dataset}/status`` (shard statuses)
- ``GET /__health``, ``GET /metrics`` (Prometheus exposition)

Two server fronts share one ``HttpDispatcher`` (all routing/rendering):

- ``FiloHttpServer`` — threaded stdlib server (one thread per connection);
  queries run on the request thread through the ``QueryBatcher``.
- ``filodb_tpu.http.fastserver.FastHttpServer`` — single-threaded selector
  event loop that coalesces every hot query parsed in one readiness pass
  into a single ``query_range_many`` engine batch (the serving-side analog
  of inference micro-batching, and the default standalone front end).
"""

from __future__ import annotations

import json
import logging
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from urllib.parse import parse_qs, unquote, urlparse

from filodb_tpu.coordinator.query_service import QueryService
from filodb_tpu.http import promjson
from filodb_tpu.promql.parser import ParseError, TimeStepParams, parse_query
# imported for the side effect of registering the federation + ODP metric
# families at boot, so /metrics exposes them even before the first
# federated query (scrape-breadth test relies on this)
from filodb_tpu.query import federation as _federation  # noqa: F401
from filodb_tpu.query.model import QueryLimitExceeded
from filodb_tpu.utils.governor import QueryRejected
from filodb_tpu.utils.metrics import Histogram, render_prometheus
from filodb_tpu.utils.resilience import DeadlineExceeded

log = logging.getLogger(__name__)

JSON_CT = "application/json"

# rendering a query result into Prom JSON happens after the query's trace
# has closed, so it has no span; both fronts observe it here instead
render_seconds = Histogram(
    "filodb_http_render_seconds",
    help="query result to Prom JSON text, a hot query request")


def retry_after_headers(after_s: float | None = None) -> dict:
    """``Retry-After`` for 503/429 sheds, shared by both server fronts.
    The header carries whole seconds (RFC 9110), never less than 1."""
    if after_s is None:
        from filodb_tpu.utils.governor import config as governor_config
        after_s = governor_config().retry_after_s
    return {"Retry-After": str(max(1, int(round(float(after_s)))))}


class ResponseCache:
    """Rendered-response cache for hot query endpoints, invalidated by the
    dataset's ingest data_version (the query-frontend pattern: Prometheus
    deployments put an equivalent cache — Thanos/Cortex query-frontend — in
    front of the reference; here it is built in). Keys are the RESOLVED
    query parameters, so an instant query defaulting to server time never
    aliases across seconds. A version bump (any ingest into any shard of
    the dataset) orphans every entry for that service.

    Layering with the extent result cache
    (``filodb_tpu/query/result_cache.py``): this cache sits OUTSIDE it and
    memoizes fully-rendered JSON bytes — a hit here skips parse, execute,
    and render, but only for byte-identical requests against an unchanged
    dataset (idle servers, repeated panels). Under live ingest the version
    stamp bumps every row and this cache contributes nothing; the extent
    cache below still answers the immutable bulk of each query and
    recomputes only the mutable head."""

    def __init__(self, cap: int = 1024):
        from collections import OrderedDict
        self.cap = cap
        self.hits = 0
        self.misses = 0
        self._lru: "OrderedDict[tuple, tuple[int, bytes]]" = OrderedDict()
        # the threaded front mutates from concurrent handler threads
        self._lock = threading.Lock()

    def get(self, key: tuple, version: int) -> bytes | None:
        with self._lock:
            entry = self._lru.get(key)
            if entry is None or entry[0] != version:
                self.misses += 1
                return None
            self._lru.move_to_end(key)
            self.hits += 1
            return entry[1]

    def put(self, key: tuple, version: int, body: bytes) -> None:
        with self._lock:
            while len(self._lru) >= self.cap:
                self._lru.popitem(last=False)
            self._lru[key] = (version, body)


def service_version(svc) -> int | None:
    """Cache-invalidation stamp: total ingest progress across the
    dataset's shards (bumps on every applied write).

    Returns ``None`` when the service does not host every shard of the
    dataset locally — in that case some query results come from remote
    members whose ingest never bumps these local versions, so the stamp
    cannot witness staleness and the response cache must be bypassed."""
    shards = svc.memstore.shards_for(svc.dataset)
    if len(shards) < getattr(svc, "num_shards", 1):
        return None
    return sum(s.data_version for s in shards)


def response_cache_key(svc, kind: str, params: tuple) -> tuple:
    """Canonical response-cache key, shared by both fronts so entries are
    keyed identically regardless of which server parsed the request.
    ``params`` is (query, start, step, end) for ranges; instant queries
    key on (query, resolved_time) — extra positions are ignored.

    Services are identified by their monotonic construction ``serial``,
    never ``id()``: a new service allocated at a freed service's address
    would alias its cache entries (stale responses for a different
    dataset/epoch)."""
    serial = getattr(svc, "serial", None)
    if serial is None:  # not `or`: a legitimate serial of 0 must not
        serial = id(svc)  # fall back to an aliasable address

    if kind == "instant":
        return (serial, "instant", params[0], params[1])
    return (serial, "range", *params)


def parse_time(s: str) -> float:
    """Unix seconds (float) or RFC3339 (Grafana sends either)."""
    try:
        return float(s)
    except ValueError:
        import datetime as dt
        return dt.datetime.fromisoformat(s.replace("Z", "+00:00")) \
            .timestamp()


class HttpDispatcher:
    """All route handling, shared by the threaded and event-loop fronts.

    ``handle`` never raises: every outcome is a ``(status, headers, body)``
    triple, with errors rendered as Prom-style JSON error envelopes."""

    def __init__(self, app: "FiloHttpServer"):
        self.app = app

    # -- entry --

    def handle(self, command: str, path: str, raw: bytes = b"",
               content_type: str = "") -> tuple[int, dict, bytes]:
        try:
            url = urlparse(path)
            qs = parse_qs(url.query)
            parts = [p for p in url.path.split("/") if p]
            if command == "POST":
                if parts[-1:] == ["read"]:
                    return self._remote_read(parts, raw)
                if raw and "x-www-form-urlencoded" in content_type:
                    for k, v in parse_qs(raw.decode()).items():
                        qs.setdefault(k, v)
            return self._dispatch(parts, qs)
        except (ParseError, ValueError) as e:
            return self._json(400, promjson.error_json(str(e)))
        except QueryLimitExceeded as e:
            return self._json(422, promjson.error_json(str(e), "query_limit"))
        except QueryRejected as e:
            # shed by the admission gate (local or a remote peer's): 503 +
            # Retry-After with a DISTINCT errorType from a timeout, so
            # clients back off instead of hammering an overloaded node
            return self._json(503,
                              promjson.error_json(str(e), "unavailable"),
                              headers=retry_after_headers(e.retry_after_s))
        except DeadlineExceeded as e:
            return self._json(503, promjson.error_json(str(e), "timeout"),
                              headers=retry_after_headers())
        except Exception as e:  # pragma: no cover
            log.exception("request failed")
            return self._json(500, promjson.error_json(str(e), "internal"))

    # -- helpers --

    @staticmethod
    def _json(code: int, payload,
              headers: dict | None = None) -> tuple[int, dict, bytes]:
        body = payload.encode() if isinstance(payload, str) \
            else json.dumps(payload).encode()
        h = {"Content-Type": JSON_CT}
        if headers:
            h.update(headers)
        return code, h, body

    # -- routing --

    def _dispatch(self, parts: list[str], qs: dict):
        if parts == ["__health"]:
            return self._json(200, {"status": "healthy"})
        if parts == ["metrics"]:
            return (200, {"Content-Type": "text/plain; version=0.0.4"},
                    render_prometheus().encode())
        if len(parts) >= 4 and parts[0] == "promql" \
                and parts[2] == "api" and parts[3] == "v1":
            dataset = parts[1]
            svc = self.app.services.get(dataset)
            if svc is None:
                return self._json(404, promjson.error_json(
                    f"unknown dataset {dataset}"))
            return self._prom_api(svc, parts[4:], qs)
        if len(parts) >= 3 and parts[0] == "api" and parts[1] == "v1" \
                and parts[2] == "cluster":
            return self._cluster_api(parts[3:], qs)
        if parts == ["api", "v1", "rules"]:
            # top-level Prom-compat view aggregating every dataset's groups
            groups = []
            for mgr in self._rule_managers().values():
                groups.extend(mgr.rules_snapshot())
            return self._json(200, {"status": "success",
                                    "data": {"groups": groups}})
        if parts == ["api", "v1", "alerts"]:
            alerts = []
            for mgr in self._rule_managers().values():
                alerts.extend(mgr.alerts_snapshot())
            return self._json(200, {"status": "success",
                                    "data": {"alerts": alerts}})
        if parts == ["api", "v1", "status", "tsdb"]:
            return self._status_tsdb(qs)
        if parts == ["api", "v1", "status", "ingest"]:
            return self._status_ingest(qs)
        if parts == ["api", "v1", "status", "tiers"]:
            return self._status_tiers(qs)
        if parts == ["api", "v1", "status", "mesh"]:
            return self._status_mesh(qs)
        return self._json(404, promjson.error_json("not found", "not_found"))

    def _rule_managers(self) -> dict:
        return getattr(self.app, "rule_managers", None) or {}

    # -- status introspection --

    def _status_datasets(self, qs: dict) -> dict:
        """Services filtered by an optional ``?dataset=`` param."""
        want = qs.get("dataset", [None])[0]
        return {name: svc for name, svc in self.app.services.items()
                if want is None or name == want}

    def _status_tsdb(self, qs: dict):
        """Prometheus-shaped TSDB status: per-shard head/memory stats plus
        top-k series cardinality by metric name (from the shard-key
        cardinality trees) and by label name (distinct values from the
        part-key indexes)."""
        try:
            k = max(1, int(qs.get("topk", ["10"])[0]))
        except ValueError:
            k = 10
        data = {}
        for name, svc in self._status_datasets(qs).items():
            by_metric: dict[str, dict] = {}
            by_label: dict[str, int] = {}
            shards = []
            num_series = 0
            for sh in svc.memstore.shards_for(name):
                # the cardinality tree root counts every live series,
                # including ones created inside the native ingest core
                # that never touch the python key map
                root = sh.cardinality.cardinality([])
                num_series += root.active_ts
                shards.append({
                    "shard": sh.shard_num,
                    "numSeries": root.active_ts,
                    "totalSeries": root.total_ts,
                    "indexRamBytes": sh.index.ram_bytes,
                    "encodedBytes": sh.stats.encoded_bytes.value,
                    "samplesEncoded": sh.stats.samples_encoded.value,
                    "chunksFlushed": sh.stats.chunks_flushed.value,
                    "partitionsEvicted":
                        sh.stats.partitions_evicted.value,
                })
                tracker = sh.cardinality
                # tree walk ws -> ns -> metric; aggregate metric counts
                # across prefixes and shards, Prometheus-status style
                for ws in tracker.top_k([], 1000):
                    for ns in tracker.top_k([ws.name], 1000):
                        for mc in tracker.top_k([ws.name, ns.name], 1000):
                            agg = by_metric.setdefault(
                                mc.name, {"active": 0, "total": 0})
                            agg["active"] += mc.active_ts
                            agg["total"] += mc.total_ts
                for label in sh.label_names():
                    by_label[label] = max(by_label.get(label, 0),
                                          len(sh.label_values(label)))
            top_metrics = sorted(by_metric.items(),
                                 key=lambda kv: -kv[1]["active"])[:k]
            top_labels = sorted(by_label.items(),
                                key=lambda kv: -kv[1])[:k]
            data[name] = {
                "headStats": {"numSeries": num_series,
                              "numShards": len(shards)},
                "shards": shards,
                "seriesCountByMetricName": [
                    {"name": m, "value": v["active"],
                     "totalValue": v["total"]} for m, v in top_metrics],
                "labelValueCountByLabelName": [
                    {"name": label, "value": v} for label, v in top_labels],
            }
        return self._json(200, {"status": "success", "data": data})

    def _status_tiers(self, qs: dict):
        """Per-dataset retention-tier map: which tiers exist (memstore /
        downsample / objectstore), their time floors, and per-tier
        series/bytes — the introspection face of query federation."""
        from filodb_tpu.query import federation
        data = {name: federation.tier_status(name, svc)
                for name, svc in self._status_datasets(qs).items()}
        return self._json(200, {"status": "success", "data": data})

    def _status_mesh(self, qs: dict):
        """Multi-process mesh runtime status: per-worker mesh slice,
        device count, descriptor-cache occupancy, last collective
        latency (``filo-cli meshstat``). Datasets without a runtime
        report ``multiproc: false`` with single-process engine info."""
        data = {}
        for name, svc in self._status_datasets(qs).items():
            rt = getattr(svc, "mesh_cluster", None)
            if rt is not None:
                entry = dict(rt.status())
                entry["multiproc"] = True
            else:
                entry = {"multiproc": False}
            eng = getattr(svc, "mesh_engine", None)
            if eng is not None:
                entry["engine"] = {"hits": eng.hits, "misses": eng.misses,
                                   "batch_cache": len(eng._batch_cache),
                                   "programs": len(eng._fns)}
            data[name] = entry
        return self._json(200, {"status": "success", "data": data})

    def _status_ingest(self, qs: dict):
        """Per-shard ingest freshness: lag vs wall clock, replay-log
        offsets, checkpoint watermarks, write-behind queue state, rules
        watermark lag, and the ingest-side slow-operation ring."""
        import time as _time
        from filodb_tpu.core.store import objectstore as objstore
        from filodb_tpu.utils import metrics as metrics_mod
        from filodb_tpu.utils.tracing import slow_ingest
        cluster = getattr(self.app, "cluster", None)
        now = _time.time()
        try:
            limit = int(qs.get("limit", ["20"])[0])
        except ValueError:
            limit = 20
        data = {"datasets": {}}
        for name, svc in self._status_datasets(qs).items():
            shards = []
            for sh in svc.memstore.shards_for(name):
                lag = (None if sh.max_ingested_ts < 0
                       else max(0.0, now - sh.max_ingested_ts / 1000.0))
                entry = {
                    "shard": sh.shard_num,
                    "maxIngestedTs": sh.max_ingested_ts,
                    "ingestLagSeconds": lag,
                    "ingestedOffset": sh.latest_offset,
                    "groupWatermarks": list(sh.group_watermarks),
                }
                log_ = (cluster.logs.get((name, sh.shard_num))
                        if cluster is not None else None)
                if log_ is not None:
                    entry["logLatestOffset"] = log_.latest_offset
                    entry["offsetLag"] = log_.offset_lag(sh.latest_offset)
                    entry["checkpointLag"] = log_.offset_lag(
                        min(sh.group_watermarks, default=-1))
                shards.append(entry)
            data["datasets"][name] = {"shards": shards}
        data["objectstore"] = {
            "queueDepth": objstore.QUEUE_DEPTH.value,
            "oldestTaskAgeSeconds": objstore._oldest_task_age(),
        }
        # gauges owned by objects this server can't reach (gateway sink,
        # rule groups) are read back from the registry by family name
        with metrics_mod._lock:
            fams = list(metrics_mod._registry.values())
        for m in fams:
            if m.name == "gateway_queue_depth" and m.value is not None:
                data["gatewayQueueDepth"] = m.value
            elif m.name == "filodb_rules_watermark_lag_seconds" \
                    and m.tags.get("group"):  # skip the untagged anchor
                data.setdefault("rulesWatermarkLagSeconds", {})[
                    m.tags["group"]] = m.value
        data["slowIngest"] = slow_ingest(limit)
        return self._json(200, {"status": "success", "data": data})

    # -- Prom API --

    @staticmethod
    def range_params(qs: dict) -> tuple[str, int, int, int]:
        """(query, start, step, end) for a query_range request."""
        return (qs["query"][0], int(parse_time(qs["start"][0])),
                int(float(qs.get("step", ["60"])[0])),
                int(parse_time(qs["end"][0])))

    @staticmethod
    def instant_params(qs: dict) -> tuple[str, int]:
        """(query, time) for an instant query request."""
        if "time" in qs:
            t = int(parse_time(qs["time"][0]))
        else:
            # Prometheus defaults instant queries to server time
            import time as _time
            t = int(_time.time())
        return qs["query"][0], t

    def _cached_query(self, svc: QueryService, kind: str, params: tuple,
                      full_stats: bool = False):
        """Hot query with the rendered-response cache around it."""
        cache = self.app.response_cache
        key = version = None
        if cache is not None:
            version = service_version(svc)
            if version is None:
                cache = None  # remote shards: stamp can't witness staleness
            else:
                key = response_cache_key(svc, kind, params)
                if full_stats:
                    # ?stats=all renders a different body — distinct entry
                    key = key + ("stats",)
                body = cache.get(key, version)
                if body is not None:
                    return 200, {"Content-Type": JSON_CT}, body
        r = self.app.batched(svc).query_range(*params)
        with render_seconds.time():
            rendered = promjson.matrix_json_str(r, full_stats=full_stats) \
                if kind == "range" \
                else promjson.vector_json_str(r, with_stats=full_stats)
        out = self._json(200, rendered)
        if cache is not None:
            cache.put(key, version, out[2])
        return out

    @staticmethod
    def _want_stats(qs: dict) -> bool:
        return qs.get("stats", [""])[0] == "all"

    def _prom_api(self, svc: QueryService, rest: list[str], qs: dict):
        if rest == ["query_range"]:
            params = self.range_params(qs)
            return self._cached_query(svc, "range", params,
                                      full_stats=self._want_stats(qs))
        if rest == ["query"]:
            query, t = self.instant_params(qs)
            return self._cached_query(svc, "instant", (query, t, 0, t),
                                      full_stats=self._want_stats(qs))
        if rest == ["series"]:
            matches = qs.get("match[]", [])
            start = int(parse_time(qs.get("start", ["0"])[0]))
            end = int(parse_time(qs.get("end", ["9999999999"])[0]))
            out = []
            for mtext in matches:
                plan = parse_query(mtext, TimeStepParams(start, 0, end))
                raw = getattr(plan, "raw", None)
                filters = raw.filters if raw is not None else ()
                for lm in svc.series(list(filters), start, end):
                    out.append({("__name__" if k == "_metric_" else k): v
                                for k, v in lm.items()})
            return self._json(200, {"status": "success", "data": out})
        if rest == ["labels"]:
            names = [("__name__" if n == "_metric_" else n)
                     for n in svc.memstore.label_names(svc.dataset)]
            return self._json(200, {"status": "success", "data": names})
        if len(rest) == 3 and rest[0] == "label" and rest[2] == "values":
            label = unquote(rest[1])
            if label == "__name__":
                label = "_metric_"
            vals = svc.memstore.label_values(svc.dataset, label)
            return self._json(200, {"status": "success", "data": vals})
        if rest == ["rules"]:
            mgr = self._rule_managers().get(svc.dataset)
            groups = mgr.rules_snapshot() if mgr is not None else []
            return self._json(200, {"status": "success",
                                    "data": {"groups": groups}})
        if rest == ["alerts"]:
            mgr = self._rule_managers().get(svc.dataset)
            alerts = mgr.alerts_snapshot() if mgr is not None else []
            return self._json(200, {"status": "success",
                                    "data": {"alerts": alerts}})
        if rest == ["debug", "trace"]:
            # span-traced execution (reference: Kamon spans around exec,
            # ExecPlan.scala:101 / startODPSpan — surfaced here as JSON
            # instead of a zipkin reporter). Force-samples this one query:
            # the active trace is joined by traced_query(), so remote
            # children ship their span trees back and they land here too.
            from filodb_tpu.utils.tracing import start_trace
            if "start" in qs:
                query, start, step, end = self.range_params(qs)
            else:
                query, t = self.instant_params(qs)
                start, step, end = t, 0, t
            with start_trace() as trace:
                r = svc.query_range(query, start, step, end)
            return self._json(200, {
                "status": "success",
                "data": {"spans": trace.as_dicts(),
                         "result_series": r.result.num_series,
                         "stats": {
                             "series_scanned": r.stats.series_scanned,
                             "samples_scanned": r.stats.samples_scanned,
                             "wall_time_s": r.stats.wall_time_s,
                         }}})
        if rest == ["debug", "slow_queries"]:
            # slow-query flight recorder: bounded ring of queries (and
            # traced operations) that exceeded slow_query_threshold_ms,
            # newest first, full span tree + stats when sampled
            from filodb_tpu.utils.tracing import slow_queries
            try:
                limit = int(qs.get("limit", ["0"])[0])
            except ValueError:
                limit = 0
            entries = [e for e in slow_queries()
                       if e.get("dataset") in (None, svc.dataset)]
            if limit > 0:
                entries = entries[:limit]
            return self._json(200, {"status": "success",
                                    "data": {"slow_queries": entries}})
        if rest == ["debug", "costmodel"]:
            # adaptive-planner introspection: per-site estimates with
            # warm state, calibration error, and recent predicted-vs-
            # actual pairs (served by `filo-cli coststats`)
            from filodb_tpu.query import cost_model
            model = cost_model.model_for(svc.dataset)
            snap = model.snapshot()
            try:
                limit = int(qs.get("limit", ["0"])[0])
            except ValueError:
                limit = 0
            if limit > 0:
                snap["estimates"] = snap["estimates"][:limit]
            return self._json(200, {"status": "success", "data": snap})
        return self._json(404, promjson.error_json("unknown endpoint"))

    def _remote_read(self, parts: list[str], body: bytes):
        """Prometheus remote-read (protobuf; reference remote-storage
        protocol endpoint in PrometheusApiRoute)."""
        from filodb_tpu.http import remote_read as rr
        if len(parts) < 2 or parts[0] != "promql":
            return self._json(404, promjson.error_json("not found"))
        svc = self.app.services.get(parts[1])
        if svc is None:
            return self._json(404, promjson.error_json(
                f"unknown dataset {parts[1]}"))
        data = rr.maybe_decompress(body)
        try:
            queries = rr.decode_read_request(data)
        except Exception:
            return self._json(501 if not rr.HAVE_SNAPPY else 400,
                              promjson.error_json(
                                  "could not decode read request "
                                  "(snappy unavailable?)"))
        results = []
        for q in queries:
            series = []
            for shard in svc.memstore.shards_for(svc.dataset):
                for pid in shard.lookup_partitions(
                        q["filters"], q["start_ms"], q["end_ms"]):
                    part = shard.partition(pid)
                    if part is None:
                        continue
                    ts, vals = part.read_samples(q["start_ms"], q["end_ms"])
                    import numpy as _np
                    if len(ts) and not isinstance(vals, _np.ndarray):
                        continue  # histograms not in remote-read v1
                    series.append((list(part.part_key.labels), ts, vals))
            results.append(series)
        payload = rr.maybe_compress(rr.encode_read_response(results))
        return (200, {"Content-Type": "application/x-protobuf",
                      "Content-Encoding":
                          "snappy" if rr.HAVE_SNAPPY else "identity"},
                payload)

    # -- cluster admin --

    def _cluster_api(self, rest: list[str], qs: dict):
        cluster = self.app.cluster
        if not rest:
            return self._json(200, {"status": "success",
                                    "data": list(self.app.services)})
        dataset = rest[0]
        if len(rest) == 2 and rest[1] in ("startshards", "stopshards") \
                and cluster is not None:
            # reference ClusterApiRoute start/stop shards commands
            from filodb_tpu.coordinator.shardmapper import (
                ShardEvent,
                ShardStatus,
            )
            shards = [int(s) for s in
                      qs.get("shards", [""])[0].split(",") if s]
            node = qs.get("node", [None])[0]
            sm = cluster.shard_managers.get(dataset)
            if sm is None:
                return self._json(404, promjson.error_json(
                    f"unknown dataset {dataset}"))
            done = []
            for shard in shards:
                if rest[1] == "stopshards":
                    owner = sm.mapper.node_for(shard)
                    if owner and owner in cluster.nodes:
                        cluster.nodes[owner].stop_shard(dataset, shard)
                        sm._publish(ShardEvent(shard, ShardStatus.STOPPED,
                                               None))
                        done.append(shard)
                else:
                    target = node or next(iter(cluster.nodes), None)
                    if target:
                        ev = ShardEvent(shard, ShardStatus.ASSIGNED, target)
                        sm._publish(ev)
                        cluster._on_event(dataset, ev)
                        done.append(shard)
            return self._json(200, {"status": "success", "data": done})
        if len(rest) == 2 and rest[1] == "status":
            if cluster is not None:
                data = cluster.shard_statuses(dataset)
            elif dataset in self.app.shard_maps:
                # member: serve the coordinator's state from the local
                # mirror (sequenced subscription with resync)
                data = self.app.shard_maps[dataset]().snapshot()
            else:
                svc = self.app.services.get(dataset)
                data = [{"shard": s.shard_num, "status": "active",
                         "numPartitions": s.num_partitions}
                        for s in svc.memstore.shards_for(dataset)] \
                    if svc else []
            return self._json(200, {"status": "success", "data": data})
        if len(rest) == 2 and rest[1] == "shardmap":
            return self._shardmap(dataset)
        if len(rest) == 2 and rest[1] == "migrate" and cluster is not None:
            try:
                shard = int(qs.get("shard", [""])[0])
            except ValueError:
                return self._json(400,
                                  promjson.error_json("shard must be an int"))
            dest = qs.get("dest", [""])[0]
            if not dest:
                return self._json(400, promjson.error_json("dest required"))
            import threading

            def _run():
                try:
                    cluster.migrate_shard(dataset, shard, dest)
                except Exception:
                    import logging
                    logging.getLogger(__name__).exception(
                        "migration of %s shard %d -> %s failed",
                        dataset, shard, dest)

            threading.Thread(target=_run, daemon=True,
                             name=f"migrate-{dataset}-{shard}").start()
            return self._json(200, {"status": "success",
                                    "data": {"dataset": dataset,
                                             "shard": shard, "dest": dest,
                                             "state": "started"}})
        return self._json(404, promjson.error_json("unknown cluster endpoint"))

    def _shardmap(self, dataset: str):
        """Shard → node/status/migration-phase map plus per-tenant
        cardinality-vs-quota usage (``filo-cli shardmap`` backend)."""
        cluster = self.app.cluster
        if cluster is not None:
            shards = cluster.shard_statuses(dataset)
            for entry in shards:
                mig = cluster.migrations.get((dataset, entry["shard"]))
                if mig is not None:
                    entry["migration"] = mig.snapshot()
                # leader covered offset + live follower watermarks: the
                # in-sync picture replicacheck/shardmap render
                owner = entry.get("node")
                node = cluster.nodes.get(owner) if owner else None
                if node is not None:
                    try:
                        entry["watermark"] = node.shard_offset(
                            dataset, entry["shard"])
                    except Exception:
                        pass
                for rep in entry.get("replicas", ()):
                    sy = cluster.replica_syncers.get(
                        (dataset, entry["shard"], rep["node"]))
                    if sy is not None:
                        rep["watermark"] = sy.applied
        elif dataset in self.app.shard_maps:
            shards = self.app.shard_maps[dataset]().snapshot()
        else:
            svc = self.app.services.get(dataset)
            shards = [{"shard": s.shard_num, "status": "active",
                       "node": None}
                      for s in svc.memstore.shards_for(dataset)] \
                if svc else []
        from filodb_tpu.utils.governor import config as gov_config
        svc = self.app.services.get(dataset)
        trackers = [s.cardinality for s in
                    svc.memstore.shards_for(dataset)] if svc else []
        tenants = []
        for tenant, tc in sorted(gov_config().tenants.items()):
            prefix = tenant.split("/")
            active = sum(t.cardinality(prefix).active_ts for t in trackers)
            tenants.append({
                "tenant": tenant,
                "active_series": active,
                "max_series": int(tc.get("max_series", 0) or 0),
                "max_inflight": int(tc.get("max_inflight", 0) or 0)})
        return self._json(200, {"status": "success",
                                "data": {"shards": shards,
                                         "tenants": tenants}})


class _ReusePortHTTPServer(ThreadingHTTPServer):
    """SO_REUSEPORT variant: N server processes bind the same port and the
    kernel load-balances connections across them — the multi-process
    serving plane (each worker is a log-tailing read replica), sidestepping
    the GIL the way the reference scales its Akka-HTTP dispatcher pool
    (``http/src/main/scala/filodb/http/FiloHttpServer.scala:23``)."""

    def server_bind(self):
        import socket
        self.socket.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEPORT, 1)
        super().server_bind()


class FiloHttpServer:
    def __init__(self, services: dict[str, QueryService], host="127.0.0.1",
                 port=8080, cluster=None, shard_maps=None,
                 reuse_port: bool = False, response_cache: bool = True,
                 rule_managers=None):
        self.services = services
        self.cluster = cluster
        # dataset -> RuleManager (standing queries); serves /api/v1/rules
        self.rule_managers = rule_managers or {}
        # member mode: dataset -> mirrored ShardMapper (StatusActor
        # subscription) so members answer cluster-status queries locally
        self.shard_maps = shard_maps or {}
        self.response_cache = ResponseCache() if response_cache else None
        self.dispatcher = HttpDispatcher(self)
        handler = _make_handler(self)
        cls = _ReusePortHTTPServer if reuse_port else ThreadingHTTPServer
        self.httpd = cls((host, port), handler)
        self.port = self.httpd.server_address[1]
        self._thread: threading.Thread | None = None
        # per-service micro-batchers: concurrent handler threads coalesce
        # into one engine batch (see coordinator.query_service.QueryBatcher)
        self._batchers: dict[int, object] = {}

    def batched(self, svc: QueryService):
        b = self._batchers.get(id(svc))
        if b is None:
            from filodb_tpu.coordinator.query_service import QueryBatcher
            b = self._batchers[id(svc)] = QueryBatcher(svc)
        return b

    def start(self) -> "FiloHttpServer":
        self._thread = threading.Thread(target=self.httpd.serve_forever,
                                        daemon=True)
        self._thread.start()
        return self

    def stop(self):
        self.httpd.shutdown()
        self.httpd.server_close()


def _make_handler(server: FiloHttpServer):
    class Handler(BaseHTTPRequestHandler):
        # keep-alive: HTTP/1.0 would pay a TCP connect + handler thread
        # spawn per request (the reference serves over a pooled Akka-HTTP
        # pipeline for the same reason, FiloHttpServer.scala:23)
        protocol_version = "HTTP/1.1"

        def log_message(self, fmt, *args):  # quiet
            log.debug(fmt, *args)

        def do_GET(self):
            self._route()

        def do_POST(self):
            self._route()

        def _route(self):
            raw = b""
            if self.command == "POST":
                try:
                    ln = int(self.headers.get("Content-Length") or 0)
                    if ln < 0:
                        raise ValueError("negative Content-Length")
                except ValueError as e:
                    # unparseable length desyncs the keep-alive stream:
                    # answer 400 and drop the connection
                    self.close_connection = True
                    body = json.dumps(promjson.error_json(str(e))).encode()
                    self.send_response(400)
                    self.send_header("Content-Type", JSON_CT)
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                    return
                raw = self.rfile.read(ln) if ln else b""
            code, headers, body = server.dispatcher.handle(
                self.command, self.path, raw,
                self.headers.get("Content-Type", ""))
            self.send_response(code)
            for k, v in headers.items():
                self.send_header(k, v)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

    return Handler
