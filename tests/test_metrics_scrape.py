"""Prometheus /metrics exposition breadth — reference shard-metric parity.

The reference names ~50 shard metrics in ``TimeSeriesShardStats``
(``TimeSeriesShard.scala:41-133``); this scrapes the standalone server after
ingest + flush + query traffic and asserts the named series are present
with per-shard dataset/shard tags.
"""

import json
import socket
import time
import urllib.request

import pytest

from filodb_tpu.config import ServerConfig
from filodb_tpu.standalone import FiloServer

START = 1_600_000_000

EXPECTED_NAMES = [
    # ingest
    "memstore_rows_ingested_total",
    "recovery_row_skipped_total",
    "memstore_data_dropped_total",
    "memstore_unknown_schema_dropped_total",
    "memstore_incompatible_containers_total",
    "memstore_offsets_not_recovered_total",
    "memstore_out_of_order_samples_total",
    "ingestion_clock_delay_ms",
    # partition lifecycle
    "memstore_partitions_created_total",
    "memstore_partitions_purged_total",
    "memstore_partitions_purged_index_total",
    "memstore_partitions_purge_time_ms_total",
    "memstore_partitions_evicted_total",
    "memstore_chunkids_evicted_total",
    "memstore_partitions_paged_restored_total",
    "memstore_eviction_stall_ns_total",
    "num_partitions",
    "memstore_timeseries_count",
    "num_ingesting_partitions",
    # encode / flush
    "memstore_samples_encoded_total",
    "memstore_encoded_bytes_allocated_total",
    "memstore_hist_encoded_bytes_total",
    "memstore_flushes_chunks_written_total",
    "memstore_flushes_success_total",
    "memstore_flushes_failed_total",
    "memstore_index_num_dirty_keys_flushed_total",
    "chunk_flush_task_latency_seconds_count",
    "memstore_downsample_records_created_total",
    # offsets
    "shard_offset_latest_inmemory",
    "shard_offset_flushed_latest",
    "shard_offset_flushed_earliest",
    # recovery
    "memstore_total_shard_recovery_time_ms",
    "memstore_index_recovery_partkeys_processed_total",
    # query
    "memstore_partitions_queried_total",
    "memstore_chunks_queried_total",
    "query_time_range_minutes_count",
    # chunk aggregate sidecars (query/engine/sidecar_lane.py,
    # memory/chunk.py) — registered at import time
    "filodb_sidecar_served_total",
    "filodb_sidecar_bypassed_total",
    "filodb_sidecar_backfilled_total",
    # ODP
    "chunks_paged_in_total",
    "memstore_partitions_paged_in_total",
    # bloom
    "evicted_pk_bloom_filter_queries_total",
    "evicted_pk_bloom_filter_fp_total",
    "evicted_pk_bloom_filter_approx_size",
    # live-state gauges
    "memstore_index_entries",
    "memstore_index_ram_bytes",
    "memstore_writebuffer_pool_size",
    "memstore_chunk_ram_bytes",
]

# extent result cache (filodb_tpu.query.result_cache) — registered the
# moment a cache-enabled service is built (standalone default-on)
RESULT_CACHE_NAMES = [
    "filodb_result_cache_hits_total",
    "filodb_result_cache_misses_total",
    "filodb_result_cache_partial_hits_total",
    "filodb_result_cache_evictions_total",
    "filodb_result_cache_bytes",
]

# distributed-aggregation pushdown + wire transport (coordinator/planner.py,
# coordinator/remote.py) — registered at import, standalone imports both
DIST_AGG_NAMES = [
    "filodb_agg_pushdown_applied_total",
    "filodb_agg_pushdown_bypassed_total",
    "filodb_remote_bytes_sent_total",
    "filodb_remote_bytes_received_total",
    "filodb_wire_frames_compressed_total",
    "filodb_wire_frames_raw_total",
    "filodb_wire_compress_bytes_in_total",
    "filodb_wire_compress_bytes_out_total",
]

# query-path resilience (coordinator/query_service.py, utils/resilience.py)
# — counters registered at import; found missing by the filolint
# metrics-parity pass (PR203), which now keeps these lists in step with
# the source tree
QUERY_RESILIENCE_NAMES = [
    "filodb_partial_results_total",
    "filodb_query_retries_total",
]

# overload protection (utils/governor.py, gateway/server.py) — gauges and
# counters pre-registered at import so families render before any shed
GOVERNOR_NAMES = [
    "filodb_governor_state",
    "filodb_governor_inflight",
    "filodb_governor_queue_depth",
    "filodb_governor_memory_utilization",
    "filodb_governor_admitted_total",
    "filodb_governor_rejected_total",
    "filodb_governor_transitions_total",
    "filodb_governor_budget_exceeded_total",
    "filodb_governor_queue_wait_seconds_bucket",
    "filodb_governor_queue_wait_seconds_count",
    "filodb_governor_queue_wait_seconds_sum",
    "gateway_queue_depth",
    "gateway_records_shed_total",
]


# live shard migration (coordinator/migration.py) — registered at import so
# dashboards see the families before any migration runs
MIGRATION_NAMES = [
    "filodb_shard_migrations_started_total",
    "filodb_shard_migrations_completed_total",
    "filodb_shard_migrations_aborted_total",
    "filodb_shard_migrations_resumed_total",
    "filodb_shard_migration_active",
    "filodb_shard_migration_phase",
    "filodb_shard_migration_lag",
    "filodb_shard_migration_seconds_bucket",
    "filodb_shard_migration_seconds_count",
    "filodb_shard_migration_seconds_sum",
]


# per-tenant isolation (utils/governor.py) — untagged family anchors
# pre-registered; runtime series carry {tenant=...} tags
TENANT_NAMES = [
    "filodb_tenant_inflight",
    "filodb_tenant_admitted_total",
    "filodb_tenant_rejected_total",
    "filodb_tenant_ingest_dropped_total",
    "filodb_tenant_series",
    "filodb_tenant_quota",
]


# standing queries (filodb_tpu/rules) — registered at import; standalone
# imports the package unconditionally, so the families render before (and
# whether or not) any rule group is configured
RULES_NAMES = [
    "filodb_rules_groups",
    "filodb_rules_watermark_lag_seconds",
    "filodb_rules_evals_total",
    "filodb_rules_eval_failures_total",
    "filodb_rules_evals_shed_total",
    "filodb_rules_steps_evaluated_total",
    "filodb_rules_steps_skipped_total",
    "filodb_rules_samples_written_total",
    "filodb_rules_eval_seconds_bucket",
    "filodb_rules_eval_seconds_count",
    "filodb_rules_eval_seconds_sum",
    "filodb_rules_last_eval_ts",
    "filodb_rules_unrecovered_groups",
]

ALERTS_NAMES = [
    "filodb_alerts_firing",
    "filodb_alerts_pending",
    "filodb_alerts_transitions_total",
    # notification egress (rules/notify.py): registered at import even
    # when no webhook is configured
    "filodb_alerts_notifications_total",
    "filodb_alerts_notification_failures_total",
    "filodb_alerts_notifications_dropped_total",
]


# distributed query tracing + slow-query flight recorder
# (utils/tracing.py) — stage histograms pre-registered at import from the
# whitelisted stage names; sampling/recorder counters too
TRACING_NAMES = [
    "filodb_query_stage_seconds_bucket",
    "filodb_query_stage_seconds_count",
    "filodb_query_stage_seconds_sum",
    "filodb_queries_sampled_total",
    "filodb_slow_queries_recorded_total",
    # render has no span (it runs after the query's trace has closed):
    # both HTTP fronts observe it into one histogram (http/server.py)
    "filodb_http_render_seconds_bucket",
    "filodb_http_render_seconds_count",
    "filodb_http_render_seconds_sum",
]

# the stage label's whitelist (utils/tracing._STAGES): the mesh engine's
# phase spans and the tails above it joined it with the spans themselves
TRACING_STAGES = [
    "parse", "plan-materialize", "exec-dispatch", "dispatch",
    "mesh-execute", "scan", "decode", "reduce", "odp-page", "cache",
    "mesh-lookup", "batch-read", "batch-stack", "mesh-group", "mesh-pad",
    "mesh-place", "mesh-dispatch", "mesh-fetch", "mesh-assemble", "finish",
    "cache-merge", "batch-fetch", "hist-flatten", "hist-quantile",
]


# object-store durable tier (core/store/objectstore.py) — registered at
# import; standalone imports the module regardless of the configured backend
OBJECTSTORE_NAMES = [
    "filodb_objectstore_puts_total",
    "filodb_objectstore_gets_total",
    "filodb_objectstore_bytes_up_total",
    "filodb_objectstore_bytes_down_total",
    "filodb_objectstore_payload_bytes_down_total",
    "filodb_objectstore_retries_total",
    "filodb_objectstore_compactions_total",
    "filodb_objectstore_corrupt_total",
    "filodb_objectstore_queue_depth",
]


# aggregate pyramids (core/store/pyramid.py, query/engine/pyramid_lane.py)
# — registered when objectstore imports pyramid at boot; kept in step with
# the source tree by the filolint PR207 rule (no lazy/GaugeFn exemptions)
PYRAMID_NAMES = [
    "filodb_pyramid_objects_written_total",
    "filodb_pyramid_backfilled_total",
    "filodb_pyramid_served_total",
    "filodb_pyramid_fallback_total",
    "filodb_pyramid_nodes_total",
    "filodb_pyramid_bytes_down_total",
]


# ingest-path freshness + self-monitoring (utils/selfmon.py,
# utils/tracing.py, coordinator/cluster.py, core/memstore/shard.py) —
# kept in step with the source tree by the filolint PR206 rule, which
# (unlike PR203) exempts nothing: lag GaugeFns register at shard start
# and the fixture boots shards + drives ingest, so all families render
INGEST_OBS_NAMES = [
    "filodb_metric_scrape_errors_total",
    "filodb_ingest_slow_recorded_total",
    "filodb_ingest_lag_seconds",
    "filodb_ingest_offset_lag",
    "filodb_ingest_checkpoint_lag",
    "filodb_ingest_errors_total",
    "filodb_ingest_e2e_seconds_bucket",
    "filodb_ingest_e2e_seconds_count",
    "filodb_ingest_e2e_seconds_sum",
    "filodb_selfmon_ticks_total",
    "filodb_selfmon_errors_total",
    "filodb_selfmon_samples_total",
    "filodb_selfmon_series",
    "filodb_selfmon_tick_seconds_bucket",
    "filodb_selfmon_tick_seconds_count",
    "filodb_selfmon_tick_seconds_sum",
    "filodb_objectstore_oldest_task_age_seconds",
]


# tiered query federation (query/federation.py, core/memstore/odp.py) —
# counters registered when the HTTP front imports federation at boot; the
# ODP cache-size GaugeFn renders 0 before any cache instance exists
FEDERATION_NAMES = [
    "filodb_federation_queries_total",
    "filodb_federation_subqueries_total",
    "filodb_odp_cache_chunks",
    "odp_range_hits_total",
]


# continuous shard replication + hedged replica reads
# (coordinator/replication.py) — counters and untagged gauge anchors
# registered at import (standalone imports cluster → replication at boot),
# so the families render before any replica exists
REPLICATION_NAMES = [
    "filodb_replica_promotions_total",
    "filodb_replica_divergence_total",
    "filodb_replica_follower_reads_total",
    "filodb_replica_lag",
    "filodb_replica_watermark",
    "filodb_hedged_reads_total",
    "filodb_hedged_reads_won_total",
]


# mesh query engine (parallel/mesh_engine.py) — plan recognition,
# split-vs-fused dispatch, device cache behavior and exec-path fallbacks;
# all registered at mesh_engine import (QueryService construction at boot)
MESH_NAMES = [
    "filodb_mesh_supported_total",
    "filodb_mesh_unsupported_total",
    "filodb_mesh_dispatch_total",
    "filodb_mesh_compile_cache_total",
    "filodb_mesh_batch_cache_total",
    "filodb_mesh_bounds_cache_total",
    "filodb_mesh_eval_cache_total",
    "filodb_mesh_fallback_total",
    "filodb_mesh_hit_rate",
    "filodb_mesh_samples_scanned_total",
    "filodb_mesh_bucket_samples_scanned_total",
]


# what the front door hands the query service in one call, and how many
# queries ride in it (coordinator/query_service.py:query_range_many);
# registered at query_service import
QUERY_BATCH_NAMES = [
    "filodb_query_batches_total",
    "filodb_query_batch_members_total",
]


# multi-process mesh runtime (coordinator/mesh_cluster.py) — descriptor
# dispatch outcomes, fallback reasons, live worker gauge, and root-side
# collective latency; registered at mesh_cluster import (pulled in by
# query_service at boot so families render before any worker spawns)
MESH_PROC_NAMES = [
    "filodb_mesh_proc_dispatch_total",
    "filodb_mesh_proc_fallback_total",
    "filodb_mesh_proc_workers",
    "filodb_mesh_proc_collective_seconds_bucket",
    "filodb_mesh_proc_collective_seconds_count",
    "filodb_mesh_proc_collective_seconds_sum",
]


# trace-driven adaptive planner (query/cost_model.py) — decision sources,
# settle counts, calibration error, signature-table occupancy; registered
# at cost_model import (QueryService admission path at boot)
COSTMODEL_NAMES = [
    "filodb_costmodel_decisions_total",
    "filodb_costmodel_settled_total",
    "filodb_costmodel_calibration_error",
    "filodb_costmodel_signatures",
    "filodb_costmodel_evictions_total",
]


# the batch build (query/engine/batch.py): series read through a native
# shard core's one call a shard against series read one at a time — one
# family, both labels registered at utils/metrics import
BATCH_NAMES = [
    "filodb_batch_rows_total",
    # the mesh engine's staging pool (parallel/staging.py): bytes of host
    # arrays a build wrote into, by where the memory came from
    "filodb_batch_buffer_bytes_total",
]


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@pytest.fixture
def server(tmp_path):
    cfg_path = tmp_path / "server.json"
    cfg_path.write_text(json.dumps({
        "node_name": "metrics-node",
        "data_dir": str(tmp_path / "data"),
        "http_port": 0,
        "gateway_port": 0,
        "datasets": {"timeseries": {
            "num_shards": 2, "spread": 1,
            "store": {"max_chunk_size": 50, "groups_per_shard": 2}}},
    }))
    cfg = ServerConfig.load(str(cfg_path))
    object.__setattr__(cfg, "gateway_port", _free_port())
    srv = FiloServer(cfg).start()
    yield srv
    srv.shutdown()


def _scrape(port: int) -> str:
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics") as r:
        assert r.status == 200
        return r.read().decode()


def _ingest_and_wait(srv, metric: str, rows: int = 150) -> None:
    """``rows`` Influx lines of ``metric`` over five hosts of App-0
    through the gateway, then wait until the shards have counted them."""
    want = rows + sum(s.stats.rows_ingested.value
                      for s in srv.memstore.shards_for("timeseries"))
    with socket.create_connection(("127.0.0.1", srv.gateway.port)) as sock:
        for i in range(rows):
            ts_ns = (START + i * 10) * 1_000_000_000
            sock.sendall(f"{metric},host=h{i % 5},_ws_=demo,"
                         f"_ns_=App-0 value={i} {ts_ns}\n".encode())
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        srv.gateway.sink.flush()
        if sum(s.stats.rows_ingested.value
               for s in srv.memstore.shards_for("timeseries")) >= want:
            break
        time.sleep(0.3)


class TestMetricsScrape:
    def test_shard_metric_breadth(self, server):
        srv = server
        # drive ingest so counters move
        with socket.create_connection(("127.0.0.1",
                                       srv.gateway.port)) as s:
            for i in range(150):
                ts_ns = (START + i * 10) * 1_000_000_000
                s.sendall(f"scrape_metric,host=h{i % 5},_ws_=demo,"
                          f"_ns_=App-0 value={i} {ts_ns}\n".encode())
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            srv.gateway.sink.flush()
            ingested = sum(s2.stats.rows_ingested.value
                           for s2 in srv.memstore.shards_for("timeseries"))
            if ingested >= 150:  # wait for the FULL batch, not first rows
                break
            time.sleep(0.3)
        # flush + query so flush/query metric families move too
        for shard in srv.memstore.shards_for("timeseries"):
            shard.flush_all()
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.http.port}/promql/timeseries/api/v1/"
                f"query_range?query=sum(rate(scrape_metric%5B1m%5D))"
                f"&start={START}&end={START + 1500}&step=60") as r:
            assert r.status == 200

        text = _scrape(srv.http.port)
        names_present = set()
        for line in text.splitlines():
            if not line or line.startswith("#"):
                continue
            name = line.split("{")[0].split(" ")[0]
            names_present.add(name)
        missing = [n for n in EXPECTED_NAMES if n not in names_present]
        assert not missing, f"missing metric families: {missing}"
        assert len([n for n in EXPECTED_NAMES if n in names_present]) >= 40

        # result-cache counters are exposed, and the range query above
        # (splittable: sum(rate(...))) actually drove them
        missing_rc = [n for n in RESULT_CACHE_NAMES
                      if n not in names_present]
        assert not missing_rc, f"missing result-cache metrics: {missing_rc}"

        # distributed-aggregation pushdown + wire counters are exposed
        # (decision-counter movement is covered in test_agg_pushdown.py —
        # the mesh engine can satisfy this query without planner
        # materialization, so movement here would be engine-dependent)
        missing_da = [n for n in DIST_AGG_NAMES if n not in names_present]
        assert not missing_da, f"missing dist-agg metrics: {missing_da}"

        # object-store tier families render even on the local backend
        # (pre-registered at import so dashboards see stable zeros)
        missing_os = [n for n in OBJECTSTORE_NAMES if n not in names_present]
        assert not missing_os, f"missing objectstore metrics: {missing_os}"

        # aggregate-pyramid families render at zero before any cold fold
        # (counters register when objectstore imports pyramid at boot)
        missing_pyr = [n for n in PYRAMID_NAMES if n not in names_present]
        assert not missing_pyr, f"missing pyramid metrics: {missing_pyr}"

        # query-path resilience counters render from import time
        missing_qr = [n for n in QUERY_RESILIENCE_NAMES
                      if n not in names_present]
        assert not missing_qr, f"missing resilience metrics: {missing_qr}"

        # governor + gateway overload families are exposed, and the range
        # query above passed the admission gate so admissions moved
        missing_gov = [n for n in GOVERNOR_NAMES if n not in names_present]
        assert not missing_gov, f"missing governor metrics: {missing_gov}"

        # live-migration families render before any migration runs
        # (standalone imports cluster → migration at boot)
        missing_mig = [n for n in MIGRATION_NAMES if n not in names_present]
        assert not missing_mig, f"missing migration metrics: {missing_mig}"

        # per-tenant isolation families render before any tenant config
        missing_t = [n for n in TENANT_NAMES if n not in names_present]
        assert not missing_t, f"missing tenant metrics: {missing_t}"

        # standing-query + alert families render with no rules configured
        missing_r = [n for n in RULES_NAMES + ALERTS_NAMES
                     if n not in names_present]
        assert not missing_r, f"missing rules metrics: {missing_r}"

        # tracing stage histograms + flight-recorder counters render from
        # import time (stage labels are a bounded whitelist)
        missing_tr = [n for n in TRACING_NAMES if n not in names_present]
        assert not missing_tr, f"missing tracing metrics: {missing_tr}"
        missing_st = [
            st for st in TRACING_STAGES
            if f'filodb_query_stage_seconds_count{{stage="{st}"}}'
            not in text]
        assert not missing_st, f"missing stage histograms: {missing_st}"

        # ingest-path freshness + selfmon families: the import-time ones
        # render unconditionally; the per-shard lag gauges register at
        # shard start and the lag-seconds GaugeFn emits once the ingest
        # above has landed
        missing_io = [n for n in INGEST_OBS_NAMES if n not in names_present]
        assert not missing_io, f"missing ingest-obs metrics: {missing_io}"

        # tier-federation + ODP cache families render before any
        # federated query (http front imports federation at boot)
        missing_fed = [n for n in FEDERATION_NAMES
                       if n not in names_present]
        assert not missing_fed, f"missing federation metrics: {missing_fed}"

        # mesh-engine observability: dispatch form, device caches, lane
        # routing — all render from mesh_engine import at boot, before
        # the first mesh-eligible query
        missing_mesh = [n for n in MESH_NAMES if n not in names_present]
        assert not missing_mesh, f"missing mesh metrics: {missing_mesh}"

        # multi-process mesh runtime: dispatch/fallback counters, worker
        # gauge, and collective-latency histogram render at zero from the
        # mesh_cluster import at boot — no worker pool needs to exist
        missing_mp = [n for n in MESH_PROC_NAMES
                      if n not in names_present]
        assert not missing_mp, f"missing mesh-proc metrics: {missing_mp}"

        # adaptive-planner cost model: decision/settle counters and
        # calibration gauges pre-register at cost_model import (pulled in
        # by the query-service admission path at boot)
        missing_cm = [n for n in COSTMODEL_NAMES if n not in names_present]
        assert not missing_cm, f"missing costmodel metrics: {missing_cm}"

        # the batch build's read-path counter pair renders from import
        missing_b = [n for n in BATCH_NAMES if n not in names_present]
        assert not missing_b, f"missing batch metrics: {missing_b}"

        # calls and members of query_range_many render from import
        missing_qb = [n for n in QUERY_BATCH_NAMES
                      if n not in names_present]
        assert not missing_qb, f"missing query-batch metrics: {missing_qb}"

        # shard-replication + hedged-read families render at zero before
        # any replica set is configured
        missing_rep = [n for n in REPLICATION_NAMES
                       if n not in names_present]
        assert not missing_rep, f"missing replication metrics: {missing_rep}"

        def total(name):
            return sum(float(line.rsplit(" ", 1)[1])
                       for line in text.splitlines()
                       if line.startswith(name + "{") or
                       line.split(" ")[0] == name)

        assert total("filodb_result_cache_hits_total") \
            + total("filodb_result_cache_misses_total") >= 1

        assert total("filodb_governor_admitted_total") >= 1

        # per-shard tagging: both shards of THIS dataset expose the
        # counter (the registry is process-wide; other tests' datasets may
        # coexist in the same exposition)
        tagged = [line for line in text.splitlines()
                  if line.startswith("memstore_rows_ingested_total")
                  and 'dataset="timeseries"' in line]
        assert any('shard="0"' in t for t in tagged), tagged
        assert any('shard="1"' in t for t in tagged), tagged

        # ingest actually counted
        total = sum(float(t.rsplit(" ", 1)[1]) for t in tagged)
        assert total >= 150

    @pytest.mark.parametrize("path", ["native", "fallback"])
    def test_batch_rows_family_is_scraped_with_both_paths(self, server,
                                                          path):
        """``filodb_batch_rows_total{path=...}`` renders before any query,
        under one HELP/TYPE header, and a mesh query moves it by the series
        of its batch: on the native path where the shards are native."""
        from filodb_tpu.core.memstore.native_shard import native_available

        srv = server
        before = _scrape(srv.http.port)
        line = f'filodb_batch_rows_total{{path="{path}"}}'
        assert [n for n in BATCH_NAMES
                if f"# TYPE {n} counter" not in before] == []
        assert before.count("# TYPE filodb_batch_rows_total counter") == 1
        assert any(ln.startswith(line) for ln in before.splitlines())

        def value(text):
            (ln,) = [ln for ln in text.splitlines() if ln.startswith(line)]
            return float(ln.rsplit(" ", 1)[1])

        with socket.create_connection(("127.0.0.1",
                                       srv.gateway.port)) as s:
            for i in range(150):
                ts_ns = (START + i * 10) * 1_000_000_000
                s.sendall(f"batch_metric,host=h{i % 5},_ws_=demo,"
                          f"_ns_=App-0 value={i} {ts_ns}\n".encode())
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            srv.gateway.sink.flush()
            if sum(s2.stats.rows_ingested.value for s2 in
                   srv.memstore.shards_for("timeseries")) >= 150:
                break
            time.sleep(0.3)
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.http.port}/promql/timeseries/api/v1/"
                f"query_range?query=sum(rate(batch_metric%5B1m%5D))"
                f"&start={START}&end={START + 1500}&step=60") as r:
            assert r.status == 200
        moved = value(_scrape(srv.http.port)) - value(before)
        engaged = "native" if native_available() else "fallback"
        if path == engaged:     # five series a batch, a batch an extent
            assert moved >= 5 and moved % 5 == 0
        else:
            assert moved == 0

    @pytest.mark.parametrize("source", ["reused", "fresh"])
    def test_batch_buffer_family_is_scraped_with_both_sources(self, server,
                                                              source):
        """``filodb_batch_buffer_bytes_total{source=...}`` renders before
        any query under one HELP/TYPE header; a server's mesh queries over
        new chunk ranges move it by whole ``[P, S]`` arrays — ``fresh`` on
        the first build of a shape, ``reused`` after."""
        srv = server
        family = "filodb_batch_buffer_bytes_total"
        line = f'{family}{{source="{source}"}}'

        def values(text):
            return {src: float(ln.rsplit(" ", 1)[1])
                    for ln in text.splitlines() for src in ("reused", "fresh")
                    if ln.startswith(f'{family}{{source="{src}"}}')}

        before = _scrape(srv.http.port)
        assert before.count(f"# TYPE {family} counter") == 1
        assert before.count(f"# HELP {family} ") == 1
        assert any(ln.startswith(line) for ln in before.splitlines())
        with socket.create_connection(("127.0.0.1",
                                       srv.gateway.port)) as s:
            for i in range(150):
                ts_ns = (START + i * 10) * 1_000_000_000
                s.sendall(f"buffer_metric_{source},host=h{i % 5},_ws_=demo,"
                          f"_ns_=App-0 value={i} {ts_ns}\n".encode())
        want = sum(s2.stats.rows_ingested.value for s2 in
                   srv.memstore.shards_for("timeseries")) + 150
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            srv.gateway.sink.flush()
            if sum(s2.stats.rows_ingested.value for s2 in
                   srv.memstore.shards_for("timeseries")) >= want:
                break
            time.sleep(0.3)
        moved = []
        # the aggregation is in the batch cache's key: two misses that
        # build the same shapes, the second into the first's arrays
        for agg in ("sum", "max"):
            at = values(_scrape(srv.http.port))
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{srv.http.port}/promql/timeseries/"
                    f"api/v1/query_range?query={agg}(max_over_time("
                    f"buffer_metric_{source}%5B1m%5D))"
                    f"&start={START}&end={START + 1500}&step=60") as r:
                assert r.status == 200
            now = values(_scrape(srv.http.port))
            moved.append({k: now[k] - at[k] for k in now})
        first, second = moved
        assert sum(first.values()) == sum(second.values()) > 0
        assert second == {"fresh": 0, "reused": sum(first.values())}

    @pytest.mark.parametrize("family,says", [
        ("filodb_mesh_samples_scanned_total", "samples of the placed batch"),
        ("filodb_mesh_bucket_samples_scanned_total",
         "a histogram sample counts once a bucket"),
    ], ids=["samples", "bucket-samples"])
    def test_mesh_scan_counters_are_scraped_and_move_alike_on_scalars(
            self, server, family, says):
        """Both scan counters render before any query under their
        ``_total`` names, one HELP/TYPE header each with the help text of
        ``parallel/mesh_engine.py``; a mesh query over scalar series moves
        the two by the same step (a histogram's by its buckets:
        ``tests/test_histo_fleet.py``)."""
        srv = server
        both = ("filodb_mesh_samples_scanned_total",
                "filodb_mesh_bucket_samples_scanned_total")

        def values(text):
            return {f: float(ln.rsplit(" ", 1)[1])
                    for ln in text.splitlines() for f in both
                    if ln.startswith(f + " ")}

        before = _scrape(srv.http.port)
        assert before.count(f"# TYPE {family} counter") == 1
        (help_line,) = [ln for ln in before.splitlines()
                        if ln.startswith(f"# HELP {family} ")]
        assert says in help_line
        assert set(values(before)) == set(both)
        _ingest_and_wait(srv, "scan_metric")
        at = values(_scrape(srv.http.port))
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.http.port}/promql/timeseries/api/v1/"
                f"query_range?query=sum(rate(scan_metric%5B1m%5D))"
                f"&start={START}&end={START + 1500}&step=60") as r:
            assert r.status == 200
        now = values(_scrape(srv.http.port))
        moved = {f: now[f] - at[f] for f in both}
        assert moved[both[0]] == moved[both[1]] > 0

    def test_flush_and_query_counters_move(self, server):
        srv = server
        with socket.create_connection(("127.0.0.1",
                                       srv.gateway.port)) as s:
            for i in range(60):
                ts_ns = (START + i * 10) * 1_000_000_000
                s.sendall(f"fq_metric,host=h1,_ws_=demo,_ns_=App-0 "
                          f"value={i} {ts_ns}\n".encode())
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            srv.gateway.sink.flush()
            if any(s2.stats.rows_ingested.value
                   for s2 in srv.memstore.shards_for("timeseries")):
                break
            time.sleep(0.3)
        for shard in srv.memstore.shards_for("timeseries"):
            shard.flush_all()
        text = _scrape(srv.http.port)

        def total(name):
            return sum(float(line.rsplit(" ", 1)[1])
                       for line in text.splitlines()
                       if line.startswith(name + "{") or line == name)

        assert total("memstore_flushes_success_total") >= 1
        assert total("memstore_samples_encoded_total") >= 60
        assert total("memstore_encoded_bytes_allocated_total") > 0
        assert total("memstore_flushes_chunks_written_total") >= 1
        # scrape-time gauges read live state
        assert total("memstore_index_entries") >= 1
        assert total("memstore_index_ram_bytes") > 0
