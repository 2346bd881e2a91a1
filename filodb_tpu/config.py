"""Server/dataset configuration.

Counterpart of the reference's layered HOCON config system
(``filodb-defaults.conf`` ← server conf ← per-dataset source conf, parsed
into ``FilodbSettings``/``StoreConfig``/``IngestionConfig``). The format here
is JSON (stdlib; HOCON adds no capability), with the same layering: defaults
← server file ← per-dataset blocks.
"""

from __future__ import annotations

import json
from dataclasses import dataclass, field

from filodb_tpu.core.store.config import IngestionConfig, StoreConfig

DEFAULTS = {
    "node_name": "node-0",
    "data_dir": "./filodb-data",
    "wal_dir": None,
    "wal_fsync": False,           # fsync every WAL append (power-failure safe)
    "wal_server_port": 0,         # serve this node's WAL over TCP (broker)
    "wal_remote": None,           # "host:port" — use a remote log server
    "wal_kafka": None,            # "host:port" — external Kafka broker WAL
    "consul": None,               # {"host","port","service"} seed discovery
    "store_server_port": 0,       # serve this node's column store over TCP
    "store_remote": None,         # "host:port" — use a remote chunk store
    "http_port": 8080,
    "gateway_port": 0,            # 0 = disabled
    "executor_port": 0,           # plan-shipping server; 0 = ephemeral
    "seeds": [],                  # bootstrap seed addresses
    "enable_failover": False,     # singleton failover via member registry
    # fault-tolerance knobs (filodb_tpu.utils.resilience.ResilienceConfig);
    # keys here override that dataclass's defaults at boot
    "resilience": {
        "query_timeout_s": 30.0,      # per-query deadline
        "retry_max_attempts": 2,      # remote dispatch attempts
        "breaker_failure_threshold": 5,
        "breaker_reset_s": 10.0,
        "allow_partial": True,        # degrade instead of fail
        "partial_max_fraction": 0.5,  # max lost children per gather
    },
    # extent result cache (filodb_tpu.query.result_cache.ResultCacheConfig):
    # range queries split at step-aligned extent boundaries; extents ending
    # before the mutable horizon cache without a version stamp, so live
    # ingest only recomputes the head
    "result_cache": {
        "enabled": True,
        "extent_steps": 32,           # extent length in steps
        "max_bytes": 256 * 1024 * 1024,
        "ooo_allowance_ms": 300_000,  # out-of-order arrival allowance
    },
    # overload protection (filodb_tpu.utils.governor.GovernorConfig): query
    # admission control, scan-time cost budgets (0 = unlimited), and the
    # memory-pressure watchdog thresholds. Keys here override that
    # dataclass's defaults at boot.
    "governor": {
        "admission_capacity": 32,     # concurrent queries when healthy
        "admission_queue_limit": 128,
        "max_queue_wait_s": 5.0,
        "retry_after_s": 1.0,
        "degraded_capacity_factor": 0.5,
        "degraded_threshold": 0.75,
        "critical_threshold": 0.92,
        "watchdog_interval_s": 0.5,
        "max_samples_scanned": 0,     # per-query budget; 0 = unlimited
        "max_result_bytes": 0,
        "max_group_cardinality": 0,
        "budget_degrade": "partial",  # "partial" | "error"
        # concurrent standing-query (rule) evaluations; their own lowest-
        # priority admission class (never queued, shed outside OK)
        "rules_max_inflight": 2,
        # per-tenant admission classes + cardinality quotas keyed on the
        # _ws_ or _ws_/_ns_ shard-key prefix, e.g.
        #   "tenants": {"demo/App-0": {"max_inflight": 8,
        #                              "max_series": 100000}}
        # a flooding tenant sheds ONLY itself (reject reason "tenant" /
        # quota-dropped ingest), never its neighbors
        "tenants": {},
    },
    # trace-driven adaptive planner (filodb_tpu.query.cost_model.CostModel):
    # online per-(dataset, plan-signature) cost estimates routing the
    # either/or planning decisions (sidecar vs decode, pyramid fallback,
    # pushdown, lane, paging, admission class, cache admission). Below
    # min_samples every site reproduces the static heuristic exactly;
    # FILODB_ADAPTIVE=0 disables routing entirely (observation continues).
    "cost_model": {
        "min_samples": 8,             # arm warm-up before routing departs
        "max_signatures": 4096,       # LRU bound on (site, signature) keys
        "reservoir": 64,              # percentile reservoir per arm
        "cheap_threshold_s": 0.05,    # admit-class CHEAP/EXPENSIVE split
    },
    # distributed query tracing + slow-query flight recorder
    # (filodb_tpu.utils.tracing.TracingConfig): head-sampling rate for
    # full span trees (deterministic on query_id), tail capture of any
    # query/operation over the slow threshold into a bounded ring served
    # at /api/v1/debug/slow_queries and `filo-cli slowlog`
    "tracing": {
        "sample_rate": 0.0,           # 0..1 fraction of queries traced
        "slow_query_threshold_ms": 500.0,  # tail capture; 0 disables
        "slowlog_capacity": 128,      # flight-recorder ring size
        # ingest-side ring: slow gateway drains / shard ingests / flushes /
        # object-store uploads, served at /api/v1/status/ingest
        "slow_ingest_threshold_ms": 250.0,
        "ingest_slowlog_capacity": 128,
    },
    # self-monitoring (filodb_tpu/utils/selfmon.py): sample the in-process
    # metric registry every interval_s and ingest the families as series
    # into the dedicated "_meta" dataset through the normal ingest path —
    # PromQL, the result cache and standing rules/alerts all work over the
    # node's own telemetry. default_alerts ships an ingest-lag +
    # breaker-open alert group evaluated over _meta.
    "selfmon": {
        "enabled": False,
        "interval_s": 15.0,
        "num_shards": 1,
        "include_buckets": False,     # also ingest per-le bucket series
        "ooo_allowance_ms": 2_000,    # _meta rules horizon allowance
        "default_alerts": True,
        "lag_alert_threshold_s": 60.0,
        "lag_alert_for": "30s",
        "alert_interval": "5s",       # default alert group eval interval
    },
    # live shard migration / rebalancing (coordinator/migration.py)
    "migration": {
        "auto_rebalance": False,      # migrate shards off joining-node
                                      # imbalance and watchdog pressure
        "lag_threshold": 0,           # max replay-offset lag at flip
        "catchup_timeout_s": 30.0,    # abort CATCHUP after this long
    },
    # multi-process mesh runtime (parallel/multiproc.py +
    # coordinator/mesh_cluster.py): N worker processes each own a
    # contiguous slice of one dataset's shard space and execute lowered
    # mesh descriptors over per-process 1-device mesh slices; the
    # coordinator reduces at window boundaries and falls back to the
    # single-process engines when a slice is unavailable.
    "mesh_workers": {
        "enabled": False,
        "workers": 2,                 # processes to spawn (N×1 harness)
        "base_port": 0,               # 0 = ephemeral per worker
        "dataset": None,              # None = first configured dataset
        "timeout_s": 30.0,            # per-worker dispatch timeout cap
        "ready_timeout_s": 120.0,     # boot wait before serving degraded
        "seed": None,                 # module:callable harness data source
    },
    # continuous shard replication / HA serving
    # (coordinator/replication.py)
    "replication": {
        "n_replicas": 0,              # warm followers per shard (0 = off)
        "in_sync_lag": 0,             # max WAL-offset lag to count IN_SYNC
        "hedge_s": 0.05,              # hedged-read timer for replica reads
        "durable_sync_s": 5.0,        # follower sealed-segment sync cadence
    },
    # standing queries (filodb_tpu/rules): recording + alerting rule
    # groups evaluated incrementally on ingest progress. Each group:
    #   {"name": ..., "interval": "60s", "dataset": <defaults to first>,
    #    "rules": [{"record": "job:heap:avg", "expr": "...",
    #               "labels": {...}},
    #              {"alert": "HighHeap", "expr": "... > 0.9",
    #               "for": "5m", "labels": {...},
    #               "annotations": {...}}]}
    # intervals must be whole seconds; durations accept Prometheus
    # strings ("5m") or bare numbers meaning seconds.
    "rules": {
        "tick_s": 1.0,                # evaluation-loop poll interval
        "max_catchup_steps": 512,     # cap on steps replayed per tick
        "groups": [],
        # alert notification egress (rules/notify.py): Alertmanager-style
        # webhook POSTed on alert state transitions. webhook_url=None
        # disables egress entirely. Delivery is at-most-once off a
        # bounded queue; the POST never runs under the manager's locks.
        "notify": {
            "webhook_url": None,
            "timeout_s": 5.0,         # per-POST socket timeout
            "max_attempts": 4,        # RetryPolicy attempts per batch
            "queue_depth": 256,       # pending batches before dropping
        },
    },
    # tiered query federation (query/federation.py + coordinator/
    # tiered_planner.py): one query_range transparently spans the raw
    # memstore, the downsample tier and object-store history. Sub-ranges
    # older than memstore retention page chunks from the column store via
    # per-shard ODP caches and are stitched with the hot result.
    "federation": {
        "enabled": True,
        # memstore data floor; None = derive from the dataset's
        # store.retention_ms at boot
        "mem_retention_ms": None,
        "odp_max_chunks": 10_000,     # per cold shard ODP cache capacity
        "refresh_s": 60.0,            # cold part-key index staleness bound
    },
    # durable-store backend selection. "local" = sqlite-per-shard on
    # data_dir (default); "object" = S3-compatible object-store tier
    # (core/store/objectstore.py): write-behind segment upload, CRC32C
    # tripwires, key-prefix split scans. With backend="object" and no
    # endpoint, a directory-backed in-process fake under data_dir is used
    # (hermetic dev/test); "http(s)://host:port" targets a real
    # S3-compatible service (minio etc.).
    "store": {
        "backend": "local",
        "endpoint": None,
        "bucket": "filodb",
        "prefix": "",
        "access_key": None,
        "secret_key": None,
        "region": "us-east-1",
        "upload_queue_depth": 64,        # bounded write-behind queue
        "segment_target_bytes": 1 << 20,  # seal open segments at this size
        "bucket_count": 8,               # key-prefix split-scan fan-out
    },
    "datasets": {
        "timeseries": {
            "num_shards": 4,
            "min_num_nodes": 1,
            "spread": 1,
            # "engine": "mesh" lowers supported aggregations onto the
            # (shard × time) device mesh on single-node deployments
            "engine": "mesh",
            "store": {
                "flush_interval_ms": 3_600_000,
                "max_chunk_size": 400,
                "groups_per_shard": 20,
                "retention_ms": 3 * 24 * 3_600_000,
            },
            # optional downsampling plane:
            # "downsample": {"resolutions_ms": [300000, 3600000],
            #                "schedule_s": 21600,
            #                "raw_retention_ms": 259200000}
        }
    },
}


@dataclass
class ServerConfig:
    node_name: str = "node-0"
    data_dir: str = "./filodb-data"
    wal_dir: str | None = None  # shared log dir (the "Kafka"); default in data_dir
    wal_fsync: bool = False     # fsync every WAL append (power-failure safe)
    wal_server_port: int = 0    # serve this node's WAL over TCP (broker)
    wal_remote: str | None = None  # "host:port" — use a remote log server
    wal_kafka: str | None = None  # "host:port" — external Kafka broker
    consul: dict | None = None    # Consul seed discovery settings
    store_server_port: int = 0    # serve the column store over TCP
    store_remote: str | None = None  # "host:port" — remote chunk store
    http_port: int = 8080
    http_reuse_port: bool = False  # SO_REUSEPORT multi-process serving
    http_impl: str = "fast"  # "fast" event loop | "threaded" stdlib server
    http_response_cache: bool = True  # data_version-keyed rendered-JSON cache
    gateway_port: int = 0
    executor_port: int = 0
    seeds: list[str] = field(default_factory=list)
    enable_failover: bool = False
    datasets: dict[str, IngestionConfig] = field(default_factory=dict)
    spreads: dict[str, int] = field(default_factory=dict)
    downsample: dict[str, dict] = field(default_factory=dict)
    engines: dict[str, str] = field(default_factory=dict)  # dataset → engine
    resilience: dict = field(default_factory=dict)  # ResilienceConfig overrides
    result_cache: dict = field(default_factory=dict)  # ResultCacheConfig block
    governor: dict = field(default_factory=dict)  # GovernorConfig overrides
    cost_model: dict = field(default_factory=dict)  # adaptive planner config
    store: dict = field(default_factory=dict)  # durable-store backend block
    migration: dict = field(default_factory=dict)  # live-migration knobs
    mesh_workers: dict = field(default_factory=dict)  # multi-process mesh
    replication: dict = field(default_factory=dict)  # shard-replica knobs
    rules: dict = field(default_factory=dict)  # standing-query rule groups
    tracing: dict = field(default_factory=dict)  # TracingConfig overrides
    selfmon: dict = field(default_factory=dict)  # _meta self-monitoring
    federation: dict = field(default_factory=dict)  # tiered-query routing

    @staticmethod
    def load(path: str | None = None) -> "ServerConfig":
        cfg = json.loads(json.dumps(DEFAULTS))  # deep copy
        if path:
            with open(path) as f:
                user = json.load(f)
            _deep_merge(cfg, user)
        datasets = {}
        spreads = {}
        downsample = {}
        engines = {}
        for name, d in cfg["datasets"].items():
            if d.get("downsample"):
                downsample[name] = d["downsample"]
            store = StoreConfig(**{k: v for k, v in d.get("store", {}).items()
                                   if k in StoreConfig.__dataclass_fields__})
            datasets[name] = IngestionConfig(
                dataset=name, num_shards=d.get("num_shards", 4),
                min_num_nodes=d.get("min_num_nodes", 1), store=store,
                downsample=d.get("downsample"))
            spreads[name] = d.get("spread", 1)
            engines[name] = d.get("engine", "mesh")
            if engines[name] not in ("mesh", "exec"):
                raise ValueError(
                    f"dataset {name!r}: engine {engines[name]!r} is not one "
                    "of 'mesh' (the device mesh, with the exec tree for "
                    "plans it does not lower) and 'exec' (the exec tree "
                    "alone)")
        return ServerConfig(
            node_name=cfg["node_name"], data_dir=cfg["data_dir"],
            wal_dir=cfg.get("wal_dir"),
            wal_fsync=cfg.get("wal_fsync", False),
            wal_server_port=cfg.get("wal_server_port", 0),
            wal_remote=cfg.get("wal_remote"),
            wal_kafka=cfg.get("wal_kafka"),
            consul=cfg.get("consul"),
            store_server_port=cfg.get("store_server_port", 0),
            store_remote=cfg.get("store_remote"),
            http_port=cfg["http_port"],
            http_reuse_port=cfg.get("http_reuse_port", False),
            http_impl=cfg.get("http_impl", "fast"),
            http_response_cache=cfg.get("http_response_cache", True),
            gateway_port=cfg["gateway_port"],
            executor_port=cfg["executor_port"], seeds=cfg["seeds"],
            enable_failover=cfg.get("enable_failover", False),
            datasets=datasets, spreads=spreads, downsample=downsample,
            engines=engines, resilience=cfg.get("resilience", {}),
            result_cache=cfg.get("result_cache", {}),
            governor=cfg.get("governor", {}),
            cost_model=cfg.get("cost_model", {}),
            store=cfg.get("store", {}),
            migration=cfg.get("migration", {}),
            mesh_workers=cfg.get("mesh_workers", {}),
            replication=cfg.get("replication", {}),
            rules=cfg.get("rules", {}),
            tracing=cfg.get("tracing", {}),
            selfmon=cfg.get("selfmon", {}),
            federation=cfg.get("federation", {}))


def _deep_merge(base: dict, over: dict) -> None:
    for k, v in over.items():
        if isinstance(v, dict) and isinstance(base.get(k), dict):
            _deep_merge(base[k], v)
        else:
            base[k] = v
