"""FiloServer: the standalone server process.

Counterpart of reference ``standalone/src/main/scala/filodb.standalone/
FiloServer.scala:38,86``: boots the stores, joins the cluster (seed
discovery), starts per-shard ingestion with recovery, and serves the
Prometheus HTTP API, the plan-executor port (remote dispatch) and optionally
the Influx gateway.
"""

from __future__ import annotations

import argparse
import json
import logging
import os
import signal
import sys

from filodb_tpu.config import ServerConfig
from filodb_tpu.coordinator.cluster import FilodbCluster, Node
from filodb_tpu.coordinator.remote import PlanExecutorServer
from filodb_tpu.core.memstore.memstore import TimeSeriesMemStore
from filodb_tpu.core.store.localstore import (
    LocalDiskColumnStore,
    LocalDiskMetaStore,
)
# imported unconditionally so the filodb_objectstore_* metric families are
# registered (and scrape-visible) regardless of the configured backend
from filodb_tpu.core.store.objectstore import open_object_store
# likewise the filodb_rules_*/filodb_alerts_* families render even with no
# rule groups configured
from filodb_tpu.rules import LogSink, RuleManager, load_groups
from filodb_tpu.gateway.server import ContainerSink, GatewayServer
from filodb_tpu.http.server import FiloHttpServer
from filodb_tpu.kafka.log import SegmentedFileLog

log = logging.getLogger(__name__)


class FiloServer:
    def __init__(self, config: ServerConfig):
        self.config = config
        if config.resilience:
            from filodb_tpu.utils import resilience
            resilience.configure(**config.resilience)
        if config.governor:
            from filodb_tpu.utils import governor
            governor.configure(**config.governor)
        if config.tracing:
            from filodb_tpu.utils import tracing
            tracing.configure(**config.tracing)
        self.watchdog = None
        os.makedirs(config.data_dir, exist_ok=True)
        self.store_server = None
        if config.store_remote:
            # remote durability tier (reference: CassandraColumnStore role)
            from filodb_tpu.core.store.remotestore import (
                RemoteColumnStore,
                RemoteMetaStore,
            )
            host, port = config.store_remote.rsplit(":", 1)
            self.column_store = RemoteColumnStore(host, int(port))
            self.meta_store = RemoteMetaStore(host, int(port))
        else:
            if config.store.get("backend") == "object":
                # S3-compatible durable tier: write-behind segment upload
                # with CRC32C tripwires (core/store/objectstore.py)
                self.column_store, self.meta_store = open_object_store(
                    config.store, config.data_dir)
            else:
                self.column_store = LocalDiskColumnStore(
                    os.path.join(config.data_dir, "columnstore"))
                self.meta_store = LocalDiskMetaStore(
                    os.path.join(config.data_dir, "columnstore"))
            if config.store_server_port:
                from filodb_tpu.core.store.remotestore import (
                    ChunkStoreServer,
                )
                self.store_server = ChunkStoreServer(
                    host="0.0.0.0", port=config.store_server_port,
                    backing=self.column_store, meta=self.meta_store).start()
        self.memstore = TimeSeriesMemStore(self.column_store, self.meta_store)
        self.node = Node(config.node_name, self.memstore)
        self.cluster = FilodbCluster()
        self.logs: dict[tuple[str, int], SegmentedFileLog] = {}
        self.http: FiloHttpServer | None = None
        self.gateway: GatewayServer | None = None
        self.executor: PlanExecutorServer | None = None
        self.selfmon = None
        self.mesh_supervisor = None  # multi-process mesh worker processes
        self.mesh_runtime = None     # root-side descriptor router
        self._setup_meta_dataset()

    def _setup_meta_dataset(self) -> None:
        """Register the ``_meta`` self-monitoring dataset when selfmon is
        enabled. Appended AFTER the user datasets: the gateway and the
        rules default-dataset both bind to the FIRST configured dataset,
        and that must stay the user's."""
        sm_cfg = self.config.selfmon or {}
        if not sm_cfg.get("enabled") or "_meta" in self.config.datasets:
            return
        from filodb_tpu.core.store.config import IngestionConfig, StoreConfig
        self.config.datasets["_meta"] = IngestionConfig(
            dataset="_meta",
            num_shards=int(sm_cfg.get("num_shards", 1)),
            min_num_nodes=1,
            store=StoreConfig(groups_per_shard=4))
        self.config.spreads["_meta"] = 0

    def _wal_path(self, dataset: str, shard: int) -> str:
        root = self.config.wal_dir or os.path.join(self.config.data_dir,
                                                   "wal")
        return os.path.join(root, dataset, f"shard-{shard}")

    def _shard_log(self, dataset: str, shard: int):
        key = (dataset, shard)
        if key not in self.logs:
            if self.config.wal_kafka:
                # external Kafka broker: topic per dataset, partition ==
                # shard (reference KafkaIngestionStream contract)
                from filodb_tpu.kafka.kafka_protocol import KafkaReplayLog
                host, port = self.config.wal_kafka.rsplit(":", 1)
                self.logs[key] = KafkaReplayLog(host, int(port), dataset,
                                                shard)
            elif self.config.wal_remote:
                # networked log (the Kafka contract): no shared FS needed
                from filodb_tpu.kafka.log_server import RemoteLog
                host, port = self.config.wal_remote.rsplit(":", 1)
                self.logs[key] = RemoteLog(host, int(port), dataset, shard)
            else:
                # members tail segments the gateway host appends to on the
                # shared wal_dir: their view must be read-only (an
                # append-mode open would run torn-tail recovery against a
                # live file)
                tailer = bool(self.config.seeds) \
                    and not self.config.gateway_port
                self.logs[key] = SegmentedFileLog(
                    self._wal_path(dataset, shard),
                    fsync=self.config.wal_fsync, read_only=tailer)
        return self.logs[key]

    def _start_mesh_workers(self, cfg, services: dict) -> None:
        """Boot the multi-process mesh runtime (coordinator role only):
        spawn N worker processes each owning a contiguous shard slice,
        then attach the descriptor router to the dataset's query service.
        Workers that never come up cost nothing at query time — the
        runtime's per-worker breakers route every query to the
        single-process engines until the slice answers."""
        mw = dict(cfg.mesh_workers or {})
        if not mw.get("enabled") or not services:
            return
        ds = mw.get("dataset") or next(iter(cfg.datasets))
        if ds not in services:
            log.warning("mesh_workers.dataset %r not served here; "
                        "multi-process mesh disabled", ds)
            return
        ing = cfg.datasets[ds]
        seed = mw.get("seed") or None
        config_path = None
        if not seed:
            # minimal worker config: shared WAL location + the dataset's
            # shard/store shape (workers recover-then-tail read-only)
            import dataclasses as _dc
            config_path = os.path.join(cfg.data_dir,
                                       "mesh_worker_config.json")
            os.makedirs(cfg.data_dir, exist_ok=True)
            with open(config_path, "w") as f:
                json.dump({"data_dir": cfg.data_dir,
                           "wal_dir": cfg.wal_dir,
                           "datasets": {ds: {
                               "num_shards": ing.num_shards,
                               "store": _dc.asdict(ing.store)}}}, f)
        from filodb_tpu.coordinator.mesh_cluster import MeshClusterRuntime
        from filodb_tpu.parallel.multiproc import MeshWorkerSupervisor
        sup = MeshWorkerSupervisor(
            dataset=ds, num_shards=ing.num_shards,
            workers=int(mw.get("workers", 2)),
            base_port=int(mw.get("base_port", 0)),
            config_path=config_path, seed=seed).spawn()
        try:
            sup.wait_ready(timeout_s=float(mw.get("ready_timeout_s",
                                                  120.0)))
        except (TimeoutError, RuntimeError) as e:
            # degraded boot: serve single-process until workers answer
            log.warning("mesh workers not ready (%s); serving via "
                        "single-process engines until they are", e)
        self.mesh_supervisor = sup
        self.mesh_runtime = MeshClusterRuntime(
            self.memstore, ds, ing.num_shards, sup.addresses(),
            timeout=float(mw.get("timeout_s", 30.0)))
        services[ds].mesh_cluster = self.mesh_runtime

    @staticmethod
    def _build_notifier(notify_cfg: dict):
        """Webhook egress for alert transitions; None when unconfigured
        (the common case — notifications stay opt-in per deployment)."""
        url = notify_cfg.get("webhook_url")
        if not url:
            return None
        from filodb_tpu.rules.notify import WebhookNotifier
        from filodb_tpu.utils.resilience import RetryPolicy
        return WebhookNotifier(
            url, timeout_s=float(notify_cfg.get("timeout_s", 5.0)),
            retry_policy=RetryPolicy(
                max_attempts=int(notify_cfg.get("max_attempts", 4)),
                base_backoff_s=0.1, max_backoff_s=2.0),
            queue_depth=int(notify_cfg.get("queue_depth", 256)))

    @staticmethod
    def _default_meta_alerts(sm_cfg: dict) -> dict:
        """The shipped self-monitoring alert group, evaluated over
        ``_meta`` like any user group: shard ingest lag and an open
        circuit breaker — the two signals that mean "this node is no
        longer keeping up / no longer talking to a peer"."""
        thr = float(sm_cfg.get("lag_alert_threshold_s", 60.0))
        return {
            "name": "selfmon_default",
            "dataset": "_meta",
            "interval": sm_cfg.get("alert_interval", "5s"),
            "rules": [
                {"alert": "FilodbIngestLagHigh",
                 "expr": f"max(filodb_ingest_lag_seconds) > {thr}",
                 "for": sm_cfg.get("lag_alert_for", "30s"),
                 "labels": {"severity": "warning"},
                 "annotations": {"summary":
                                 "shard ingest lag above threshold"}},
                {"alert": "FilodbBreakerOpen",
                 "expr": "max(filodb_breaker_state) >= 2",
                 "for": "0s",
                 "labels": {"severity": "warning"},
                 "annotations": {"summary":
                                 "a circuit breaker to a peer is open"}},
            ],
        }

    # -- control handlers (member side; reference NodeCoordinatorActor) --

    def _handle_start_shard(self, dataset: str, shard: int):
        cfg = self.config.datasets[dataset]
        self.node.start_shard(dataset, shard, cfg,
                              self._shard_log(dataset, shard))
        return True

    def _handle_stop_shard(self, dataset: str, shard: int):
        self.node.stop_shard(dataset, shard)
        return True

    def _handle_prepare_handoff(self, dataset: str, shard: int):
        """Migration source side: flush + drain the shard's durable state
        and return its replay offset (coordinator/migration.py SYNC)."""
        return self.node.prepare_handoff(dataset, shard)

    def _handle_shard_offset(self, dataset: str, shard: int):
        return self.node.shard_offset(dataset, shard)

    def _handle_migration_status(self, dataset: str):
        """Coordinator side: in-flight migrations for the CLI/shardmap."""
        return [mig.snapshot() for (d, _s), mig in
                self.cluster.migrations.items() if d == dataset]

    def _handle_shard_status(self, dataset: str):
        out = []
        for (d, s), w in self.node._workers.items():
            if d == dataset:
                out.append((s, "active" if w.caught_up.is_set()
                            else "recovery"))
        return out

    def _handle_shard_events(self, dataset: str, since_seq: int,
                             epoch: str | None = None):
        """Sequenced shard-event feed for member subscribers (reference
        StatusActor ack/resync): events after ``since_seq``, or a full
        snapshot when the follower fell behind the retained window or its
        epoch predates a coordinator restart."""
        sm = self.cluster.shard_managers.get(dataset)
        if sm is None:
            return ([], since_seq, False, epoch)
        events, seq, resynced, ep = sm.events_since(since_seq, epoch)
        # 6-tuples since replica sets: old 4-field readers were removed in
        # the same change (both ends of this wire ship together), and the
        # subscriber unpacks with *rest so further growth stays compatible
        return ([(e.shard, e.status.name, e.node, e.progress,
                  e.replica, e.watermark)
                 for e in events], seq, resynced, ep)

    def _handle_role(self):
        """(role, coord_host, coord_port) — consul bootstrap probes this
        to find an ESTABLISHED cluster before electing by address. A node
        still booting answers 'undecided'."""
        if getattr(self, "is_coordinator", False):
            return ("coordinator", None, None)
        ca = getattr(self, "_coord_addr", None)
        if ca is not None:
            return ("member", ca[0], ca[1])
        return ("undecided", None, None)

    def _handle_join(self, name: str, host: str, control_port: int):
        """Coordinator side: a remote member joined (reference
        NodeClusterActor member-up). Shard assignment (which calls back to
        the member) runs off the handler thread so the join reply isn't held
        hostage to the member's own startup."""
        import threading
        from filodb_tpu.coordinator.bootstrap import RemoteNodeHandle

        def do_join():
            try:
                self.cluster.join(RemoteNodeHandle(name, host, control_port))
            except Exception:
                log.exception("join of %s failed", name)

        threading.Thread(target=do_join, daemon=True).start()
        return True

    def start(self) -> "FiloServer":
        cfg = self.config
        if cfg.wal_server_port:
            # broker role: serve this node's WAL dir over TCP (reference
            # Kafka broker analog)
            from filodb_tpu.kafka.log_server import LogServer
            root = cfg.wal_dir or os.path.join(cfg.data_dir, "wal")
            self.log_server = LogServer(root,
                                        port=cfg.wal_server_port).start()
            if not cfg.wal_remote:
                # the broker's own shards go through the server too — one
                # owner per log file
                cfg.wal_remote = f"127.0.0.1:{self.log_server.port}"
        # control/executor port: plan shipping + shard lifecycle messages
        self.executor = PlanExecutorServer(
            self.memstore, port=cfg.executor_port,
            extra_handlers={
                "start_shard": self._handle_start_shard,
                "stop_shard": self._handle_stop_shard,
                "shard_status": self._handle_shard_status,
                "shard_events": self._handle_shard_events,
                "prepare_handoff": self._handle_prepare_handoff,
                "shard_offset": self._handle_shard_offset,
                "migration_status": self._handle_migration_status,
                "join": self._handle_join,
                "role": self._handle_role,
            }).start()
        self.node.executor_port = self.executor.port
        self._consul = None
        self._consul_registered = False
        if cfg.consul:
            # Consul-backed seed discovery (reference akka-bootstrapper
            # Consul strategy). Register FIRST, then decide the role:
            #  - any discovered node answering the "role" control query as
            #    coordinator (or a member pointing at one) is joined — an
            #    ESTABLISHED cluster always wins, regardless of boot order;
            #  - otherwise (everyone racing or unreachable), the lowest
            #    (host, port) forms the cluster and the rest join it — the
            #    reference's sorted head-seed election.
            from filodb_tpu.coordinator.bootstrap import ConsulDiscovery
            from filodb_tpu.coordinator.remote import RemotePlanDispatcher
            self._consul = ConsulDiscovery(
                host=cfg.consul.get("host", "127.0.0.1"),
                port=int(cfg.consul.get("port", 8500)),
                service_name=cfg.consul.get("service", "filodb"))
            adv = cfg.consul.get("advertise", "127.0.0.1")
            me = (adv, self.executor.port)
            try:
                self._consul.register(cfg.node_name, adv,
                                      self.executor.port)
                self._consul_registered = True
            except OSError as e:
                log.warning("consul register failed: %s", e)
            if not cfg.seeds:
                others = sorted(t for t in self._consul.discover()
                                if tuple(t) != me)
                coord_addr = None
                for h, p in others:
                    try:
                        role, ch, cp = RemotePlanDispatcher(h, p).call(
                            "role")
                    except (ConnectionError, OSError, RuntimeError):
                        continue
                    if role == "coordinator":
                        coord_addr = (h, p)
                        break
                    if role == "member" and ch:
                        coord_addr = (ch, cp)
                        break
                if coord_addr is not None:
                    cfg.seeds = [f"{coord_addr[0]}:{coord_addr[1]}"]
                elif others and min(others) < me:
                    cfg.seeds = [f"{h}:{p}" for h, p in others]
                # else: we sort lowest (or are alone) -> form the cluster
                log.info("consul discovery: role=%s seeds=%s",
                         "member" if cfg.seeds else "coordinator",
                         cfg.seeds)
        # role is decided once seeds are final; the "role" control query
        # (consul bootstrap of later nodes) depends on this being set for
        # every node, not just failover-enabled ones
        self.is_coordinator = not cfg.seeds
        services = {}
        self.rule_managers: dict[str, RuleManager] = {}
        if cfg.seeds:
            # member role: register with the coordinator; shard assignments
            # arrive as start_shard control messages
            from filodb_tpu.coordinator.remote import RemotePlanDispatcher
            joined = False
            for seed in cfg.seeds:
                host, port = seed.rsplit(":", 1)
                try:
                    RemotePlanDispatcher(host, int(port)).call(
                        "join", cfg.node_name, "127.0.0.1",
                        self.executor.port)
                    joined = True
                    self._coord_addr = (host, int(port))
                    break
                except (ConnectionError, OSError, RuntimeError) as e:
                    log.warning("seed %s unreachable: %s", seed, e)
            if not joined:
                raise RuntimeError("could not join any seed")
            # mirror the coordinator's shard map locally (reference
            # StatusActor subscription with ack/resync); members serve
            # cluster-status queries from this mirror
            from filodb_tpu.coordinator.bootstrap import (
                ShardUpdateSubscriber,
            )
            self.shard_subscribers = {}
            for name, ing_cfg in cfg.datasets.items():
                self.shard_subscribers[name] = ShardUpdateSubscriber(
                    name, ing_cfg.num_shards,
                    RemotePlanDispatcher(host, int(port)))
            import threading as _th
            self._sub_stop = _th.Event()

            def poll_loop():
                while not self._sub_stop.wait(1.0):
                    for sub in self.shard_subscribers.values():
                        try:
                            sub.poll()
                        except Exception:
                            log.debug("shard-update poll failed",
                                      exc_info=True)

            _th.Thread(target=poll_loop, daemon=True,
                       name="shard-updates").start()
        else:
            # coordinator role: own the cluster singleton
            mig_cfg = cfg.migration or {}
            self.cluster.auto_rebalance = bool(
                mig_cfg.get("auto_rebalance", False))
            self.cluster.migration_lag_threshold = int(
                mig_cfg.get("lag_threshold", 0))
            self.cluster.migration_catchup_timeout_s = float(
                mig_cfg.get("catchup_timeout_s", 30.0))
            rep_cfg = cfg.replication or {}
            self.cluster.replication = int(rep_cfg.get("n_replicas", 0))
            self.cluster.replica_in_sync_lag = int(
                rep_cfg.get("in_sync_lag", 0))
            self.cluster.replica_hedge_s = float(
                rep_cfg.get("hedge_s", 0.05))
            self.cluster.replica_durable_sync_s = float(
                rep_cfg.get("durable_sync_s", 5.0))
            self.cluster.join(self.node)
            from filodb_tpu.coordinator.bootstrap import poll_remote_statuses
            for name, ing_cfg in cfg.datasets.items():
                logs = {s: self._shard_log(name, s)
                        for s in range(ing_cfg.num_shards)}
                self.cluster.setup_dataset(ing_cfg, logs)
                services[name] = self.cluster.query_service(
                    name, cfg.spreads.get(name, 1),
                    engine=cfg.engines.get(name, "mesh"),
                    result_cache=cfg.result_cache)
                eng = services[name].mesh_engine
                if eng is not None:
                    # claim and name the device at boot: a server that
                    # cannot reach it fails here, not at its first query
                    mesh = eng.ensure_mesh()
                    dev = mesh.devices.flat[0]
                    log.info("dataset %s: %s engine on %d %s device(s) "
                             "(%s), mesh %s", name, cfg.engines.get(name),
                             mesh.devices.size, dev.platform,
                             dev.device_kind, dict(mesh.shape))
                self.cluster.on_heartbeat.append(
                    lambda n=name: poll_remote_statuses(self.cluster, n))
            # adaptive planner: load persisted per-dataset cost estimates
            # and register the live retry-after provider before any query
            # admission happens — restarts keep learned routing
            from filodb_tpu.coordinator import adaptive_planner
            for name in cfg.datasets:
                adaptive_planner.install(name, self.meta_store,
                                         cfg.cost_model)
            self.cluster.start_failure_detector()
            self._start_mesh_workers(cfg, services)
            # standing queries: one RuleManager per dataset with groups,
            # writing outputs through the shard WAL (first-class series)
            rules_cfg = dict(cfg.rules or {})
            sm_cfg = cfg.selfmon or {}
            groups_cfg = list(rules_cfg.get("groups") or [])
            if sm_cfg.get("enabled") and sm_cfg.get("default_alerts", True):
                groups_cfg.append(self._default_meta_alerts(sm_cfg))
            rules_cfg["groups"] = groups_cfg
            if groups_cfg:
                first_ds = next(iter(cfg.datasets))
                by_ds: dict[str, list] = {}
                for grp in load_groups(rules_cfg, first_ds):
                    by_ds.setdefault(grp.dataset, []).append(grp)
                notify_cfg = rules_cfg.get("notify", {}) or {}
                for ds, grps in by_ds.items():
                    ing = cfg.datasets[ds]
                    sink = LogSink(
                        {s: self._shard_log(ds, s)
                         for s in range(ing.num_shards)},
                        ing.num_shards, cfg.spreads.get(ds, 1))
                    # _meta carries only selfmon samples stamped at tick
                    # time: the default 5-minute out-of-order allowance
                    # would hold alert evaluation that far behind the
                    # ingest clock for no reason
                    ooo = (int(sm_cfg.get("ooo_allowance_ms", 2_000))
                           if ds == "_meta" else None)
                    self.rule_managers[ds] = RuleManager(
                        services[ds], sink, grps,
                        ooo_allowance_ms=ooo,
                        max_catchup_steps=int(
                            rules_cfg.get("max_catchup_steps", 512)),
                        notifier=self._build_notifier(notify_cfg),
                    ).start(float(rules_cfg.get("tick_s", 1.0)))
            if sm_cfg.get("enabled"):
                from filodb_tpu.rules.manager import LogSink as _MetaSink
                from filodb_tpu.utils.selfmon import MetaMonitor
                ing = cfg.datasets["_meta"]
                meta_sink = _MetaSink(
                    {s: self._shard_log("_meta", s)
                     for s in range(ing.num_shards)},
                    ing.num_shards, cfg.spreads.get("_meta", 0))
                self.selfmon = MetaMonitor(
                    meta_sink,
                    interval_s=float(sm_cfg.get("interval_s", 15.0)),
                    node=cfg.node_name,
                    instance=f"{cfg.node_name}:{cfg.http_port}",
                    include_buckets=bool(sm_cfg.get("include_buckets",
                                                    False)))
                self.selfmon.start()
        shard_maps = {
            name: (lambda n=name: self.shard_subscribers[n].mapper)
            for name in getattr(self, "shard_subscribers", {})
        }
        if cfg.http_impl == "fast":
            from filodb_tpu.http.fastserver import FastHttpServer
            http_cls = FastHttpServer
        else:
            http_cls = FiloHttpServer
        self.http = http_cls(services, port=cfg.http_port,
                             cluster=self.cluster
                             if not cfg.seeds else None,
                             shard_maps=shard_maps,
                             reuse_port=cfg.http_reuse_port,
                             response_cache=cfg.http_response_cache,
                             rule_managers=self.rule_managers).start()
        if cfg.gateway_port:
            first = next(iter(cfg.datasets.values()))
            sink = ContainerSink(
                {s: self._shard_log(first.dataset, s)
                 for s in range(first.num_shards)},
                first.num_shards, cfg.spreads.get(first.dataset, 1),
                dataset=first.dataset)
            self.gateway = GatewayServer(sink, port=cfg.gateway_port).start()
        # memory-pressure watchdog: write-buffer-pool occupancy and result-
        # cache bytes drive the governor's ok → degraded → critical states;
        # degraded evicts the result caches and tightens admission,
        # critical sheds gateway ingest and new expensive queries
        import weakref
        from filodb_tpu.utils.governor import MemoryWatchdog
        self.watchdog = MemoryWatchdog()
        memstore = self.memstore
        datasets = list(cfg.datasets)

        def buffer_pool_utilization():
            worst = None
            for name in datasets:
                for shard in memstore.shards_for(name):
                    for pool in getattr(shard, "buffer_pools", {}).values():
                        frac = pool.in_use / max(1, pool.cap)
                        worst = frac if worst is None else max(worst, frac)
            return worst

        self.watchdog.add_source("write_buffer_pools",
                                 buffer_pool_utilization)
        for name, svc in services.items():
            rc = getattr(svc, "result_cache", None)
            if rc is None:
                continue
            rc_ref = weakref.ref(rc)

            def cache_fraction(rc_ref=rc_ref):
                rc = rc_ref()
                if rc is None:
                    return None
                return rc.nbytes / max(1, rc.config.max_bytes)

            self.watchdog.add_source(f"result_cache.{name}", cache_fraction)

        def evict_caches(_state):
            for svc in services.values():
                rc = getattr(svc, "result_cache", None)
                if rc is not None:
                    rc.clear()

        self.watchdog.on_degraded.append(evict_caches)
        if not cfg.seeds:
            # PR 4 watchdog → PR 6 rebalance: a node going CRITICAL sheds
            # whole shards to peers via live migration, not just caches.
            # Runs off the watchdog thread — migrations block through
            # catch-up and must not stall pressure sampling.
            import threading as _th2
            cluster, me = self.cluster, cfg.node_name

            def shed_on_pressure(state):
                if state != "critical" or len(cluster.nodes) < 2:
                    return
                _th2.Thread(target=lambda: cluster.shed_load(me),
                            daemon=True, name="shed-load").start()

            self.watchdog.on_degraded.append(shed_on_pressure)
        # per-tenant active-series gauges summed over this node's shards
        from filodb_tpu.utils.governor import register_tenant_series_gauges
        register_tenant_series_gauges(
            lambda: [sh for name in datasets
                     for sh in memstore.shards_for(name)])
        self.watchdog.start()
        if os.environ.get("FILODB_PROFILER"):
            # built-in sampling profiler (reference SimpleProfiler started
            # from FiloServer.start)
            from filodb_tpu.utils.profiler import SimpleProfiler
            self.profiler = SimpleProfiler().start()
        if cfg.enable_failover:
            self._setup_failover()
        if cfg.downsample and not cfg.seeds:
            self._setup_downsampling(services)
        if not cfg.seeds:
            # tier federation wraps whatever planner the dataset ended up
            # with (raw-only or raw+downsample) — must run AFTER the
            # downsample plane so it can absorb the ds planner as a tier
            self._setup_federation(services)
        log.info("FiloServer up: http=%d executor=%d role=%s", self.http.port,
                 self.executor.port, "member" if cfg.seeds else "coordinator")
        return self

    # -- downsampling plane (reference DownsamplerMain scheduled job +
    #    LongTimeRangePlanner query routing) -------------------------------

    def _setup_downsampling(self, services: dict):
        import threading
        import time as _time
        from filodb_tpu.coordinator.longtime_planner import (
            LongTimeRangePlanner,
        )
        from filodb_tpu.coordinator.planner import SingleClusterPlanner
        from filodb_tpu.core.downsample import (
            DownsampledTimeSeriesStore,
            DownsamplerJob,
        )
        cfg = self.config
        self._ds_threads = []
        for dataset, ds_cfg in cfg.downsample.items():
            ing = cfg.datasets[dataset]
            resolutions = tuple(ds_cfg.get("resolutions_ms",
                                           (300_000, 3_600_000)))
            schedule_s = ds_cfg.get("schedule_s", 6 * 3600)
            raw_retention = ds_cfg.get("raw_retention_ms",
                                       ing.store.retention_ms)
            job = DownsamplerJob(self.column_store, dataset,
                                 ing.num_shards, resolutions,
                                 meta_store=self.meta_store)

            def runner(job=job, schedule_s=schedule_s):
                while True:
                    now_ms = int(_time.time() * 1000)
                    try:
                        # checkpointed: a restart resumes from the last
                        # persisted watermark, re-covering any window lost
                        # to a crash between raw flush and ds run
                        job.catch_up(now_ms)
                    except Exception:
                        log.exception("downsampler job failed")
                    _time.sleep(schedule_s)

            t = threading.Thread(target=runner, daemon=True,
                                 name=f"downsampler-{dataset}")
            t.start()
            self._ds_threads.append(t)
            # queries split raw vs downsample at the raw-retention boundary
            svc = services.get(dataset)
            if svc is not None:
                from filodb_tpu.core.downsample.downsampler import (
                    ds_dataset_name,
                )
                raw_planner = svc.planner
                dispatcher = getattr(raw_planner, "dispatcher_for_shard",
                                     None)
                if ds_cfg.get("streaming"):
                    # streaming rollups live in co-sharded memstore datasets
                    ds_planner = SingleClusterPlanner(
                        dataset, ing.num_shards,
                        cfg.spreads.get(dataset, 1),
                        dispatcher_for_shard=dispatcher,
                        dataset_name_override=ds_dataset_name(
                            dataset, min(resolutions)))
                else:
                    ds_store = DownsampledTimeSeriesStore(
                        self.column_store, dataset, min(resolutions),
                        ing.num_shards)
                    ds_planner = SingleClusterPlanner(
                        dataset, ing.num_shards,
                        cfg.spreads.get(dataset, 1), store=ds_store)
                svc.planner = LongTimeRangePlanner(
                    raw_planner, ds_planner, raw_retention)

    # -- tier federation (query/federation.py): one query_range across
    #    memstore, the downsample tier and object-store history ------------

    def _setup_federation(self, services: dict):
        fed = dict(self.config.federation or {})
        # opt-in: routing the hot tier by configured memory retention is
        # only safe when the operator asserts data past that horizon is
        # durably uploaded; without an explicit horizon the memstore (or
        # the downsample wiring's LongTimeRangePlanner) serves everything
        if not fed.get("enabled", True) or not fed.get("mem_retention_ms"):
            return
        from filodb_tpu.coordinator.longtime_planner import (
            LongTimeRangePlanner,
        )
        from filodb_tpu.coordinator.tiered_planner import (
            build_tiered_planner,
        )
        cfg = self.config
        for dataset, svc in services.items():
            if dataset.startswith("_"):
                continue  # _meta self-monitoring stays memstore-only
            ing = cfg.datasets.get(dataset)
            if ing is None:
                continue
            mem_retention = fed["mem_retention_ms"]
            raw_planner, ds_planner, raw_retention = svc.planner, None, None
            if isinstance(svc.planner, LongTimeRangePlanner):
                raw_planner = svc.planner.raw_planner
                ds_planner = svc.planner.ds_planner
                raw_retention = svc.planner.raw_retention_ms
            svc.planner = build_tiered_planner(
                raw_planner, self.column_store, dataset, ing.num_shards,
                cfg.spreads.get(dataset, 1),
                mem_retention_ms=int(mem_retention),
                raw_retention_ms=raw_retention,
                ds_planner=ds_planner,
                odp_max_chunks=int(fed.get("odp_max_chunks", 10_000)),
                refresh_s=float(fed.get("refresh_s", 60.0)))
            log.info("federation: %s routed across memstore%s/objectstore "
                     "(mem floor %dms)", dataset,
                     "/downsample" if ds_planner is not None else "",
                     mem_retention)

    # -- singleton failover (reference ClusterSingletonFailoverSpec) --------

    def _registry(self):
        from filodb_tpu.coordinator.bootstrap import MemberRegistry
        root = self.config.wal_dir or os.path.join(self.config.data_dir,
                                                   "wal")
        return MemberRegistry(os.path.join(root, "members.txt"))

    def _setup_failover(self):
        import threading
        reg = self._registry()
        role = "member" if self.config.seeds else "coord"
        reg.register(role, self.config.node_name, "127.0.0.1",
                     self.executor.port)
        self.is_coordinator = role == "coord"
        if role == "member":
            self._failover_stop = threading.Event()
            self._failover_thread = threading.Thread(
                target=self._failover_watch, daemon=True)
            self._failover_thread.start()

    def _failover_watch(self, interval_s: float = 0.25):
        from filodb_tpu.coordinator.bootstrap import (
            alive_members,
            RemotePlanDispatcher,
        )
        reg = self._registry()
        misses = 0
        while not self._failover_stop.wait(interval_s):
            coord = reg.current_coordinator()
            if coord == self.config.node_name:
                return  # we promoted
            members = reg.members()
            entry = members.get(coord)
            if entry is not None and RemotePlanDispatcher(
                    entry[1], entry[2], timeout=1.0).ping():
                misses = 0
                continue
            misses += 1
            if misses < 3:
                continue
            alive = alive_members(reg)
            alive.pop(coord, None)
            if alive and min(alive) == self.config.node_name:
                log.warning("coordinator %s down; promoting self", coord)
                try:
                    self._promote(alive)
                except Exception:
                    log.exception("promotion failed")
                return
            misses = 0  # another member should promote; keep watching

    def _promote(self, alive: dict):
        """Become the cluster singleton: adopt running members' shards,
        reassign the dead coordinator's shards, serve queries."""
        from filodb_tpu.coordinator.bootstrap import (
            RemoteNodeHandle,
            poll_remote_statuses,
        )
        from filodb_tpu.coordinator.shard_manager import ShardManager
        from filodb_tpu.coordinator.shardmapper import ShardStatus
        cfg = self.config
        self.cluster = FilodbCluster()
        self.cluster.join(self.node)
        for name, (host, port) in alive.items():
            if name != cfg.node_name:
                self.cluster.nodes[name] = RemoteNodeHandle(name, host, port)
        for dataset, ing_cfg in cfg.datasets.items():
            logs = {s: self._shard_log(dataset, s)
                    for s in range(ing_cfg.num_shards)}
            for shard, l in logs.items():
                self.cluster.logs[(dataset, shard)] = l
            self.cluster.configs[dataset] = ing_cfg
            # degraded mode: a promoted singleton assigns to the survivors
            # even below min-num-nodes — availability over balance until
            # replacement members join
            sm = ShardManager(dataset, ing_cfg.num_shards,
                              min(ing_cfg.min_num_nodes,
                                  len(self.cluster.nodes)))
            self.cluster.shard_managers[dataset] = sm
            # adopt what's already running (incl. our own shards)
            for name, node in self.cluster.nodes.items():
                if name == cfg.node_name:
                    statuses = self._handle_shard_status(dataset)
                else:
                    try:
                        statuses = node.shard_status(dataset)
                    except (ConnectionError, OSError, RuntimeError):
                        statuses = []
                for shard, st in statuses:
                    sm.adopt(shard, name,
                             ShardStatus.ACTIVE if st == "active"
                             else ShardStatus.RECOVERY)
            # the dead coordinator's shards are unassigned: reassign
            for ev in sm.rebalance():
                self.cluster._on_event(dataset, ev)
            svc = self.cluster.query_service(
                dataset, cfg.spreads.get(dataset, 1),
                engine=cfg.engines.get(dataset, "mesh"),
                result_cache=cfg.result_cache)
            self.http.services[dataset] = svc
            self.cluster.on_heartbeat.append(
                lambda n=dataset: poll_remote_statuses(self.cluster, n))
        self.http.cluster = self.cluster
        self.cluster.start_failure_detector()
        self._registry().register("coord", cfg.node_name, "127.0.0.1",
                                  self.executor.port)
        self.is_coordinator = True

    def shutdown(self):
        if self.selfmon is not None:
            self.selfmon.stop()  # before the WALs close under its sink
        for mgr in getattr(self, "rule_managers", {}).values():
            mgr.stop()
        if getattr(self, "watchdog", None) is not None:
            self.watchdog.stop()  # also resets the governor state to OK
        if getattr(self, "_failover_stop", None) is not None:
            self._failover_stop.set()
        if getattr(self, "_sub_stop", None) is not None:
            self._sub_stop.set()  # stop the shard-update poll loop
        if self.http:
            self.http.stop()
        if self.gateway:
            self.gateway.stop()
        if self.executor:
            self.executor.stop()
        if getattr(self, "mesh_runtime", None) is not None:
            self.mesh_runtime.shutdown()
        if getattr(self, "mesh_supervisor", None) is not None:
            self.mesh_supervisor.stop()
        self.cluster.stop()
        for l in self.logs.values():
            l.close()
        if getattr(self, "log_server", None) is not None:
            self.log_server.stop()  # broker role: port, thread, open logs
        if getattr(self, "_consul", None) is not None:
            try:
                self._consul.deregister(self.config.node_name)
            except OSError:
                pass
        if self.store_server is not None:
            self.store_server.shutdown()
        self.column_store.close()
        if getattr(self, "is_coordinator", False):
            # learned cost estimates survive restarts via the metastore
            from filodb_tpu.coordinator import adaptive_planner
            for name in getattr(self.config, "datasets", {}) or {}:
                try:
                    adaptive_planner.persist(name, self.meta_store)
                except Exception:
                    log.debug("cost-model persist failed for %s", name,
                              exc_info=True)
        self.meta_store.close()


def main(argv=None):
    ap = argparse.ArgumentParser(description="filodb_tpu standalone server")
    ap.add_argument("--config", help="server config JSON", default=None)
    args = ap.parse_args(argv)
    logging.basicConfig(level=logging.INFO)
    from filodb_tpu import startup
    log.info("jax compile cache: %s", startup.configure_jax())
    server = FiloServer(ServerConfig.load(args.config)).start()
    stop = []
    signal.signal(signal.SIGTERM, lambda *a: stop.append(1))
    signal.signal(signal.SIGINT, lambda *a: stop.append(1))
    import time
    while not stop:
        time.sleep(0.5)
    server.shutdown()


if __name__ == "__main__":
    sys.exit(main())
