"""Standalone server + CLI smoke tests (reference: FiloServer boot +
filo-cli commands)."""

import json
import socket
import time
import urllib.request

import pytest

from filodb_tpu.cli import main as cli_main
from filodb_tpu.config import ServerConfig
from filodb_tpu.standalone import FiloServer

START = 1_600_000_000


@pytest.fixture
def server(tmp_path):
    cfg_path = tmp_path / "server.json"
    cfg_path.write_text(json.dumps({
        "node_name": "test-node",
        "data_dir": str(tmp_path / "data"),
        "http_port": 0,
        "gateway_port": 0,
        "datasets": {"timeseries": {
            "num_shards": 2, "spread": 1,
            "store": {"max_chunk_size": 100, "groups_per_shard": 2}}},
    }))
    cfg = ServerConfig.load(str(cfg_path))
    # enable gateway on an ephemeral port
    object.__setattr__(cfg, "gateway_port", _free_port())
    srv = FiloServer(cfg).start()
    yield srv, tmp_path
    srv.shutdown()


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_unknown_engine_refused_at_load(tmp_path):
    """``engine`` is input from outside: a value that names no engine stops
    the boot, and the message names the two that exist."""
    cfg_path = tmp_path / "server.json"
    cfg_path.write_text(json.dumps({
        "datasets": {"timeseries": {"engine": "adaptive"}}}))
    with pytest.raises(ValueError, match="'adaptive'") as e:
        ServerConfig.load(str(cfg_path))
    assert "'mesh'" in str(e.value) and "'exec'" in str(e.value)


class TestFiloServer:
    def test_ingest_via_gateway_then_query(self, server):
        srv, tmp_path = server
        with socket.create_connection(("127.0.0.1",
                                       srv.gateway.port)) as s:
            for i in range(120):
                ts_ns = (START + i * 10) * 1_000_000_000
                s.sendall(f"cpu_usage,host=h1,_ws_=demo,_ns_=App-0 "
                          f"value={50 + i % 7} {ts_ns}\n".encode())
        # wait for the ingestion workers to drain the log
        deadline = time.monotonic() + 10
        got = 0
        while time.monotonic() < deadline:
            srv.gateway.sink.flush()
            code, body = _get(srv.http.port,
                              "/promql/timeseries/api/v1/query_range",
                              query="count_over_time(cpu_usage[10m])",
                              start=START + 1200, end=START + 1200, step=60)
            res = body["data"]["result"]
            if res and float(res[0]["values"][0][1]) >= 59:
                got = float(res[0]["values"][0][1])
                break
            time.sleep(0.1)
        assert got == 59.0  # 10m window @10s, left-exclusive

    def test_health_and_status(self, server):
        srv, _ = server
        code, body = _get(srv.http.port, "/__health")
        assert body["status"] == "healthy"
        code, body = _get(srv.http.port, "/api/v1/cluster/timeseries/status")
        assert len(body["data"]) == 2

    def test_restart_recovers_from_wal(self, server):
        srv, tmp_path = server
        with socket.create_connection(("127.0.0.1", srv.gateway.port)) as s:
            for i in range(50):
                ts_ns = (START + i * 10) * 1_000_000_000
                s.sendall(f"mem_usage,_ws_=demo,_ns_=App-0 value={i} "
                          f"{ts_ns}\n".encode())
        time.sleep(0.3)
        srv.gateway.sink.flush()
        time.sleep(0.3)
        srv.shutdown()
        # restart on the same data dir: WAL replay restores the data
        cfg = ServerConfig.load(None)
        object.__setattr__(cfg, "data_dir", str(tmp_path / "data"))
        object.__setattr__(cfg, "http_port", 0)
        cfg.datasets = {k: v for k, v in cfg.datasets.items()}
        srv2 = FiloServer(cfg).start()
        try:
            deadline = time.monotonic() + 10
            n = 0
            while time.monotonic() < deadline:
                code, body = _get(
                    srv2.http.port, "/promql/timeseries/api/v1/query_range",
                    query="count_over_time(mem_usage[10m])",
                    start=START + 500, end=START + 500, step=60)
                res = body["data"]["result"]
                if res:
                    n = float(res[0]["values"][0][1])
                    if n == 50:
                        break
                time.sleep(0.1)
            assert n == 50.0
        finally:
            srv2.shutdown()


class TestCli:
    def test_importcsv_and_promql(self, tmp_path, capsys):
        csv_path = tmp_path / "data.csv"
        lines = []
        for i in range(100):
            lines.append(f"{(START + i * 10) * 1000},{i * 1.5},"
                         f"host=h1,_ws_=demo,_ns_=App-0")
        csv_path.write_text("\n".join(lines))
        data_dir = str(tmp_path / "clidata")
        cli_main(["--data-dir", data_dir, "--num-shards", "2", "importcsv",
                  str(csv_path), "--metric", "cli_metric"])
        out = capsys.readouterr().out
        assert "imported 100 samples" in out
        cli_main(["--data-dir", data_dir, "--num-shards", "2", "promql",
                  "max_over_time(cli_metric[20m])",
                  "--start", str(START + 990), "--end", str(START + 990)])
        out = capsys.readouterr().out
        body = json.loads(out)
        assert body["data"]["result"]
        assert float(body["data"]["result"][0]["values"][0][1]) == 99 * 1.5
        cli_main(["--data-dir", data_dir, "--num-shards", "2", "list"])
        out = capsys.readouterr().out
        assert "total partitions: 1" in out
        cli_main(["--data-dir", data_dir, "--num-shards", "2",
                  "decodechunks", "--verbose"])
        out = capsys.readouterr().out
        assert "chunks" in out


def _get(port, path, **params):
    import urllib.parse
    qs = urllib.parse.urlencode(params, doseq=True)
    url = f"http://127.0.0.1:{port}{path}" + (f"?{qs}" if qs else "")
    with urllib.request.urlopen(url) as r:
        return r.status, json.loads(r.read())


class TestTopkCard:
    def test_topkcard(self, tmp_path, capsys):
        csv_path = tmp_path / "d.csv"
        csv_path.write_text("\n".join(
            f"{(START + i * 10) * 1000},{i},host=h{i % 3},_ws_=demo,_ns_=App-0"
            for i in range(30)))
        data_dir = str(tmp_path / "cd")
        cli_main(["--data-dir", data_dir, "--num-shards", "2", "importcsv",
                  str(csv_path), "--metric", "card_metric"])
        capsys.readouterr()
        cli_main(["--data-dir", data_dir, "--num-shards", "2", "topkcard",
                  "--prefix", "demo"])
        out = capsys.readouterr().out
        assert "App-0" in out and "series=3" in out


class TestServerDownsampling:
    def test_downsample_plane_boots(self, tmp_path):
        import time as _time
        cfg_path = tmp_path / "ds.json"
        cfg_path.write_text(json.dumps({
            "node_name": "ds-node", "data_dir": str(tmp_path / "d"),
            "http_port": 0, "gateway_port": 0,
            "datasets": {"timeseries": {
                "num_shards": 2, "spread": 1,
                "store": {"max_chunk_size": 50, "groups_per_shard": 2},
                "downsample": {"resolutions_ms": [300000],
                               "schedule_s": 1,
                               "raw_retention_ms": 3600000}}},
        }))
        srv = FiloServer(ServerConfig.load(str(cfg_path))).start()
        try:
            from filodb_tpu.coordinator.longtime_planner import (
                LongTimeRangePlanner,
            )
            svc = srv.http.services["timeseries"]
            assert isinstance(svc.planner, LongTimeRangePlanner)
            # feed data via the WAL, flush, let the job produce ds chunks
            from filodb_tpu.coordinator.ingestion import route_container
            from filodb_tpu.testing.data import (
                gauge_stream,
                machine_metrics_series,
            )
            keys = machine_metrics_series(2)
            for sd in gauge_stream(keys, 120, start_ms=START * 1000):
                for shard, cont in route_container(sd.container, 2,
                                                   1).items():
                    srv.logs[("timeseries", shard)].append(cont)
            deadline = _time.monotonic() + 15
            got = 0
            while _time.monotonic() < deadline:
                for node in srv.cluster.nodes.values():
                    for s in node.owned_shards("timeseries"):
                        node.memstore.get_shard("timeseries", s).flush_all()
                recs = sum(
                    len(srv.column_store.scan_part_keys(
                        "timeseries_ds_5m", s)) for s in range(2))
                if recs >= 2:
                    got = recs
                    break
                _time.sleep(0.5)
            assert got >= 2  # downsampler produced ds part keys
        finally:
            srv.shutdown()
