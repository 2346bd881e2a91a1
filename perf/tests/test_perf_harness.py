"""Tests of the benchmark harness itself. Run with

    JAX_PLATFORMS=cpu python -m pytest perf/tests -q

They live under ``perf/`` because the benchmark's files may not sit
elsewhere; the repository's tier-1 command (``pytest tests/``) does not
collect them. Nothing here touches JAX in the test process: the cells run as
child processes, so no topology is described and no chip is taken at import.
"""

from __future__ import annotations

import http.server
import json
import os
import re
import shutil
import subprocess
import sys
import threading

import numpy as np
import pytest

PERF = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(PERF)
sys.path.insert(0, PERF)

import client  # noqa: E402
import reference  # noqa: E402
import traffic  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def rehearse(root: str, cell: str, trace: int, tmp_path) -> tuple:
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
               TMPDIR=str(tmp_path))
    p = subprocess.run(
        [sys.executable, os.path.join(root, "perf", "run.py"), "--workload",
         cell, "--seed", str(2**31 + 11), "--seconds", "2", "--trace",
         str(trace), "--rehearsal"],
        capture_output=True, text=True, env=env, cwd=root, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1]), p.stdout


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", [0, 1])
def test_cell_rehearses_end_to_end(cell, trace, tmp_path):
    before = set(os.listdir(PERF))
    line, out = rehearse(ROOT, cell, trace, tmp_path)
    assert line["correct"] is True and line["rehearsal"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    # counts and shares only: never a timing, never a device-named metric
    for name, m in line["metrics"].items():
        assert by_name[name]["source"] == "program_counter", name
        assert m["unit"] in ("%", "count"), name
    if trace:
        assert line["metrics"]["compiles_in_window"]["value"] == 0
        assert line["metrics"]["mesh_hit_share"]["value"] == 100.0
    assert "busy_s" not in line["device"] and "breakdown" not in line
    assert '"seconds"' not in out and '_s"' not in out
    assert set(os.listdir(PERF)) - {"__pycache__"} == before - {"__pycache__"}


def test_names_units_and_files():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert os.path.isfile(os.path.join(
            PERF, "layer_metrics", f"{m['name']}.py")), m["name"]
    configs = {c["name"]: c for c in BENCH["configs"]}
    for c in configs.values():
        assert NAME.match(c["name"])
        with open(os.path.join(ROOT, c["file"])) as f:
            conf = json.load(f)
        assert set(c["reduced"]) == set(conf["reduced"]) <= set(
            conf["params"]) | set(conf["layout"])
        assert os.path.isfile(os.path.join(
            PERF, "generators", f"{conf['generator']}.py"))
    for w in BENCH["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        with open(os.path.join(PERF, "cells", f"{w['name']}.json")) as f:
            assert json.load(f)["config"] == w["config"] in configs


def test_added_files_run_without_an_edit(tmp_path):
    """A later PR's cell, configuration and per-layer metric: files added,
    one entry each in BENCHMARK.json, nothing that is there edited."""
    root = tmp_path / "copy"
    shutil.copytree(PERF, root / "perf",
                    ignore=shutil.ignore_patterns("__pycache__"))
    conf = json.load(open(os.path.join(PERF, "configs", "tsbs-cpu-10k.json")))
    conf["name"] = "tsbs-cpu-tiny"
    conf["rehearsal"]["params"]["hosts"] = 24
    json.dump(conf, open(root / "perf/configs/tsbs-cpu-tiny.json", "w"))
    cell = json.load(open(os.path.join(
        PERF, "cells", "tsbs-cpu-10k.double-groupby-1.json")))
    cell["config"] = "tsbs-cpu-tiny"
    cell["panels"][0]["promql"] = \
        "max by (region)(max_over_time(cpu_usage_system[5m]))"
    cell["panels"][0]["check"].update(
        metric="cpu_usage_system", fn="max_over_time", window_s=300,
        agg="max", by="region", sample_groups=None)
    cell["range_s"], cell["step_s"] = 1800, 300
    json.dump(cell, open(root / "perf/cells/tsbs-cpu-tiny.groupby-region.json",
                         "w"))
    (root / "perf/layer_metrics/answered_count.py").write_text(
        "def read(spans, counters, trace, run):\n"
        "    return float(len(run['latencies_ms']))\n")
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({"name": "tsbs-cpu-tiny", "source": "test",
                             "file": "perf/configs/tsbs-cpu-tiny.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tsbs-cpu-tiny.groupby-region",
                               "config": "tsbs-cpu-tiny",
                               "traffic": "groupby-region", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "answered_count", "unit": "count",
                               "better": "higher",
                               "source": "program_counter", "layer": "test",
                               "moves": "queries_per_s"})
    if "fleet-110k.dash-review" not in CELLS:
        # written and rehearsed, waiting for its sets on the chip (PERF.md)
        bench["configs"].append({"name": "fleet-110k", "source": "test",
                                 "file": "perf/configs/fleet-110k.json",
                                 "reduced": ["num_shards"], "why": "test"})
        bench["workloads"].append({"name": "fleet-110k.dash-review",
                                   "config": "fleet-110k",
                                   "traffic": "dash-review", "chips": 1,
                                   "why": "test"})
    json.dump(bench, open(root / "BENCHMARK.json", "w"))
    line, _ = rehearse(str(root), "tsbs-cpu-tiny.groupby-region", 1, tmp_path)
    assert line["correct"] is True
    assert line["metrics"]["answered_count"]["value"] == line["attempted"]
    line, _ = rehearse(str(root), "fleet-110k.dash-review", 1, tmp_path)
    assert line["correct"] is True and line["failed"] == 0
    assert line["metrics"]["result_cache_hit_share"]["value"] > 0


# ---------------------------------------------------------------------------
# the reference decides `correct`

def _tiny_fleet():
    sys.path.insert(0, os.path.join(PERF, "generators"))
    import fleet

    conf = json.load(open(os.path.join(PERF, "configs", "fleet-110k.json")))
    params = {**conf["params"], "counter_series": 300, "gauge_series": 200}
    return params, fleet.make(params, 5)


def _answer(check, metrics, params, key, start, end, step):
    """The reference's own answer, as a Prom matrix body."""
    steps, lo, _, groups = reference.evaluate(
        check, metrics, params["interval_ms"], key, start, end, step,
        np.random.default_rng(0))
    return reference.answer_body(check, steps, lo, groups)


@pytest.mark.parametrize("panel", [0, 2])
def test_a_wrong_answer_is_not_correct(panel):
    params, metrics = _tiny_fleet()
    cell = json.load(open(os.path.join(PERF, "cells",
                                       "fleet-110k.dash-review.json")))
    check = cell["panels"][panel]["check"]
    end = params["t0_sec"] + 5400
    args = (7, end - 3600, end, 60)
    body = _answer(check, metrics, params, *args)
    rng = np.random.default_rng(0)
    got = reference.check_panel(check, metrics, params["interval_ms"], *args,
                                body, rng)
    assert got["worst_rel_error"] <= check["rtol"]
    t, v = body["data"]["result"][0]["values"][20]
    body["data"]["result"][0]["values"][20] = [t, repr(float(v) * 1.001)]
    with pytest.raises(reference.Mismatch, match="outside the reference"):
        reference.check_panel(check, metrics, params["interval_ms"], *args,
                              body, rng)
    del body["data"]["result"][0]["values"][20]
    with pytest.raises(reference.Mismatch, match="gaps differ"):
        reference.check_panel(check, metrics, params["interval_ms"], *args,
                              body, rng)
    with pytest.raises(reference.Mismatch):
        reference.check_panel(check, metrics, params["interval_ms"], *args,
                              {"status": "error", "error": "x"}, rng)


@pytest.mark.parametrize("path", ["reference.py"] + sorted(
    os.path.join("forms", f) for f in os.listdir(os.path.join(PERF, "forms"))
    if f.endswith(".py")))
def test_reference_imports_nothing_of_the_program(path):
    src = open(os.path.join(PERF, path)).read()
    assert "filodb_tpu" not in src.split('"""', 2)[2]
    assert "import jax" not in src


# ---------------------------------------------------------------------------
# traffic: every seed sends the same dashboards in another order

@pytest.mark.parametrize("cell_name", sorted(
    f[:-5] for f in os.listdir(os.path.join(PERF, "cells"))))
def test_every_seed_sends_the_same_work(cell_name):
    cell = json.load(open(os.path.join(PERF, "cells", f"{cell_name}.json")))
    a = traffic.streams(cell, "timeseries", 1_599_999_360, 1)
    b = traffic.streams(cell, "timeseries", 1_599_999_360, 2**31 + 5)
    assert a == traffic.streams(cell, "timeseries", 1_599_999_360, 1)
    assert len(a) == cell["loop"]["clients"] and a != b
    per_block = cell["pool"]["block"] * len(cell["panels"])

    def first_blocks(streams, n):
        each = n * per_block // len(streams)
        return sorted(r["path"] for s in streams for r in s[:each])

    assert first_blocks(a, 3) == first_blocks(b, 3)
    lo = 1_599_999_360 + cell["end"]["first_s"]
    hi = 1_599_999_360 + cell["end"]["last_s"]
    assert all(lo <= r["end"] <= hi for s in a for r in s)


# ---------------------------------------------------------------------------
# trace_reduce on a slice recorded on a v5e (PR 22's run of the tsbs cell)

def test_trace_reduce_on_a_recorded_trace():
    code = (
        "import json, sys; sys.path.insert(0, %r); import trace_reduce\n"
        "r = trace_reduce.reduce(%r, host_spans=[('request-untraced', -1, "
        "100.0, 106.0), ('mesh-execute', 1, 100.5, 102.0)], "
        "wall_ns_at_start=100_000_000_000)\n"
        "print(json.dumps(r))"
        % (PERF, os.path.join(PERF, "tests", "data",
                              "tsbs_v5e_slice.xplane.pb.gz")))
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    r = json.loads(p.stdout.strip().splitlines()[-1])
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx(5.001053324, abs=1e-6)
    assert r["busy_s"] == pytest.approx(0.496722602, abs=1e-6)
    assert r["device_ops"][0][0] == "jit_bounds/while.13"
    assert r["device_ops"][0][1] == pytest.approx(0.163791515, abs=1e-6)
    assert len(r["device_ops"]) == 10 and len(r["idle_gaps"]) == 10
    # three device bursts, so four long gaps; the slice's idle share is 90%
    long = [g for g in r["idle_gaps"] if g[1] > 0.5]
    assert len(long) == 4
    assert sum(g[1] for g in long) == pytest.approx(
        r["window_s"] - r["busy_s"], abs=0.01)
    assert {g[0] for g in long} == {"mesh-execute", "request-untraced"}


# ---------------------------------------------------------------------------
# the client keeps the closed loop

class _Stub(http.server.ThreadingHTTPServer):
    daemon_threads = True

    def __init__(self):
        self.lock = threading.Lock()
        self.in_flight = self.max_in_flight = self.served = 0
        self.by_conn = {}
        stub = self

        class H(http.server.BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, *a):
                pass

            def do_GET(self):
                with stub.lock:
                    stub.in_flight += 1
                    stub.max_in_flight = max(stub.max_in_flight,
                                             stub.in_flight)
                    stub.by_conn.setdefault(self.client_address, []).append(
                        self.path)
                threading.Event().wait(0.01)
                bad = "bad" in self.path
                body = (b'{"status":"error"}' if bad else
                        b'{"status":"success","data":{"result":[]}}')
                with stub.lock:
                    stub.in_flight -= 1
                    stub.served += 1
                self.send_response(500 if bad else 200)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

        super().__init__(("127.0.0.1", 0), H)
        threading.Thread(target=self.serve_forever, daemon=True).start()


@pytest.mark.parametrize("clients", [1, 6])
def test_client_keeps_the_closed_loop(clients):
    stub = _Stub()
    try:
        streams = [[{"path": f"/c{c}/r{i}"} for i in range(500)]
                   for c in range(clients)]
        streams[0][1] = {"path": "/bad"}
        got = client.run(stub.server_address[1], streams, 0.5)
    finally:
        stub.shutdown()
        stub.server_close()
    reqs = got["requests"]
    assert stub.max_in_flight == clients
    assert len(stub.by_conn) == clients        # one persistent connection each
    assert stub.served == len(reqs)            # every request sent was answered
    for paths in stub.by_conn.values():        # in stream order, none skipped
        c = [s for s in streams if s[0]["path"] == paths[0]][0]
        assert paths == [r["path"] for r in c[:len(paths)]]
    assert [r for r in reqs if not r[4]] == [[0, 1] + r[2:] for r in reqs
                                             if r[:2] == [0, 1]]
    assert got["window_s"] >= 0.5
    assert all(r[2] - got["t0"] < 0.5 + 0.05 for r in reqs)
    assert all(r[2] + r[3] <= got["t_end"] + 0.05 for r in reqs)
    # closed loop: a client's next send follows its last answer
    for c in range(clients):
        mine = sorted((r for r in reqs if r[0] == c), key=lambda r: r[1])
        for a, b in zip(mine, mine[1:]):
            assert b[2] >= a[2] + a[3] - 0.005
