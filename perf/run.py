"""perf/run.py — one process, one cell, once.

    python3 perf/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``'s ``workloads``; everything that
belongs to it is found by name: ``perf/configs/<config>.json`` (sizes,
generator, layout, guarantees), ``perf/generators/<generator>.py``,
``perf/cells/<cell>.json`` (traffic) and, in a traced run,
``perf/layer_metrics/<metric>.py`` for each per-layer metric.

    python3 perf/run.py --cell-file <cell.json> --config-file <config.json>
                        [--chips N] [--metrics a,b] --seed <n> ...

runs a cell that ``BENCHMARK.json`` does not list yet, through the same
``run()``: to try a deployment on the chip before a PR lists it. The
generator is still found by the configuration's ``generator``. Its per-layer
metrics are every entry of ``per_layer`` without a ``workloads`` list plus
those ``--metrics`` names; its line carries ``"unlisted": true``.

Order of a run: start-up (compile cache) → generate from the seed → load →
the default server's query service and HTTP front over the loaded store →
warm-up of this cell's shapes → the window, driven by ``perf/client.py`` in
a child process → a seeded sample of the window's own requests re-issued and
held to the f64 reference → one JSON line. Everything before the first
measured request is ``setup_s``.

Any platform but a TPU is refused unless ``--rehearsal`` is given. A
rehearsal runs the configuration's ``rehearsal`` sizes and prints counts and
``correct``, never a timing or a device-named metric.
"""

from __future__ import annotations

import argparse
import http.client
import importlib.util
import json
import os
import shutil
import statistics
import subprocess
import sys
import tempfile
import threading
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (HERE, ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def _process_start_wall() -> float:
    """Wall clock at which this process was started (Linux), else now."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            up = float(f.read().split()[0])
        return time.time() - (up - ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return time.time()


T_PROCESS = _process_start_wall()


def say(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def load_module(kind: str, name: str):
    path = os.path.join(HERE, kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"perf_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


class CompileWatch:
    """Counts what JAX compiled, from its own monitoring events (a copy of
    ``chip_smoke.CompileWatch``, PR 21). JAX offers no way to unregister a
    listener, so make one per process."""

    def __init__(self):
        import jax

        self.compile_secs: list[float] = []
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.compile_secs.append(secs)

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def mark(self) -> tuple:
        return len(self.compile_secs), self.cache_hits, self.cache_misses

    def since(self, mark: tuple) -> dict:
        n, hits, misses = mark
        return {"programs_built": len(self.compile_secs) - n,
                "build_s": sum(self.compile_secs[n:]),
                "persistent_cache_hits": self.cache_hits - hits,
                "persistent_cache_misses": self.cache_misses - misses}


# ---------------------------------------------------------------------------
# the system under test: the default server's query path over a loaded store

def start_server(memstore, layout: dict):
    """The query service and HTTP front a default ``conf/server.json``
    starts, wired as ``FiloServer.start`` wires them, on a free loopback
    port."""
    from filodb_tpu.config import ServerConfig
    from filodb_tpu.coordinator.query_service import QueryService

    cfg = ServerConfig.load(None)
    ds = layout["dataset"]
    svc = QueryService(memstore, ds, layout["num_shards"],
                       spread=layout["spread"], engine=cfg.engines[ds],
                       result_cache=cfg.result_cache)
    if cfg.http_impl == "fast":
        from filodb_tpu.http.fastserver import FastHttpServer as Front
    else:
        from filodb_tpu.http.server import FiloHttpServer as Front
    http_front = Front({ds: svc}, port=0,
                       response_cache=cfg.http_response_cache).start()
    return svc, http_front


def counters_now() -> dict:
    from filodb_tpu.utils.metrics import render_prometheus

    from measure import parse_prometheus
    return parse_prometheus(render_prometheus())


def fetch(port: int, path: str) -> tuple[float, int, bytes]:
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=300)
    t0 = time.perf_counter()
    conn.request("GET", path)
    resp = conn.getresponse()
    body = resp.read()
    took = time.perf_counter() - t0
    conn.close()
    return took, resp.status, body


def run_client(port: int, streams_path: str, seconds: float,
               during=None) -> dict:
    """The window: ``perf/client.py`` in a child that never touches JAX.
    ``during`` runs in this process meanwhile (the profiler's slice)."""
    proc = subprocess.Popen(
        [sys.executable, os.path.join(HERE, "client.py"), "--port",
         str(port), "--streams", streams_path, "--seconds", str(seconds)],
        stdout=subprocess.PIPE, cwd=ROOT)
    try:
        if during is not None:
            during()
        out, _ = proc.communicate(timeout=seconds + 240)
    finally:
        if proc.poll() is None:
            proc.kill()
        proc.wait()
    if proc.returncode != 0:
        raise RuntimeError(f"client exited with {proc.returncode}")
    return json.loads(out)


# ---------------------------------------------------------------------------
# the traced slice

class Slice:
    """Profiles one slice from the middle of the window and snapshots the
    program's counters at both ends of it."""

    def __init__(self, out_dir: str, window_s: float, slice_s: float):
        self.out_dir = out_dir
        self.delay = 0.5 + max(0.0, (window_s - slice_s) / 2)
        self.slice_s = slice_s
        self.counters = None
        self.wall = None
        self.error = None
        self._thread = threading.Thread(target=self._run, daemon=True)

    def start(self):
        self._thread.start()

    def join(self):
        self._thread.join()
        if self.error is not None:
            raise self.error

    def _run(self):
        import jax

        try:
            time.sleep(self.delay)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.out_dir, profiler_options=opts)
            try:
                before = counters_now()
                a = time.time_ns()
                with jax.profiler.TraceAnnotation("perf_trace_start",
                                                  wall_ns=a):
                    pass
                time.sleep(self.slice_s)
                with jax.profiler.TraceAnnotation("perf_trace_end"):
                    pass
                b = time.time_ns()
                self.counters = (before, counters_now())
                self.wall = (a / 1e9, b / 1e9)
            finally:
                jax.profiler.stop_trace()
        except Exception as e:  # surfaced by join()
            self.error = e


def host_spans(entries: list, requests: list) -> list:
    """(name, depth, wall start, wall end) of the client's requests and the
    program's spans. The flight recorder keeps each span's duration and
    parent but not its start, so the spans of one query are laid one after
    another from their parent's start, in the order they were opened — exact
    where a parent's children leave no gap between them."""
    out = [("request-untraced", -1, r[2], r[2] + r[3]) for r in requests]
    for e in entries:
        end = e["when"]
        root0 = end - e["duration_ms"] / 1e3
        out.append(("query-service", 0, root0, end))
        start_of, cursor = {0: root0}, {0: root0}
        for s in e.get("spans") or []:
            parent = s["parent_id"] if s["parent_id"] in start_of else 0
            a = cursor[parent]
            b = a + s["duration_ms"] / 1e3
            cursor[parent] = b
            start_of[s["span_id"]] = cursor[s["span_id"]] = a
            out.append((s["name"], 1 + s["depth"], a, b))
    return out


# ---------------------------------------------------------------------------

def verify(cell: dict, config: dict, metrics: dict, port: int, sent: list,
           seed: int) -> dict:
    """Re-issue a seeded sample of the window's own requests, the same
    number of each panel, and hold each answer to the reference."""
    import numpy as np

    from reference import Mismatch, check_panel

    rng = np.random.default_rng(seed)
    n_panels = len(cell["panels"])
    want = cell["verify"]["requests"]
    checks, ok = [], True
    for p in range(n_panels):
        mine = [r for r in sent if r["panel"] == p]
        take = min(len(mine), -(-want // n_panels))
        for i in rng.choice(len(mine), take, replace=False):
            r = mine[int(i)]
            _, status, body = fetch(port, r["path"])
            try:
                if status != 200:
                    raise Mismatch(f"HTTP {status}: {body[:200]!r}")
                got = check_panel(
                    cell["panels"][p]["check"], metrics,
                    config["params"]["interval_ms"], r["key"],
                    r["end"] - cell["range_s"], r["end"], cell["step_s"],
                    json.loads(body), rng)
            except Mismatch as e:
                ok = False
                got = {"error": str(e)[:500]}
            checks.append({"panel": p, "key": r["key"], "end": r["end"],
                           **got})
    worst = max((c.get("worst_rel_error", 0.0) for c in checks), default=0.0)
    return {"ok": ok and bool(checks), "queries_checked": len(checks),
            "worst_rel_error": worst, "checks": checks}


def compared(cell: dict, checked: dict, failed: int) -> dict:
    """Each number that decides ``correct`` beside its limit: a panel's
    worst relative error over the answers checked (null where one was
    refused outright: the reason is under ``refused``) against its
    ``rtol``, and the requests that failed against 0."""
    out = {}
    for p, panel in enumerate(cell["panels"]):
        mine = [c for c in checked["checks"] if c["panel"] == p]
        errors = [c["error"] for c in mine if "error" in c]
        out[f"panel{p}_worst_rel_error"] = {
            "value": None if errors or not mine else max(
                c["worst_rel_error"] for c in mine),
            "limit": float(panel["check"]["rtol"]), "answers": len(mine),
            **({"refused": errors[0][:300]} if errors else {})}
    out["failed_requests"] = {"value": failed, "limit": 0}
    return out


_KEPT_TAGS = ("reused_bytes", "copied_bytes", "native_rows", "fallback_rows")


def slowest_request(done: list, t0: float, slice_wall,
                    entries: list) -> dict:
    """When the window's largest latency came, and what covered it: seconds
    into the window at which it was sent; in a traced run the profiler
    slice's start and end on the same clock (stopping a trace holds the
    process) and the longest recorded entry that ended inside the request,
    with its three longest spans. An entry far shorter than the request
    says that the wait was outside the query service."""
    _, _, sent, took, _, _ = max(done, key=lambda r: r[3])
    out = {"sent_at_s": sent - t0, "latency_ms": took * 1e3}
    if slice_wall:
        out["trace_slice_s"] = [slice_wall[0] - t0, slice_wall[1] - t0]
    inside = [e for e in entries if sent <= e["when"] <= sent + took + 0.05]
    if inside:
        e = max(inside, key=lambda e: e["duration_ms"])
        spans = sorted(e.get("spans") or [], key=lambda s: -s["duration_ms"])
        out["entry_ms"] = e["duration_ms"]
        out["spans"] = [[s["name"], s["duration_ms"]] for s in spans[:3]]
    return out


def window_detail(latencies_ms: list, entries: list, timed: bool) -> dict:
    """What the readers drop, for whoever sizes the next cell: the window's
    latency quartiles, mean, p95 and largest (a timed run only), and of the
    recorded spans the byte and row tags, a span name each — how many spans
    carried the tag, its median and its largest — and the shapes built."""
    import numpy as np

    out = {}
    if timed:
        q1, q2, q3 = statistics.quantiles(latencies_ms, n=4) \
            if len(latencies_ms) > 1 else latencies_ms * 3
        out["latency_ms"] = {
            "requests": len(latencies_ms), "min": min(latencies_ms),
            "q1": q1, "median": q2, "q3": q3,
            "mean": statistics.fmean(latencies_ms),
            "p95": float(np.percentile(latencies_ms, 95)),
            "max": max(latencies_ms)}
    tags, shapes = {}, {}
    for e in entries:
        for s in e.get("spans") or []:
            for k, v in (s.get("tags") or {}).items():
                if k in _KEPT_TAGS:
                    tags.setdefault(f"{s['name']}.{k}", []).append(v)
                elif k == "shape":
                    key = f"{s['name']} {list(v)}"
                    shapes[key] = shapes.get(key, 0) + 1
    if entries:
        out["span_tags"] = {k: {"spans": len(v),
                                "median": statistics.median(v),
                                "max": max(v)} for k, v in sorted(tags.items())}
        out["shapes"] = dict(sorted(shapes.items(), key=lambda kv: -kv[1])[:8])
    return out


def run(args, bench: dict, workload: dict, device: dict) -> dict:
    import jax
    import numpy as np

    from filodb_tpu import startup

    import loader
    import trace_reduce
    import traffic

    cache_dir = startup.configure_jax()
    watch = CompileWatch()
    unlisted = "cell_file" in workload
    config = read_json(workload.get("config_file") or os.path.join(
        HERE, "configs", f"{workload['config']}.json"))
    cell = read_json(workload.get("cell_file") or os.path.join(
        HERE, "cells", f"{workload['name']}.json"))
    if cell["config"] != workload["config"] \
            or cell["loop"]["kind"] != "closed":
        raise ValueError(f"{workload['name']}: the cell file names another "
                         "configuration, or a loop this client cannot run")
    params = dict(config["params"])
    if args.rehearsal:
        params.update(config["rehearsal"]["params"])
    layout = loader.server_layout()
    if layout != config["layout"]:
        raise RuntimeError(f"the program's default layout {layout} is no "
                           f"longer the configuration's {config['layout']}")
    say("start", workload=workload["name"], seed=args.seed, device=device,
        compile_cache_dir=cache_dir, rehearsal=args.rehearsal)

    t0 = time.perf_counter()
    metrics = load_module("generators", config["generator"]).make(
        params, args.seed)
    generate_s = time.perf_counter() - t0
    memstore, load = loader.load(metrics)
    if not (load["have_native"] and load["native_shards"]):
        raise RuntimeError(f"the load did not take the native lane: {load}")
    say("load", **{k: round(v, 2) if isinstance(v, float) else v
                   for k, v in {"generate_s": generate_s, **load}.items()
                   if not (args.rehearsal and k.endswith("_s"))})

    traced = bool(args.trace)
    if traced:
        # the program's own public switch: every query traced, every trace
        # kept, a ring that holds the window
        from filodb_tpu.utils import tracing
        tracing.configure(sample_rate=1.0, slow_query_threshold_ms=1e-9,
                          slowlog_capacity=200_000)
    svc, front = start_server(memstore, layout)
    out_dir = args.out or tempfile.mkdtemp(prefix="perf-run-")
    os.makedirs(out_dir, exist_ok=True)
    try:
        t0_sec = params["t0_sec"]
        # warm-up: this cell's shapes, one request at a time
        t_warm = time.perf_counter()
        warm_lat = []
        for r in traffic.warmup_requests(cell, layout["dataset"], t0_sec):
            took, status, body = fetch(front.port, r["path"])
            if status != 200:
                raise RuntimeError(f"warm-up: HTTP {status}: {body[:300]!r}")
            warm_lat.append(took)
        warm_s = time.perf_counter() - t_warm
        compile_before = watch.since((0, 0, 0))
        say("warmup", requests=len(warm_lat), **(
            {} if args.rehearsal else {"seconds": round(warm_s, 2)}),
            **{k: v for k, v in compile_before.items() if k != "build_s"})

        streams = traffic.streams(cell, layout["dataset"], t0_sec, args.seed)
        streams_path = os.path.join(out_dir, "streams.jsonl")
        with open(streams_path, "w") as f:
            for s in streams:
                f.write(json.dumps(s) + "\n")

        slice_ = None
        if traced:
            tr = cell["trace"]
            slice_s = min(max(tr["slice_s"], tr["min_requests"]
                              * min(warm_lat) * 1.1), 0.8 * args.seconds)
            slice_ = Slice(os.path.join(out_dir, "trace"), args.seconds,
                           slice_s)
            tracing.flight_recorder().clear()
        mark = watch.mark()
        before = counters_now()
        got = run_client(front.port, streams_path, args.seconds,
                         during=slice_.start if slice_ else None)
        after = counters_now()
        compile_in_window = watch.since(mark)
        if slice_:
            slice_.join()

        requests = got["requests"]
        done = [r for r in requests if r[4]]
        failed = len(requests) - len(done)
        if not done:
            raise RuntimeError("no request was answered")
        latencies_ms = [r[3] * 1e3 for r in done]
        peak = max(((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                    for d in jax.local_devices()), default=0)

        sent = [streams[r[0]][r[1] % len(streams[r[0]])] for r in done]
        checked = verify(cell, config, metrics, front.port, sent, args.seed)
        say("verify", **checked)

        entries, trace = [], None
        if traced:
            entries = [e for e in tracing.flight_recorder().snapshot()
                       if got["t0"] <= e["when"] <= got["t_end"] + 1]
            xplane = trace_reduce.find_xplane(slice_.out_dir)
            if xplane:
                trace = trace_reduce.reduce(
                    xplane, host_spans(entries, requests))
        if args.out:
            # every request's send time and latency, and what was recorded
            with open(os.path.join(out_dir, "requests.json"), "w") as f:
                json.dump({"t0": got["t0"], "requests": requests,
                           "entries": entries}, f)
    finally:
        front.stop()
        if not args.out:
            shutil.rmtree(out_dir, ignore_errors=True)

    setup_s = got["t0"] - T_PROCESS
    end_to_end = {
        "query_p50_ms": statistics.median(latencies_ms),
        "query_p95_ms": float(np.percentile(latencies_ms, 95)),
        "queries_per_s": len(done) / got["window_s"],
        "setup_s": setup_s,
    }
    say("window", requests=len(requests), failed=failed,
        compile_in_window=compile_in_window["programs_built"],
        recorded_queries=len(entries))

    def declared(m: dict) -> bool:
        return "workloads" not in m or workload["name"] in m["workloads"] \
            or m["name"] in workload.get("metrics", ())

    out_metrics = {}
    if not traced:
        if not args.rehearsal:
            for m in bench["end_to_end"]:
                if declared(m):
                    out_metrics[m["name"]] = {
                        "value": end_to_end[m["name"]], "unit": m["unit"]}
    else:
        peaks = read_json(HERE, "peaks.json")
        if not args.rehearsal and device["kind"] not in peaks:
            raise KeyError(f"no peaks for device kind {device['kind']!r} "
                           "in perf/peaks.json")
        facts = {
            "latencies_ms": latencies_ms, "window_s": got["window_s"],
            "requests": requests, "load": load, "generate_s": generate_s,
            "warm_s": warm_s, "compile_before": compile_before,
            "compile_in_window": compile_in_window,
            "memory_peak_bytes": peak, "device": device,
            "peaks": peaks.get(device["kind"]),
            "slice_wall": slice_.wall, "setup_s": setup_s,
        }
        counters = {"window": (before, after), "slice": slice_.counters}
        for m in bench["per_layer"]:
            if not declared(m):
                continue
            if args.rehearsal and m["source"] != "program_counter":
                continue
            value = load_module("layer_metrics", m["name"]).read(
                entries, counters, trace, facts)
            if value is not None:
                out_metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    line = {"correct": bool(checked["ok"] and not failed),
            "attempted": len(requests), "failed": failed,
            "metrics": out_metrics,
            "device": {**device, "memory_peak_bytes": int(peak)}}
    if args.rehearsal:
        line["rehearsal"] = True
    elif traced:
        if not trace or not trace["busy_s"]:
            raise RuntimeError("the trace shows no operation on the device")
        line["device"].update(busy_s=trace["busy_s"],
                              window_s=trace["window_s"])
        line["breakdown"] = {"device_ops": trace["device_ops"],
                             "idle_gaps": trace["idle_gaps"]}
    if unlisted:
        line["unlisted"] = True
    if traced or unlisted:
        line["detail"] = window_detail(latencies_ms, entries,
                                       timed=not args.rehearsal)
        if not args.rehearsal:
            line["detail"]["slowest"] = slowest_request(
                done, got["t0"], slice_.wall if slice_ else None, entries)
    line["compared"] = compared(cell, checked, failed)
    for name, c in line["compared"].items():
        print(f"compared {name}: {json.dumps(c)}", file=sys.stderr)
    return line


def unlisted_workload(args, bench: dict) -> dict:
    """The ``workloads`` entry a cell file would get, with where its files
    are and the per-layer metrics asked for by name: named
    ``<config>.<file's stem>`` (the stem alone where it starts so)."""
    config = read_json(args.config_file)["name"]
    stem = os.path.splitext(os.path.basename(args.cell_file))[0]
    name = stem if stem.startswith(config + ".") else f"{config}.{stem}"
    metrics = [m for m in args.metrics.split(",") if m]
    unknown = set(metrics) - {m["name"] for m in bench["per_layer"]}
    if unknown:
        raise SystemExit(f"--metrics: no per-layer metric {sorted(unknown)}")
    return {"name": name, "config": config, "chips": args.chips,
            "cell_file": args.cell_file, "config_file": args.config_file,
            "metrics": metrics}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--cell-file", help="a cell BENCHMARK.json does not "
                                        "list; with --config-file")
    ap.add_argument("--config-file")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="the chips an unlisted cell needs")
    ap.add_argument("--metrics", default="",
                    help="per-layer metrics an unlisted cell reads beside "
                         "those every cell reads, comma-separated")
    ap.add_argument("--seed", type=int, default=24)
    ap.add_argument("--seconds", type=float)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny sizes on any platform: counts and "
                         "`correct`, no timing")
    ap.add_argument("--out", help="keep the trace, the request streams and "
                                  "every request's send time and latency "
                                  "(requests.json) here (default: a "
                                  "temporary directory, removed at the end)")
    args = ap.parse_args(argv)
    bench = read_json(ROOT, "BENCHMARK.json")
    if args.cell_file:
        if args.workload or not args.config_file:
            ap.error("--cell-file goes with --config-file, without "
                     "--workload")
        workload = unlisted_workload(args, bench)
    else:
        if not args.workload or args.config_file or args.metrics:
            ap.error("--workload <cell>, or --cell-file with --config-file")
        workload = next((w for w in bench["workloads"]
                         if w["name"] == args.workload), None)
        if workload is None:
            ap.error(f"no workload {args.workload!r} in BENCHMARK.json")
    if args.seconds is None:
        args.seconds = float(bench["run_seconds"])

    from filodb_tpu import startup

    device = startup.device_info()
    if not args.rehearsal and (device["platform"] != "tpu"
                               or device["count"] < workload["chips"]):
        print(f"needs {workload['chips']} TPU chip(s); JAX found "
              f"{device['count']} {device['platform']} device(s)",
              file=sys.stderr)
        return 1
    try:
        line = run(args, bench, workload, device)
    except Exception:  # the one boundary: report, then fail without a line
        traceback.print_exc()
        return 1
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
