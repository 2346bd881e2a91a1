"""Self-monitoring overhead: ingest throughput with MetaMonitor off vs on.

The ``_meta`` sampler walks the whole metric registry every tick, builds a
record container, and writes it through the normal ingest path — all on
its own daemon thread, but sharing the process (GIL, registry lock,
memstore) with real ingest. This measures what that costs: the same
pre-built ingest workload as ``run_benchmarks.py`` ``ingestion`` run with
the monitor stopped and then with it ticking. To make the delta
measurable inside a benchmark-sized run the monitor ticks every 50 ms —
300× the default 15 s cadence — and the result reports both the measured
overhead at that aggressive interval and the per-tick cost, from which
the production-cadence (15 s) overhead is projected (target: ≤2%).

    python benchmarks/selfmon_overhead.py [--samples 300000]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

START = 1_600_000_000


def bench_selfmon_overhead(samples: int = 300_000, rounds: int = 3,
                           interval_s: float = 0.05):
    from filodb_tpu.core.memstore.memstore import TimeSeriesMemStore
    from filodb_tpu.core.record import BytesContainer, SomeData
    from filodb_tpu.core.store.config import StoreConfig
    from filodb_tpu.rules.manager import MemstoreSink
    from filodb_tpu.testing.data import gauge_stream, machine_metrics_series
    from filodb_tpu.utils import selfmon as selfmon_mod
    from filodb_tpu.utils.selfmon import MetaMonitor

    ms = TimeSeriesMemStore()
    shard = ms.setup("bench", 0, StoreConfig(max_chunk_size=400,
                                             retention_ms=10**15))
    ms.setup("_meta", 0, StoreConfig(groups_per_shard=4,
                                     retention_ms=10**15))
    keys = machine_metrics_series(100)
    per_round = samples // 100

    # every round gets FRESH samples (advancing timestamps + offsets):
    # replaying one segment would hit the shards' out-of-order drop path
    # instead of real encode work
    segment_no = 0

    def next_segment():
        nonlocal segment_no
        base = START * 1000 + segment_no * per_round * 10_000
        seg = [SomeData(BytesContainer(sd.container.serialize()), sd.offset)
               for sd in gauge_stream(
                   keys, per_round, start_ms=base, batch=500,
                   start_offset=segment_no * samples)]
        segment_no += 1
        return seg

    def run_round():
        seg = next_segment()
        t0 = time.perf_counter()
        for sd in seg:
            shard.ingest(sd)
        return time.perf_counter() - t0

    mon = MetaMonitor(MemstoreSink(ms, "_meta", 1), interval_s=interval_s,
                      node="bench", instance="bench:0")
    # warm both lanes (compile caches, registry growth from first ticks)
    run_round()
    mon.tick()

    off, on = [], []
    ticks0 = selfmon_mod.TICKS.value
    # alternate mode order per round so allocator/cache drift doesn't
    # bias one side
    for rnd in range(rounds):
        order = [("off", off), ("on", on)]
        if rnd % 2:
            order.reverse()
        for name, acc in order:
            if name == "on":
                mon.start()
                acc.append(run_round())
                mon.stop()
            else:
                acc.append(run_round())
    ticks = selfmon_mod.TICKS.value - ticks0

    # isolated per-tick cost (sampler walk + container build + write)
    n = 20
    t0 = time.perf_counter()
    for _ in range(n):
        mon.tick()
    tick_ms = (time.perf_counter() - t0) / n * 1000

    off_s, on_s = min(off), min(on)
    thr_off, thr_on = samples / off_s, samples / on_s
    overhead = (thr_off - thr_on) / thr_off * 100
    # production cadence: one tick_ms slice out of every 15 s of wall
    # time, as a percentage
    projected = tick_ms / 150.0
    return {
        "metric": "selfmon_overhead",
        "samples": samples,
        "interval_s": interval_s,
        "ticks_during_on_rounds": ticks,
        "ingest_off_samples_per_s": round(thr_off),
        "ingest_on_samples_per_s": round(thr_on),
        "overhead_pct_at_bench_interval": round(overhead, 2),
        "tick_ms": round(tick_ms, 2),
        "projected_overhead_pct_at_15s": round(projected, 3),
        "unit": "samples/sec",
    }


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--samples", type=int, default=300_000)
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--interval", type=float, default=0.05)
    args = ap.parse_args(argv)
    print(json.dumps(bench_selfmon_overhead(args.samples, args.rounds,
                                            args.interval)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
