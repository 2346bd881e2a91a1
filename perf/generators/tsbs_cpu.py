"""``tsbs_cpu``: TSBS's ``cpu-only`` use case — ten CPU gauges a host, every
host reporting on the same 10 s tick, each field a random walk of whole
numbers clamped to 0..100 (TSBS writes them as integers), and TSBS's ten
host tags. Tag cardinalities are written from memory of TSBS's devops
generator and listed as ``assumed`` in the configuration."""

from __future__ import annotations

import numpy as np

FIELDS = ("usage_user", "usage_system", "usage_idle", "usage_nice",
          "usage_iowait", "usage_irq", "usage_softirq", "usage_steal",
          "usage_guest", "usage_guest_nice")
_REGIONS = ("us-east-1", "us-west-1", "us-west-2", "eu-west-1",
            "eu-central-1", "ap-southeast-1", "ap-southeast-2",
            "ap-northeast-1", "sa-east-1")
_OS = ("Ubuntu16.10", "Ubuntu16.04LTS", "Ubuntu15.10")
_ARCH = ("x64", "x86")
_TEAMS = ("SF", "NYC", "LON", "CHI")
_ENVS = ("production", "staging", "test")


def _host_tags(n_hosts: int, rng) -> dict:
    host = np.arange(n_hosts)
    region = rng.integers(0, len(_REGIONS), n_hosts)
    zone = rng.integers(0, 3, n_hosts)
    pick = lambda names: np.array(names)[rng.integers(0, len(names), n_hosts)]
    return {
        "hostname": np.char.add("host_", host.astype(str)),
        "region": np.array(_REGIONS)[region],
        "datacenter": np.char.add(np.char.add(np.array(_REGIONS)[region],
                                              "-"), zone.astype(str)),
        "rack": rng.integers(0, 100, n_hosts).astype(str),
        "os": pick(_OS), "arch": pick(_ARCH), "team": pick(_TEAMS),
        "service": rng.integers(0, 20, n_hosts).astype(str),
        "service_version": rng.integers(0, 2, n_hosts).astype(str),
        "service_environment": pick(_ENVS),
    }


def make(params: dict, seed: int) -> dict:
    rng = np.random.default_rng(seed)
    n, samples = params["hosts"], params["samples"]
    tags = _host_tags(n, rng)
    tags["_ws_"] = np.full(n, params["_ws_"])
    tags["_ns_"] = np.full(n, params["_ns_"])
    ts = np.broadcast_to(
        params["t0_sec"] * 1000 + np.arange(samples, dtype=np.int64)
        * params["interval_ms"], (n, samples))
    out = {}
    for field in FIELDS:
        name = f"cpu_{field}"
        # f32 steps halve the generator's memory; the walk itself is f64
        walk = rng.uniform(0.0, 100.0, (n, 1)) + np.cumsum(
            rng.standard_normal((n, samples), np.float32), axis=1,
            dtype=np.float64)
        # reflect at the walls, then round: whole numbers in 0..100
        walk = np.abs(walk) % 200.0
        vals = np.rint(np.where(walk > 100.0, 200.0 - walk, walk))
        out[name] = {"name": name, "schema": "gauge", "labels": tags,
                     "ts": ts, "vals": vals}
    return out
