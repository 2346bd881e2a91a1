"""The one traffic generator: a cell's data file → each client's stream of
requests.

A cell's traffic is a sequence of *dashboards*: a key (an app, say) and an
``end`` instant, each sent as the cell's panels one after another over
``[end - range_s, end]``. The sequence is drawn ONCE, from the cell's own
``pool.seed``, from the cell's ``key`` and ``end`` distributions; ``--seed``
then shuffles each block of ``pool.block`` dashboards and deals it to the
clients. So every seed sends the same dashboards in another order and from
other clients: two runs that get equally far have done the same work, which
is what lets a 1% bound stand (a seed that drew its own apps would warm the
result cache its own way). A client that reaches the end of its stream
starts it again; size ``pool.dashboards`` so that none does.
"""

from __future__ import annotations

from urllib.parse import quote

import numpy as np


def _draw_keys(spec: dict, n: int, rng):
    if spec["dist"] == "none":
        return [None] * n
    if spec["dist"] == "zipf":
        w = 1.0 / np.arange(1, spec["n"] + 1) ** spec["s"]
        return rng.choice(spec["n"], n, p=w / w.sum()).tolist()
    raise ValueError(f"unknown key distribution {spec['dist']!r}")


def _draw_ends(spec: dict, n: int, rng, t0_sec: int):
    if spec["dist"] != "uniform":
        raise ValueError(f"unknown end distribution {spec['dist']!r}")
    res = spec["resolution_s"]
    slots = (spec["last_s"] - spec["first_s"]) // res + 1
    return (t0_sec + spec["first_s"]
            + rng.integers(0, slots, n) * res).tolist()


def request(cell: dict, dataset: str, panel: int, key, end_s: int) -> dict:
    promql = cell["panels"][panel]["promql"].replace("{key}", str(key))
    path = (f"/promql/{dataset}/api/v1/query_range?query={quote(promql)}"
            f"&start={end_s - cell['range_s']}&end={end_s}"
            f"&step={cell['step_s']}")
    return {"path": path, "panel": panel, "key": key, "end": end_s}


def dashboards(cell: dict, t0_sec: int) -> list:
    """The cell's fixed sequence of (key, end), the same for every seed."""
    pool = cell["pool"]
    rng = np.random.default_rng(pool["seed"])
    n = pool["dashboards"]
    return list(zip(_draw_keys(cell["key"], n, rng),
                    _draw_ends(cell["end"], n, rng, t0_sec)))


def streams(cell: dict, dataset: str, t0_sec: int, seed: int) -> list:
    """One list of requests a client."""
    clients = cell["loop"]["clients"]
    block = cell["pool"]["block"]
    assert block % clients == 0, "pool.block must be a multiple of clients"
    rng = np.random.default_rng(seed)
    out = [[] for _ in range(clients)]
    seq = dashboards(cell, t0_sec)
    for b0 in range(0, len(seq) - block + 1, block):
        for j, d in enumerate(rng.permutation(block)):
            key, end_s = seq[b0 + d]
            out[j % clients].extend(
                request(cell, dataset, p, key, end_s)
                for p in range(len(cell["panels"])))
    return out


def warmup_requests(cell: dict, dataset: str, t0_sec: int) -> list:
    """Each panel at each ``end`` the cell's ``warmup`` block names: the
    shapes this cell's traffic compiles, and no others."""
    w = cell["warmup"]
    return [request(cell, dataset, p, w.get("key"), t0_sec + off)
            for off in w["end_offsets_s"]
            for p in range(len(cell["panels"]))]
