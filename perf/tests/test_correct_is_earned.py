"""``correct`` has to be able to come out false (PR 36).

The control: the reference put in the program's place and computed in the
nearest precision below the program's (the server computes in float32; the
control in bfloat16, ``ml_dtypes``' numpy type), panel by panel of every
cell file: each has to be refused, where the same answer in f64 is
accepted. And the faults: the rest of a run driven with the timed path
broken underneath — an answer altered where it is rendered, half the
selected series left out of every batch — has to print ``correct: false``.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest

PERF = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(PERF)
TINY = os.path.join(PERF, "tests", "data", "histo-tiny")
for _p in (PERF, os.path.join(PERF, "generators")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import reference  # noqa: E402


def read_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def _cells() -> list:
    """(id, cell file, configuration file) of every cell file there is."""
    out = [(f[:-5], os.path.join(PERF, "cells", f)) for f in sorted(
        os.listdir(os.path.join(PERF, "cells")))]
    out = [(name, path, os.path.join(
        PERF, "configs", read_json(path)["config"] + ".json"))
        for name, path in out]
    return out + [("histo-tiny", os.path.join(TINY, "cell.json"),
                   os.path.join(TINY, "config.json"))]


CELLS = _cells()
PANELS = [(name, cell, conf, p) for name, cell, conf in CELLS
          for p in range(len(read_json(cell)["panels"]))]


@pytest.fixture(scope="module")
def generated():
    """Every configuration at its rehearsal size, made once."""
    import importlib

    made = {}
    for _, _, conf in CELLS:
        if conf not in made:
            config = read_json(conf)
            params = {**config["params"], **config["rehearsal"]["params"]}
            made[conf] = params, importlib.import_module(
                config["generator"]).make(params, 2**31 + 36)
    return made


@pytest.mark.parametrize("name,cell_file,conf,panel", PANELS, ids=[
    f"{name}-panel{p}" for name, _, _, p in PANELS])
def test_the_bf16_control_is_not_correct(name, cell_file, conf, panel,
                                         generated):
    import ml_dtypes

    cell = read_json(cell_file)
    params, metrics = generated[conf]
    check = cell["panels"][panel]["check"]
    key = 1 if cell["key"]["dist"] != "none" else None
    end = params["t0_sec"] + 5417
    args = (check, metrics, params["interval_ms"], key,
            end - cell["range_s"], end, cell["step_s"])

    def answer(cast):
        steps, lo, _, groups = reference.evaluate(
            *args, np.random.default_rng(0), cast=cast)
        return reference.answer_body(check, steps, np.asarray(lo, np.float64),
                                     groups)

    good = reference.check_panel(*args, answer(None),
                                 np.random.default_rng(0))
    assert good["worst_rel_error"] <= check["rtol"]
    with pytest.raises(reference.Mismatch):
        reference.check_panel(
            *args, answer(lambda v: v.astype(ml_dtypes.bfloat16)),
            np.random.default_rng(0))


def test_the_reference_in_the_programs_place_shows_exactly_k():
    """``answer_body`` under ``topk``: a tie at the k-th place (whole-number
    counters under a 1 m rate tie often) shows k rows, as the program does,
    and ``check_panel`` takes it."""
    check = {"by": "instance", "topk": 2, "rtol": 5e-5}
    rows = np.array([[3.0, 1.0], [2.0, 2.0], [2.0, np.nan], [2.0, 2.0]])
    groups = (np.array(["a", "b", "c", "d"]), 4, None)
    steps = np.array([60_000, 120_000])
    body = reference.answer_body(check, steps, rows, groups)
    shown = {r["metric"]["instance"]: [t for t, _ in r["values"]]
             for r in body["data"]["result"]}
    assert shown == {"a": [60.0], "b": [60.0, 120.0], "d": [120.0]}
    got = reference._compare_topk(check, body, steps, rows, rows, groups, "t")
    assert got["cells_checked"] == 4 and got["worst_rel_error"] == 0.0


# ---------------------------------------------------------------------------
# a run with the timed path broken underneath

FAULTS = {
    "answer-altered": """
from filodb_tpu.http import promjson
_strings = promjson._value_strings
promjson._value_strings = lambda vals: _strings(vals * 1.001)
""",
    "half-the-series-left-out": """
from filodb_tpu.core.memstore.shard import TimeSeriesShard
_lookup = TimeSeriesShard.lookup_partitions
TimeSeriesShard.lookup_partitions = \\
    lambda self, *a, **k: list(_lookup(self, *a, **k))[::2]
""",
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("name,cell_file,conf", CELLS,
                         ids=[c[0] for c in CELLS])
def test_a_broken_timed_path_is_not_correct(name, cell_file, conf, fault,
                                            tmp_path):
    """``run.main`` past its look for a chip (``--rehearsal``), the program
    patched before it starts: the line is printed, and says false."""
    listed = {w["name"] for w in read_json(ROOT, "BENCHMARK.json")[
        "workloads"]}
    if name in listed:
        argv = ["--workload", name]
    else:   # a cell file that is not listed runs by its files
        argv = ["--cell-file", cell_file, "--config-file", conf]
    argv += ["--seed", str(2**31 + 36), "--seconds", "2", "--rehearsal"]
    code = (f"import sys; sys.path[:0] = [{PERF!r}, {ROOT!r}]\n"
            f"{FAULTS[fault]}\nimport run\nsys.exit(run.main({argv!r}))")
    p = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu", TMPDIR=str(tmp_path)),
        cwd=ROOT, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is False and line["failed"] == 0
    over = [k for k, c in line["compared"].items()
            if c["value"] is None or c["value"] > c["limit"]]
    assert over and "refused" in line["compared"][over[0]], line["compared"]
