"""The native Prom JSON writer of a matrix answer against the Python renderer.

``promjson.matrix_json_bytes`` writes the series in one native pass where the
library is loaded; with ``native.HAVE_NATIVE`` off it takes the Python
renderer. The two must give the same bytes for every answer.
"""

import json

import numpy as np
import pytest

from filodb_tpu.http import promjson
from filodb_tpu.memory import native
from filodb_tpu.query.model import (
    QueryResult,
    QueryStats,
    RangeVectorKey,
    StepMatrix,
)

pytestmark = pytest.mark.skipif(native.get_lib() is None,
                                reason="native library unavailable")

T0 = 1_700_000_000_123


def _steps(k, step_ms=15_000):
    return T0 + np.arange(k, dtype=np.int64) * step_ms


def _hosts(n):
    return [RangeVectorKey.of({"hostname": f"host_{i}"}) for i in range(n)]


def _tsbs():
    rng = np.random.default_rng(10_000)
    return QueryResult(StepMatrix(_hosts(10_000),
                                  rng.normal(50, 25, (10_000, 2)),
                                  _steps(2, 60_000)))


def _dash():
    rng = np.random.default_rng(61)
    key = RangeVectorKey.of({"_metric_": "http_requests", "app": "app-7"})
    return QueryResult(StepMatrix([key], rng.exponential(3.0, (1, 61)),
                                  _steps(61)))


def _histogram():
    rng = np.random.default_rng(64)
    les = np.concatenate([2.0 ** np.arange(-10, 53), [np.inf]])
    vals = np.cumsum(rng.exponential(1.0, (1, 61, 64)), axis=2)
    vals[0, 7, :] = np.nan
    key = RangeVectorKey.of({"service": "svc-3"})
    return QueryResult(StepMatrix([key], vals, _steps(61), les=les))


SPECIALS = [1e16, 1e15, 1e-5, 1e-4, 5e-324, -0.0, 0.1 + 0.2, float(2 ** 53),
            1e22, np.inf, -np.inf, 1.2345678901234568e+17, 123.456, -1e-300,
            1e300, 0.0, 100.0, -7.0, 9007199254740993.0, 1e16 - 2, 0.5e-4,
            -1.7976931348623157e308, 2.2250738585072014e-308]


def _specials():
    vals = np.array([SPECIALS, [-v for v in SPECIALS]])
    return QueryResult(StepMatrix(_hosts(2), vals, _steps(len(SPECIALS))))


def _seeded_values():
    rng = np.random.default_rng(2 ** 31 + 17)
    vals = np.concatenate([
        rng.normal(0, 1, (40, 25)) * 10.0 ** rng.integers(-30, 30, (40, 25)),
        rng.integers(-10 ** 6, 10 ** 6, (10, 25)).astype(np.float64),
        rng.random((10, 25)).astype(np.float32).astype(np.float64)])
    return QueryResult(StepMatrix(_hosts(60), vals, _steps(25)))


def _nan_gaps():
    rng = np.random.default_rng(5)
    vals = rng.normal(0, 1, (6, 12))
    vals[rng.random((6, 12)) < 0.3] = np.nan
    vals[2] = np.nan
    vals[4, 0] = vals[4, -1] = np.nan
    return QueryResult(StepMatrix(_hosts(6), vals, _steps(12)))


def _empty():
    return QueryResult(StepMatrix.empty(_steps(5)))


def _labels():
    odd = ['q"uote', "back\\slash", "nl\ncr\rtab\tbs\bff\f", "ctl\x00\x01\x1f",
           "del\x7f", "é ñ ü", "中文", "emoji 😀 𝄞", "lone \ud800 \udfff",
           "", " "]
    keys = [RangeVectorKey.of({"_metric_": "m", "k": v}) for v in odd]
    keys += [RangeVectorKey.of({v or "empty": "x", "z": v}) for v in odd]
    # a name twice and the metric label under both spellings: the dict
    # _labels_json builds keeps the first place and the last value
    keys.append(RangeVectorKey((("__name__", "a"), ("_metric_", "b"),
                                ("x", "1"), ("x", "2"))))
    keys.append(RangeVectorKey(()))
    vals = np.arange(len(keys) * 3, dtype=np.float64).reshape(-1, 3)
    return QueryResult(StepMatrix(keys, vals, _steps(3)))


def _partial():
    r = _dash()
    r.partial = True
    r.warnings = ['shard 3 lost: "timeout"', "ünïcode"]
    return r


def _full_stats():
    r = _nan_gaps()
    r.stats = QueryStats(series_scanned=6, samples_scanned=72,
                         result_series=6, wall_time_s=0.0123456,
                         decode_s=0.001, tiers={"mem": {"nodes": 2,
                                                        "ms": 1.23456}})
    return r


CASES = {
    "tsbs_10000x2": (_tsbs, False),
    "dash_1x61": (_dash, False),
    "histogram_1x61x64": (_histogram, False),
    "special_values": (_specials, False),
    "seeded_values": (_seeded_values, False),
    "nan_gaps": (_nan_gaps, False),
    "empty": (_empty, False),
    "label_escapes": (_labels, False),
    "partial_warnings": (_partial, False),
    "full_stats": (_full_stats, True),
}


def _python_bytes(monkeypatch, result, full_stats=False):
    with monkeypatch.context() as mp:
        mp.setattr(native, "HAVE_NATIVE", False)
        return promjson.matrix_json_bytes(result, full_stats=full_stats)


@pytest.mark.parametrize("case", sorted(CASES))
def test_native_bytes_match_python(case, monkeypatch):
    make, full_stats = CASES[case]
    result = make()
    want = _python_bytes(monkeypatch, result, full_stats)
    got = promjson.matrix_json_bytes(result, full_stats=full_stats)
    assert got == want
    assert promjson.matrix_json_str(result, full_stats).encode() == want
    # and both say what the dict renderer says
    body = json.loads(got)
    assert body["data"] == json.loads(json.dumps(
        promjson.matrix_json(result)))["data"]


def test_fallback_without_library(monkeypatch):
    result = _nan_gaps()
    monkeypatch.setattr(native, "HAVE_NATIVE", False)
    assert native.render_matrix_json(np.zeros((1, 1)), b"0.0",
                                     np.array([0, 3]), b"", np.array([0]),
                                     np.array([0])) is None
    before = promjson.RENDER_SERIES_NATIVE.value
    body = json.loads(promjson.matrix_json_str(result))
    assert len(body["data"]["result"]) == 5  # the all-NaN row is left out
    assert promjson.RENDER_SERIES_NATIVE.value == before


def test_writer_refuses_offsets_that_do_not_fit():
    vals = np.zeros((2, 1))
    ok = dict(ts=b"1.0", ts_offs=np.array([0, 3]), labels=b"abcd",
              label_offs=np.array([0, 1, 2, 3, 4]),
              label_counts=np.array([1, 1]))
    assert native.render_matrix_json(vals, **ok)[1] == 2
    for bad in (dict(ts_offs=np.array([0, 4])),
                dict(label_counts=np.array([1])),
                dict(label_counts=np.array([2, 1])),
                dict(label_offs=np.array([0, 1, 2, 3, 5]))):
        with pytest.raises(ValueError):
            native.render_matrix_json(vals, **{**ok, **bad})


@pytest.mark.parametrize("path", ["native", "python"])
def test_series_counter_moves_on_path_taken(path, monkeypatch):
    if path == "python":
        monkeypatch.setattr(native, "HAVE_NATIVE", False)
    taken, other = ((promjson.RENDER_SERIES_NATIVE,
                     promjson.RENDER_SERIES_PYTHON) if path == "native" else
                    (promjson.RENDER_SERIES_PYTHON,
                     promjson.RENDER_SERIES_NATIVE))
    t0, o0 = taken.value, other.value
    promjson.matrix_json_bytes(_tsbs())
    promjson.matrix_json_bytes(_nan_gaps())
    assert taken.value - t0 == 10_000 + 5
    assert other.value == o0
