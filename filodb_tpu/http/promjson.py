"""Prometheus HTTP API JSON rendering.

Counterpart of reference ``query/PrometheusModel.scala:13-51`` +
``PromCirceSupport.scala``: StepMatrix → Prom ``matrix``/``vector``/``scalar``
response payloads. NaN entries are gaps and are omitted; first-class histogram
results are flattened to ``le``-labelled bucket series (as the reference does
when converting histogram RangeVectors to the Prom wire model).
"""

from __future__ import annotations

import json
import math
from itertools import chain

import numpy as np

from filodb_tpu.core.partkey import METRIC_LABEL
from filodb_tpu.memory import native
from filodb_tpu.query.model import QueryResult, StepMatrix
from filodb_tpu.utils.metrics import Counter

# the series of matrix answers, by the renderer that wrote them: "native"
# where the library is loaded, "python" where it is absent
_RENDER_SERIES_HELP = "series of Prom matrix answers rendered, by renderer"
RENDER_SERIES_NATIVE = Counter("filodb_http_render_series", {"path": "native"},
                               help=_RENDER_SERIES_HELP)
RENDER_SERIES_PYTHON = Counter("filodb_http_render_series", {"path": "python"},
                               help=_RENDER_SERIES_HELP)


def _fmt(v: float) -> str:
    if math.isinf(v):
        return "+Inf" if v > 0 else "-Inf"
    if math.isnan(v):
        return "NaN"
    return repr(float(v))


def _labels_json(key) -> dict:
    out = {}
    for k, v in key.labels:
        out["__name__" if k == METRIC_LABEL else k] = v
    return out


def _flatten_histograms(m: StepMatrix) -> StepMatrix:
    """[P,K,B] histogram matrix -> per-bucket series with le labels."""
    from filodb_tpu.query.model import RangeVectorKey

    keys, rows = [], []
    les = m.les if m.les is not None else np.arange(m.values.shape[2])
    for i, k in enumerate(m.keys):
        for b, le in enumerate(les):
            lm = k.label_map
            lm["le"] = _fmt(float(le))
            keys.append(RangeVectorKey.of(lm))
            rows.append(m.values[i, :, b])
    return StepMatrix(keys, np.stack(rows) if rows
                      else np.zeros((0, m.num_steps)), m.steps_ms)


def _stats_json(result: QueryResult, full: bool = False) -> dict:
    s = result.stats
    out = {"seriesScanned": s.series_scanned,
           "samplesScanned": s.samples_scanned,
           "resultSeries": s.result_series,
           "wallTimeMs": round(s.wall_time_s * 1000.0, 3)}
    if full:
        # ?stats=all — the expanded per-query counters merged across
        # remote children (distributed tracing / flight-recorder stats)
        out.update({
            "chunksTouched": s.chunks_touched,
            "cacheHits": s.cache_hits,
            "cacheMisses": s.cache_misses,
            "wireBytes": s.wire_bytes,
            "admissionWaitMs": round(s.admission_wait_s * 1000.0, 3),
            "decodeMs": round(s.decode_s * 1000.0, 3),
            "reduceMs": round(s.reduce_s * 1000.0, 3),
        })
        if s.tiers:
            # federated query: per-tier attribution (query/federation.py)
            out["tiers"] = {
                tier: {k: (round(v, 3) if isinstance(v, float) else v)
                       for k, v in bucket.items()}
                for tier, bucket in s.tiers.items()}
        if s.pyramid:
            # cold folds served from stored aggregate levels
            # (query/engine/pyramid_lane.py)
            out["pyramid"] = {
                k: (round(v, 3) if isinstance(v, float) else v)
                for k, v in s.pyramid.items()}
    return out


def _partial_fields(result: QueryResult) -> dict:
    """``partial``/``warnings`` response fields for a degraded scatter-gather
    result (Prom API ``warnings`` convention); empty when complete."""
    if not getattr(result, "partial", False) \
            and not getattr(result, "warnings", None):
        return {}
    out = {}
    if result.partial:
        out["partial"] = True
    if result.warnings:
        out["warnings"] = list(result.warnings)
    return out


def _partial_fields_str(result: QueryResult) -> str:
    """String-renderer form of :func:`_partial_fields` — ``""`` or a
    leading-comma fragment to splice before the closing brace."""
    fields = _partial_fields(result)
    if not fields:
        return ""
    return "," + json.dumps(fields, separators=(",", ":"))[1:-1]


def matrix_json(result: QueryResult) -> dict:
    m = result.result
    if m.is_histogram:
        m = _flatten_histograms(m)
    series = []
    for i, key in enumerate(m.keys):
        vals = []
        row = m.values[i]
        for k in range(m.num_steps):
            v = row[k]
            if not math.isnan(v):
                vals.append([m.steps_ms[k] / 1000.0, _fmt(v)])
        if vals:
            series.append({"metric": _labels_json(key), "values": vals})
    return {"status": "success",
            "data": {"resultType": "matrix", "result": series},
            "queryStats": _stats_json(result),
            **_partial_fields(result)}


def _labels_json_str(key) -> str:
    """Serialized metric-label object, memoized per key instance (keys
    repeat across queries and series)."""
    s = key.__dict__.get("_json_str")
    if s is None:
        s = json.dumps(_labels_json(key), separators=(",", ":"))
        object.__setattr__(key, "_json_str", s)
    return s


def _value_strings(vals: np.ndarray) -> np.ndarray:
    """Shortest-round-trip value strings, vectorized (numpy's float→str is
    the same shortest-repr algorithm as Python's repr); Prom spellings for
    the specials."""
    sv = vals.astype("U24")
    if not np.isfinite(vals).all():
        sv = np.where(np.isposinf(vals), "+Inf", sv)
        sv = np.where(np.isneginf(vals), "-Inf", sv)
        sv = np.where(np.isnan(vals), "NaN", sv)
    return sv


def _series_python(m: StepMatrix, vals: np.ndarray,
                   ts_str: list[str]) -> tuple[str, int]:
    """The matrix's series joined into one JSON text, and how many — numpy
    formats every sample value in one vectorized pass, the rest is a loop
    over the series. The oracle of :func:`_series_native`, and the path
    where the native library is absent."""
    ok = ~np.isnan(vals)
    sv = _value_strings(vals)
    parts = []
    for i, key in enumerate(m.keys):
        idx = np.flatnonzero(ok[i])
        if not len(idx):
            continue
        row = sv[i]
        body = ",".join(f'[{ts_str[k]},"{row[k]}"]' for k in idx.tolist())
        parts.append('{"metric":%s,"values":[%s]}'
                     % (_labels_json_str(key), body))
    return ",".join(parts), len(parts)


def _series_native(m: StepMatrix, vals: np.ndarray, ts_str: list[str]):
    """:func:`_series_python`'s bytes in one native pass, or None where the
    library is absent. Python only lays every key's label names and values
    end to end; the writer applies ``_labels_json``'s rename and last-wins
    itself. ``surrogatepass`` lets a lone surrogate through as the 3 bytes
    the writer reads back to the code point ``json.dumps`` escapes."""
    labels = [k.labels for k in m.keys]
    counts = np.fromiter(map(len, labels), np.int64, len(labels))
    strs = list(chain.from_iterable(chain.from_iterable(labels)))
    text = "".join(strs)
    blob = text.encode("utf-8", "surrogatepass")
    lens = map(len, strs) if len(blob) == len(text) else \
        (len(s.encode("utf-8", "surrogatepass")) for s in strs)
    offs = np.zeros(len(strs) + 1, np.int64)
    offs[1:] = np.fromiter(lens, np.int64, len(strs))
    ts_offs = np.zeros(len(ts_str) + 1, np.int64)
    ts_offs[1:] = np.fromiter(map(len, ts_str), np.int64, len(ts_str))
    return native.render_matrix_json(
        vals, "".join(ts_str).encode(), np.cumsum(ts_offs), blob,
        np.cumsum(offs), counts)


_MATRIX_HEAD = b'{"status":"success","data":{"resultType":"matrix","result":['


def matrix_json_bytes(result: QueryResult, full_stats: bool = False) -> bytes:
    """Prom matrix response as the bytes of its JSON text (ASCII): the
    series from the native writer, with one copy, or from the Python
    renderer where the library is absent — the same bytes either way (the
    reference leans on Jackson streaming for the same reason,
    ``PromCirceSupport``). Counts the series under the path that wrote
    them."""
    m = result.result
    if m.is_histogram:
        m = _flatten_histograms(m)
    m.materialize()
    vals = np.asarray(m.values, np.float64)
    ts_str = [repr(t / 1000.0) for t in m.steps_ms.tolist()]
    rendered = _series_native(m, vals, ts_str)
    if rendered is None:
        text, n = _series_python(m, vals, ts_str)
        series = text.encode()
        RENDER_SERIES_PYTHON.inc(n)
    else:
        series, n = rendered
        RENDER_SERIES_NATIVE.inc(n)
    stats = json.dumps(_stats_json(result, full=full_stats),
                       separators=(",", ":"))
    tail = ']},"queryStats":%s%s}' % (stats, _partial_fields_str(result))
    return b"".join((_MATRIX_HEAD, series, tail.encode()))


def matrix_json_str(result: QueryResult, full_stats: bool = False) -> str:
    """Prom matrix response rendered straight to a JSON string."""
    return matrix_json_bytes(result, full_stats).decode()


def vector_json_str(result: QueryResult, with_stats: bool = False) -> str:
    """Prom vector response rendered straight to a JSON string."""
    m = result.result
    if m.is_histogram:
        m = _flatten_histograms(m)
    m.materialize()
    statstr = ""
    if with_stats:
        statstr = ',"queryStats":%s' % json.dumps(
            _stats_json(result, full=True), separators=(",", ":"))
    if not m.num_steps or not m.num_series:
        return ('{"status":"success","data":{"resultType":"vector",'
                '"result":[]}%s%s}' % (statstr, _partial_fields_str(result)))
    k = m.num_steps - 1
    vals = np.asarray(m.values[:, k], np.float64)
    ok = ~np.isnan(vals)
    sv = _value_strings(vals)
    t = repr(float(m.steps_ms[k]) / 1000.0)
    parts = [
        '{"metric":%s,"value":[%s,"%s"]}' % (_labels_json_str(m.keys[i]),
                                             t, sv[i])
        for i in np.flatnonzero(ok).tolist()]
    return ('{"status":"success","data":{"resultType":"vector","result":'
            '[%s]}%s%s}' % (",".join(parts), statstr,
                            _partial_fields_str(result)))


def vector_json(result: QueryResult, with_stats: bool = False) -> dict:
    m = result.result
    if m.is_histogram:
        m = _flatten_histograms(m)
    out = []
    k = m.num_steps - 1
    for i, key in enumerate(m.keys):
        v = m.values[i, k] if m.num_steps else float("nan")
        if not math.isnan(v):
            out.append({"metric": _labels_json(key),
                        "value": [m.steps_ms[k] / 1000.0, _fmt(v)]})
    resp = {"status": "success",
            "data": {"resultType": "vector", "result": out},
            **_partial_fields(result)}
    if with_stats:
        resp["queryStats"] = _stats_json(result, full=True)
    return resp


def scalar_json(result: QueryResult) -> dict:
    m = result.result
    k = m.num_steps - 1
    v = m.values[0, k] if m.num_series else float("nan")
    return {"status": "success",
            "data": {"resultType": "scalar",
                     "result": [m.steps_ms[k] / 1000.0, _fmt(v)]}}


def error_json(message: str, error_type: str = "bad_data") -> dict:
    return {"status": "error", "errorType": error_type, "error": message}
