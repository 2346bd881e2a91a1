"""Minimal in-process metrics registry.

Counterpart of the reference's Kamon counters/gauges/histograms
(``TimeSeriesShardStats``, ``KamonLogger.scala``): a process-wide registry that
the HTTP server exposes in Prometheus text exposition format (the reference's
"metrics sink" concept, ``README.md:860-876``).

Updates are thread-safe: ``Counter.inc``, ``Gauge.set``, and
``Histogram.observe`` synchronize on a per-metric lock, since updates race
across gather workers, the write-behind uploader, and rules threads.
"""

from __future__ import annotations

import logging
import threading
import time
from collections import defaultdict

log = logging.getLogger("filodb.metrics")

_registry: dict[str, "Metric"] = {}
_lock = threading.Lock()

# GaugeFn callbacks whose first failure has already been logged (keyed by
# metric key) — one log line per broken callback, not one per scrape
_scrape_error_logged: set[str] = set()


class Metric:
    def __init__(self, name: str, tags: dict[str, str] | None = None,
                 help: str | None = None):
        self.name = name
        self.tags = tags or {}
        self.help = help or name
        self._mlock = threading.Lock()
        key = self._key()
        with _lock:
            _registry[key] = self

    def _key(self) -> str:
        t = ",".join(f"{k}={v}" for k, v in sorted(self.tags.items()))
        return f"{self.name}{{{t}}}"


class Counter(Metric):
    def __init__(self, name: str, tags: dict[str, str] | None = None,
                 help: str | None = None):
        super().__init__(name, tags, help)
        self.value = 0

    def inc(self, n: int = 1) -> None:
        with self._mlock:
            self.value += n


class Gauge(Metric):
    def __init__(self, name: str, tags: dict[str, str] | None = None,
                 help: str | None = None):
        super().__init__(name, tags, help)
        self.value = 0.0

    def set(self, v: float) -> None:
        with self._mlock:
            self.value = v


class GaugeFn(Metric):
    """Gauge whose value is computed at scrape time from a callback —
    used for state that lives elsewhere (index sizes, pool sizes, arena
    stats) so scrapes never go stale and no update path is needed. A
    callback returning ``None`` (e.g. its subject was torn down) drops
    the series from the exposition instead of rendering NaN."""

    def __init__(self, name: str, fn, tags: dict[str, str] | None = None,
                 help: str | None = None):
        super().__init__(name, tags, help)
        self.fn = fn

    @property
    def value(self) -> float | None:
        try:
            v = self.fn()
            return None if v is None else float(v)
        except Exception:
            SCRAPE_ERRORS.inc()
            key = self._key()
            with _lock:
                first = key not in _scrape_error_logged
                if first:
                    _scrape_error_logged.add(key)
            if first:
                log.warning("metric scrape callback failed: %s", key,
                            exc_info=True)
            return float("nan")


class Histogram(Metric):
    """Fixed-boundary histogram; default bounds suit latency seconds,
    pass ``bounds`` for other units (e.g. query ranges in minutes)."""

    BOUNDS = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
              1.0, 2.5, 5.0, 10.0)

    def __init__(self, name: str, tags: dict[str, str] | None = None,
                 bounds: tuple | None = None, help: str | None = None):
        super().__init__(name, tags, help)
        self.bounds = tuple(bounds) if bounds is not None else self.BOUNDS
        self.buckets = defaultdict(int)
        self.count = 0
        self.sum = 0.0

    def observe(self, v: float) -> None:
        with self._mlock:
            self.count += 1
            self.sum += v
            for b in self.bounds:
                if v <= b:
                    self.buckets[b] += 1

    def time(self):
        return _Timer(self)


class _Timer:
    def __init__(self, hist: Histogram):
        self.hist = hist

    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.hist.observe(time.perf_counter() - self.t0)


# broken scrape callbacks are counted, not silently masked as nan: a
# dashboard watching this family catches a dead gauge the first scrape
SCRAPE_ERRORS = Counter("filodb_metric_scrape_errors")

# query/engine/batch.py:build_batch — series a batch build read through the
# native shard core's one call a shard, and series it read one at a time
_BATCH_ROWS_HELP = "series read into a query batch, by read path"
BATCH_ROWS_NATIVE = Counter("filodb_batch_rows", {"path": "native"},
                            help=_BATCH_ROWS_HELP)
BATCH_ROWS_FALLBACK = Counter("filodb_batch_rows", {"path": "fallback"},
                              help=_BATCH_ROWS_HELP)

# parallel/staging.py:StagingPool — bytes of [P, S] host staging arrays the
# mesh engine handed to a batch build, by where the memory came from
_BATCH_BUFFER_HELP = ("bytes of host staging arrays handed to a mesh batch "
                      "build: taken back from an earlier placement, or "
                      "fresh from the allocator")
BATCH_BUFFER_REUSED = Counter("filodb_batch_buffer_bytes",
                              {"source": "reused"}, help=_BATCH_BUFFER_HELP)
BATCH_BUFFER_FRESH = Counter("filodb_batch_buffer_bytes",
                             {"source": "fresh"}, help=_BATCH_BUFFER_HELP)


def get_counter(name: str, tags: dict[str, str] | None = None,
                help: str | None = None) -> Counter:
    """Idempotent counter lookup: error-path call sites (flush loops,
    protocol handlers) increment per-(name, tags) counters without each
    having to hold a module-level instance — re-registering would reset the
    running value."""
    t = ",".join(f"{k}={v}" for k, v in sorted((tags or {}).items()))
    key = f"{name}{{{t}}}"
    with _lock:
        m = _registry.get(key)
    if isinstance(m, Counter):
        return m
    return Counter(name, tags, help)


def get_gauge(name: str, tags: dict[str, str] | None = None,
              help: str | None = None) -> Gauge:
    """Idempotent gauge lookup (per-(name, tags)) — the gauge analog of
    :func:`get_counter`, for dynamically-tagged series (per-tenant,
    per-migration) where re-registering would drop the live value."""
    t = ",".join(f"{k}={v}" for k, v in sorted((tags or {}).items()))
    key = f"{name}{{{t}}}"
    with _lock:
        m = _registry.get(key)
    if isinstance(m, Gauge):
        return m
    return Gauge(name, tags, help)


def escape_label_value(v) -> str:
    """Prometheus text-exposition label-value escaping: a backslash,
    double quote, or newline in a tag value would otherwise corrupt the
    whole scrape body."""
    return (str(v).replace("\\", "\\\\").replace('"', '\\"')
            .replace("\n", "\\n"))


def render_prometheus() -> str:
    """Expose all metrics in Prometheus text format, series grouped per
    family under ``# HELP``/``# TYPE`` headers (the help string defaults to
    the family name unless the metric was created with ``help=``)."""
    with _lock:
        metrics = list(_registry.values())
    families: dict[tuple[str, str], list[Metric]] = {}
    for m in metrics:
        if isinstance(m, Counter):
            fam = (f"{m.name}_total", "counter")
        elif isinstance(m, (Gauge, GaugeFn)):
            fam = (m.name, "gauge")
        elif isinstance(m, Histogram):
            fam = (m.name, "histogram")
        else:
            continue
        families.setdefault(fam, []).append(m)
    lines = []
    for (fam, typ), members in families.items():
        help_text = " ".join(str(members[0].help).split())
        lines.append(f"# HELP {fam} {help_text}")
        lines.append(f"# TYPE {fam} {typ}")
        for m in members:
            tagstr = ",".join(f'{k}="{escape_label_value(v)}"'
                              for k, v in sorted(m.tags.items()))
            tagstr = f"{{{tagstr}}}" if tagstr else ""
            if isinstance(m, Counter):
                lines.append(f"{m.name}_total{tagstr} {m.value}")
            elif isinstance(m, (Gauge, GaugeFn)):
                v = m.value
                if v is None:
                    continue  # subject gone (GaugeFn over a dead shard)
                lines.append(f"{m.name}{tagstr} {v}")
            elif isinstance(m, Histogram):
                for b in m.bounds:
                    t = (tagstr[:-1] + f',le="{b}"}}' if tagstr
                         else f'{{le="{b}"}}')
                    lines.append(f"{m.name}_bucket{t} {m.buckets.get(b, 0)}")
                t = tagstr[:-1] + ',le="+Inf"}' if tagstr else '{le="+Inf"}'
                lines.append(f"{m.name}_bucket{t} {m.count}")
                lines.append(f"{m.name}_count{tagstr} {m.count}")
                lines.append(f"{m.name}_sum{tagstr} {m.sum}")
    return "\n".join(lines) + "\n"
