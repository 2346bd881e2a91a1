"""Part-key tag inverted index.

Counterpart of the reference's ``PartKeyLuceneIndex``
(``core/src/main/scala/filodb.core/memstore/PartKeyLuceneIndex.scala:38-42,71``):
per shard, maps label=value postings to partition ids, tracks per-partition
[startTime, endTime] for time-bounded lookups, supports Equals / NotEquals /
regex / In filters (``leafFilter:455``, ``partIdsFromFilters:494``) and label
introspection (labelValues / indexNames).

Rebuilt TPU-first as a two-tier structure (no Lucene, no mmap):

- **frozen tier**: per label, a sorted value table (offset-indexed bytes) and
  flat sorted pid arrays — loaded as zero-copy numpy slices from an index
  snapshot; lookups are a binary search + array slice, and filter
  intersections are ``np.intersect1d`` over sorted arrays (the
  roaring-bitmap analog, vectorized instead of pointer-chasing sets).
- **tail tier**: plain ``dict → set`` postings for keys added since the last
  freeze/restore; merged into query results and folded into the next
  snapshot.

Regex/negative filters scan the per-label value table, which is tiny
relative to the postings; non-empty-matching regexes use the value scan as a
positive filter (Lucene's regexp query analog).
"""

from __future__ import annotations

from bisect import bisect_left
from collections import defaultdict

import numpy as np

from filodb_tpu.core.filters import ColumnFilter, Equals, EqualsRegex, In
from filodb_tpu.core.partkey import PartKey

_INIT_CAP = 1024
# endTime for a still-ingesting partition (reference Long.MaxValue semantics)
INGESTING = np.iinfo(np.int64).max
_EMPTY = np.array([], np.int64)


class FrozenLabel:
    """One label's frozen postings: sorted value table + flat pid arrays."""

    __slots__ = ("voff", "vblob", "poff", "pids")

    def __init__(self, voff: np.ndarray, vblob: bytes, poff: np.ndarray,
                 pids: np.ndarray):
        self.voff = voff    # u32 [nv+1] offsets into vblob
        self.vblob = vblob  # concatenated value bytes, sorted
        self.poff = poff    # i64 [nv+1] offsets into pids
        self.pids = pids    # i32, sorted within each value's slice

    @property
    def nv(self) -> int:
        return len(self.voff) - 1

    def value(self, vi: int) -> bytes:
        return self.vblob[self.voff[vi] : self.voff[vi + 1]]

    def find(self, value: bytes) -> int:
        """Binary search the sorted value table; -1 when absent."""
        lo, hi = 0, self.nv
        while lo < hi:
            mid = (lo + hi) // 2
            if self.value(mid) < value:
                lo = mid + 1
            else:
                hi = mid
        if lo < self.nv and self.value(lo) == value:
            return lo
        return -1

    def pid_slice(self, vi: int) -> np.ndarray:
        return self.pids[self.poff[vi] : self.poff[vi + 1]]

    def prefix_range(self, prefix: bytes) -> tuple[int, int]:
        """[lo, hi) of sorted value-table indexes starting with ``prefix``
        — binary search against the prefix and its byte-successor."""
        def bisect(target: bytes) -> int:
            lo, hi = 0, self.nv
            while lo < hi:
                mid = (lo + hi) // 2
                if self.value(mid) < target:
                    lo = mid + 1
                else:
                    hi = mid
            return lo

        start = bisect(prefix)
        succ = bytearray(prefix)
        while succ and succ[-1] == 0xFF:
            succ.pop()
        if not succ:
            return start, self.nv
        succ[-1] += 1
        return start, bisect(bytes(succ))

    def values(self):
        for vi in range(self.nv):
            yield self.value(vi), vi

    @staticmethod
    def build(pairs: list) -> "FrozenLabel":
        """From (value_bytes, sorted pid sequence) pairs (any order).
        Sequences may be arrays or lists; the flat pid array is built with
        one fromiter pass (1M tiny per-value concatenations would dominate
        snapshot writes at high cardinality)."""
        from itertools import chain
        pairs.sort(key=lambda t: t[0])
        nv = len(pairs)
        vlens = np.fromiter((len(vb) for vb, _ in pairs), np.uint32, nv)
        plens = np.fromiter((len(a) for _, a in pairs), np.int64, nv)
        voff = np.zeros(nv + 1, np.uint32)
        np.cumsum(vlens, out=voff[1:])
        poff = np.zeros(nv + 1, np.int64)
        np.cumsum(plens, out=poff[1:])
        vblob = b"".join(vb for vb, _ in pairs)
        total = int(poff[-1])
        pids = np.fromiter(chain.from_iterable(a for _, a in pairs),
                           np.int32, total)
        return FrozenLabel(voff, vblob, poff, pids)


def _filter_cache_key(flt):
    """Stable per-predicate memo key for value-table scans (None = no memo)."""
    from filodb_tpu.core.filters import NotEquals, NotEqualsRegex
    if isinstance(flt, EqualsRegex):
        return ("re", flt.pattern)
    if isinstance(flt, NotEqualsRegex):
        return ("nre", flt.pattern)
    if isinstance(flt, NotEquals):
        return ("ne", flt.value)
    return None


def _intersect_sorted(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Intersection of two SORTED-unique id arrays via binary search —
    ``np.intersect1d`` re-sorts its inputs every call, which dominated
    regex-filter queries (all postings here are already sorted)."""
    if not len(a) or not len(b):
        return a[:0]
    if len(a) > len(b):
        a, b = b, a
    pos = np.searchsorted(b, a)
    pos[pos == len(b)] = len(b) - 1
    return a[b[pos] == a]


def _from_set(s: set[int]) -> np.ndarray:
    a = np.fromiter(s, np.int64, len(s))
    a.sort()
    return a


class PartKeyIndex:
    """Tag index for one shard."""

    def __init__(self, schemas=None):
        import os

        # schema registry for lazy blob -> PartKey materialization
        self._schemas = schemas
        # tail tier: label -> value -> set of partIds (new since freeze)
        self._tail: dict[str, dict[str, set[int]]] = defaultdict(
            lambda: defaultdict(set)
        )
        # frozen tier from a snapshot restore: label -> FrozenLabel
        self._frozen: dict[str, FrozenLabel] = {}
        # pids removed since freeze (may still appear in frozen arrays)
        self._deleted: set[int] = set()
        # entries are PartKey objects, or raw key blobs (bytes) after a
        # snapshot restore — materialized lazily via part_key()
        self._part_keys: list[PartKey | bytes | None] = []
        self._start: np.ndarray = np.full(_INIT_CAP, INGESTING, np.int64)
        self._end: np.ndarray = np.full(_INIT_CAP, INGESTING, np.int64)
        self._count = 0
        # native postings store (C++ TagIndex): owns label→value→pid postings
        # for series-create/equals/intersect hot paths; Python keeps times,
        # tombstones and key blobs. Falls back to the pure-Python tiers when
        # the toolchain is absent (or FILODB_NO_NATIVE_INDEX is set).
        self._nt = None
        if not os.environ.get("FILODB_NO_NATIVE_INDEX"):
            try:
                from filodb_tpu.memory.native import TagIndexNative
                self._nt = TagIndexNative()
            except Exception:
                self._nt = None
        # (label, predicate-key) -> (generation, ids): regex/value-scan memo
        self._vscan_cache: dict = {}
        # filters tuple -> (blob, blob addr, npairs): equals-query memo
        self._pairs_cache: dict = {}
        # (starts ref, ends ref, starts addr, ends addr, len) memo
        self._bounds_addr: tuple | None = None

    def __len__(self) -> int:
        return self._count

    @property
    def ram_bytes(self) -> int:
        """Approximate resident bytes of the index tiers (reference
        ``indexRamBytes`` gauge): time arrays + tail postings + native
        postings store."""
        n = self._start.nbytes + self._end.nbytes
        for vals in self._tail.values():
            for pids in vals.values():
                n += 64 + 8 * len(pids)
        for fl in self._frozen.values():
            n += len(fl.vblob) + fl.voff.nbytes + fl.poff.nbytes \
                + fl.pids.nbytes
        if self._nt is not None:
            try:
                n += int(self._nt.ram_bytes())
            except Exception:
                pass
        return n

    def _ensure(self, part_id: int) -> None:
        while part_id >= len(self._start):
            self._start = np.concatenate([self._start,
                                          np.full(len(self._start), INGESTING)])
            self._end = np.concatenate([self._end,
                                        np.full(len(self._end), INGESTING)])
        while part_id >= len(self._part_keys):
            self._part_keys.append(None)

    def add_part_key(self, part_id: int, key: PartKey, start_time: int,
                     end_time: int = INGESTING) -> None:
        self._ensure(part_id)
        if self._part_keys[part_id] is None:
            self._count += 1
        self._part_keys[part_id] = key
        self._start[part_id] = start_time
        self._end[part_id] = end_time
        if self._nt is not None:
            if part_id in self._deleted:
                # pid re-created after a remove: stale postings for the old
                # key would resurrect under a new key — purge them first
                self._nt.purge_pid(part_id)
            self._deleted.discard(part_id)
            from filodb_tpu.core.memstore.native_shard import part_key_blob
            self._nt.add(part_id, part_key_blob(key))
            return
        self._deleted.discard(part_id)
        for name, value in key.labels:
            self._tail[name][value].add(part_id)

    def add_part_key_blob(self, part_id: int, key: PartKey, blob: bytes,
                          start_time: int,
                          end_time: int = INGESTING) -> None:
        """Register postings from ``key`` but keep only the canonical blob
        in the key table (materialized lazily on demand): at high
        cardinality per-series PartKey objects dominate resident memory."""
        if self._nt is not None:
            self._ensure(part_id)
            if self._part_keys[part_id] is None:
                self._count += 1
            self._start[part_id] = start_time
            self._end[part_id] = end_time
            if part_id in self._deleted:
                self._nt.purge_pid(part_id)
                self._deleted.discard(part_id)
            self._nt.add(part_id, blob)
            self._part_keys[part_id] = blob
            return
        self.add_part_key(part_id, key, start_time, end_time)
        self._part_keys[part_id] = blob

    def remove_part_key(self, part_id: int) -> None:
        if self._nt is not None:
            if part_id >= len(self._part_keys) \
                    or self._part_keys[part_id] is None:
                return
            self._deleted.add(part_id)  # postings masked on query
            self._part_keys[part_id] = None
            self._start[part_id] = INGESTING
            self._end[part_id] = INGESTING
            self._count -= 1
            return
        key = self.part_key(part_id)
        if key is None:
            return
        for name, value in key.labels:
            by_value = self._tail.get(name)
            if by_value is not None:
                s = by_value.get(value)
                if s is not None:
                    s.discard(part_id)
                    if not s:
                        del by_value[value]
        self._deleted.add(part_id)  # masks any frozen postings
        self._part_keys[part_id] = None
        self._start[part_id] = INGESTING
        self._end[part_id] = INGESTING
        self._count -= 1

    def update_end_time(self, part_id: int, end_time: int) -> None:
        self._end[part_id] = end_time

    def set_start_time(self, part_id: int, start_time: int) -> None:
        self._start[part_id] = start_time

    def pid_for_exact_key(self, key: PartKey, blob: bytes,
                          exclude: int = -1) -> int | None:
        """Find a live pid whose part key is byte-identical to ``blob``
        (evicted-series identity restore). Label-equals intersection
        narrows candidates; blob equality rejects superset-label matches."""
        from filodb_tpu.core.filters import Equals
        filters = [ColumnFilter(k, Equals(v)) for k, v in key.labels]
        for pid in self.part_ids_from_filters(filters, 0, INGESTING):
            if pid == exclude:
                continue
            stored = self._part_keys[pid] \
                if pid < len(self._part_keys) else None
            if stored is None:
                continue
            if isinstance(stored, bytes):
                if stored == blob:
                    return pid
            else:
                from filodb_tpu.core.memstore.native_shard import (
                    part_key_blob,
                )
                if part_key_blob(stored) == blob:
                    return pid
        return None

    def start_time(self, part_id: int) -> int:
        return int(self._start[part_id])

    def end_time(self, part_id: int) -> int:
        return int(self._end[part_id])

    def time_spans(self, part_ids: np.ndarray) -> tuple[np.ndarray,
                                                        np.ndarray]:
        """Start and end times of ``part_ids`` as the time predicate of
        ``part_ids_from_filters`` reads them now."""
        return self._start[part_ids], self._end[part_ids]

    def part_key(self, part_id: int) -> PartKey | None:
        if part_id >= len(self._part_keys):
            return None
        k = self._part_keys[part_id]
        if isinstance(k, bytes):  # lazy blob from a snapshot restore
            from filodb_tpu.core.memstore.native_shard import (
                part_key_from_blob,
            )
            k = part_key_from_blob(k, self._schemas)
            self._part_keys[part_id] = k
        return k

    # ---- filter evaluation ----------------------------------------------

    def _equals_ids(self, col: str, value: str) -> np.ndarray:
        if self._nt is not None:
            return self._nt.equals(col, value).astype(np.int64)
        parts = []
        fr = self._frozen.get(col)
        if fr is not None:
            vi = fr.find(value.encode())
            if vi >= 0:
                parts.append(fr.pid_slice(vi).astype(np.int64))
        tail = self._tail.get(col)
        if tail is not None:
            s = tail.get(value)
            if s:
                parts.append(_from_set(s))
        if not parts:
            return _EMPTY
        if len(parts) == 1:
            return parts[0]
        return np.unique(np.concatenate(parts))

    def _value_scan_ids(self, col: str, match,
                        cache_key=None, prefix: str | None = None
                        ) -> np.ndarray:
        """Union postings of every value matching the predicate. Native
        path memoizes per (label, predicate) keyed on the postings
        generation — dashboards repeat the same regex scans. ``prefix``
        (a literal regex prefix, see ``filters.regex_plan``) narrows the
        candidate set before the regex runs: binary-searched range on the
        sorted frozen table, cheap ``startswith`` pre-filter elsewhere."""
        if self._nt is not None:
            gen = self._nt.generation
            ck = (col, cache_key) if cache_key is not None else None
            if ck is not None:
                hit = self._vscan_cache.get(ck)
                if hit is not None and hit[0] == gen:
                    return hit[1]
            values = self._nt.values(col)
            if prefix:
                cand = ((i, v) for i, v in enumerate(values)
                        if v.startswith(prefix))
            else:
                cand = enumerate(values)
            vids = np.fromiter((i for i, v in cand if match(v)), np.int32)
            ids = self._nt.union_values(col, vids).astype(np.int64) \
                if len(vids) else _EMPTY
            if ck is not None:
                if len(self._vscan_cache) >= 128:
                    self._vscan_cache.pop(next(iter(self._vscan_cache)))
                self._vscan_cache[ck] = (gen, ids)
            return ids
        parts = []
        fr = self._frozen.get(col)
        if fr is not None:
            if prefix:
                lo, hi = fr.prefix_range(prefix.encode())
                vrange = ((fr.value(vi), vi) for vi in range(lo, hi))
            else:
                vrange = fr.values()
            for vb, vi in vrange:
                if match(vb.decode()):
                    parts.append(fr.pid_slice(vi).astype(np.int64))
        tail = self._tail.get(col)
        if tail is not None:
            for value, s in tail.items():
                if prefix and not value.startswith(prefix):
                    continue
                if s and match(value):
                    parts.append(_from_set(s))
        if not parts:
            return _EMPTY
        return np.unique(np.concatenate(parts))

    def _ids_for_filter(self, f: ColumnFilter) -> np.ndarray:
        flt = f.filter
        if isinstance(flt, Equals):
            return self._equals_ids(f.column, flt.value)
        if isinstance(flt, In):
            parts = [self._equals_ids(f.column, v) for v in flt.values]
            parts = [p for p in parts if len(p)]
            if not parts:
                return _EMPTY
            return np.unique(np.concatenate(parts))
        if isinstance(flt, EqualsRegex):
            # FastRegexMatcher-style rewriting: literals and literal
            # alternations become postings lookups; a literal prefix
            # narrows the value scan (reference leans on Lucene regex
            # automata, PartKeyLuceneIndex.scala:455)
            from filodb_tpu.core.filters import regex_plan
            kind, arg = regex_plan(flt.pattern)
            if kind == "literal":
                return self._equals_ids(f.column, arg)
            if kind == "alts":
                parts = [self._equals_ids(f.column, v) for v in arg]
                parts = [p for p in parts if len(p)]
                if not parts:
                    return _EMPTY
                return np.unique(np.concatenate(parts))
            return self._value_scan_ids(f.column, flt.matches,
                                        cache_key=_filter_cache_key(flt),
                                        prefix=arg if kind == "prefix"
                                        else None)
        # NotEqualsRegex/NotEquals that can't match an absent label ("":
        # doesn't match): the per-label value scan is a sound positive filter
        return self._value_scan_ids(f.column, flt.matches,
                                    cache_key=_filter_cache_key(flt))

    def _label_all_ids(self, col: str) -> np.ndarray:
        """Every pid that has ANY value for this label."""
        if self._nt is not None:
            return self._nt.label_all(col).astype(np.int64)
        parts = []
        fr = self._frozen.get(col)
        if fr is not None and len(fr.pids):
            parts.append(np.unique(fr.pids).astype(np.int64))
        tail = self._tail.get(col)
        if tail is not None:
            for s in tail.values():
                if s:
                    parts.append(_from_set(s))
        if not parts:
            return _EMPTY
        return np.unique(np.concatenate(parts)) if len(parts) > 1 \
            else parts[0]

    def _all_live_ids(self) -> np.ndarray:
        # live entries have real start bounds (tombstones carry INGESTING) —
        # no key materialization needed
        n = len(self._part_keys)
        return np.flatnonzero(self._start[:n] != INGESTING).astype(np.int64)

    def _ids_for_filter_set(self, f: ColumnFilter) -> set[int]:
        """Tail-only postings as a set (fast path: nothing frozen)."""
        by_value = self._tail.get(f.column)
        flt = f.filter
        if by_value is None:
            return set()
        if isinstance(flt, Equals):
            return by_value.get(flt.value) or set()
        if isinstance(flt, In):
            out: set[int] = set()
            for v in flt.values:
                out |= by_value.get(v, set())
            return out
        out = set()
        for value, ids in by_value.items():
            if flt.matches(value):
                out |= ids
        return out

    def _native_query_prep(self, key: tuple):
        """(pairs_entry, bounds_snapshot) for the native query fast paths
        — memoized encoded pair buffers + raw bounds addresses."""
        ent = self._pairs_cache.get(key)
        if ent is None:
            from filodb_tpu.memory.native import TagIndexNative
            blob = TagIndexNative.encode_pairs(list(key))
            ent = (blob, TagIndexNative.addr_of(blob), len(key))
            if len(self._pairs_cache) >= 256:
                self._pairs_cache.pop(next(iter(self._pairs_cache)))
            self._pairs_cache[key] = ent
        ba = self._bounds_addr
        if ba is None or ba[0] is not self._start:
            ba = self._bounds_addr = (
                self._start, self._end, self._start.ctypes.data,
                self._end.ctypes.data, len(self._start))
        return ent, ba

    def part_ids_from_filters(
        self, filters: list[ColumnFilter], start_time: int, end_time: int
    ) -> list[int]:
        """Intersect filter postings, then apply the time overlap predicate
        (reference ``partIdsFromFilters:494``). Set ops while everything is
        in the mutable tail; sorted-array ops once a frozen tier exists;
        pure-Equals batches intersect natively (galloping, one C++ call)."""
        if self._nt is not None and not self._deleted and filters \
                and all(type(f.filter) is Equals for f in filters):
            # all-Equals fast path: intersection + time predicate in one
            # native call (the dominant query shape — shard-key lookups);
            # encoded pair buffers and raw bounds addresses are cached
            key = tuple((f.column, f.filter.value) for f in filters)
            ent, ba = self._native_query_prep(key)
            return self._nt.query_equals(ent[1], ent[2], ba[2], ba[3],
                                         ba[4], start_time, end_time)
        if self._nt is not None and not self._deleted and filters:
            # equals + positive-regex fast path: cached regex postings ride
            # into the native call as a sorted allow-list; intersection AND
            # the time predicate run in one C++ pass
            eqs = [f for f in filters if type(f.filter) is Equals]
            regs = [f for f in filters if isinstance(f.filter, EqualsRegex)
                    and not f.filter.matches("")]
            if regs and len(eqs) + len(regs) == len(filters):
                allow = None
                for f in regs:
                    ids = self._ids_for_filter(f)
                    allow = ids if allow is None \
                        else _intersect_sorted(allow, ids)
                    if not len(allow):
                        return []
                key = tuple((f.column, f.filter.value) for f in eqs)
                ent, ba = self._native_query_prep(key)
                return self._nt.query_equals_allow(
                    ent[1], ent[2], allow, ba[2], ba[3], ba[4],
                    start_time, end_time)
        if self._nt is None and not self._frozen:
            return self._part_ids_set_path(filters, start_time, end_time)
        result: np.ndarray | None = None
        negatives: list[ColumnFilter] = []
        eq_pairs: list[tuple[str, str]] = []
        others: list[ColumnFilter] = []
        for f in filters:
            flt = f.filter
            if self._nt is not None and isinstance(flt, Equals):
                eq_pairs.append((f.column, flt.value))
            elif isinstance(flt, (Equals, In)) or (
                    isinstance(flt, EqualsRegex) and not flt.matches("")):
                others.append(f)
            else:
                negatives.append(f)
        if eq_pairs:
            result = self._nt.intersect_equals(eq_pairs).astype(np.int64)
            if not len(result):
                return []
        for f in others:
            ids = self._ids_for_filter(f)
            result = ids if result is None \
                else _intersect_sorted(result, ids)
            if not len(result):
                return []
        if result is None:
            result = self._all_live_ids()
        if self._deleted and len(result):
            dead = _from_set(self._deleted)
            result = result[~np.isin(result, dead, assume_unique=True)]
        for f in negatives:
            # match semantics: absent label == "" for negative/regex
            # filters. Evaluated against the label's VALUE TABLE (frozen +
            # tail) — never by materializing per-series keys: keep pids
            # whose value matches, plus pids lacking the label entirely
            # when the filter matches "".
            if not len(result):
                break
            matched = self._value_scan_ids(
                f.column, f.filter.matches,
                cache_key=_filter_cache_key(f.filter))
            keep = result[np.isin(result, matched)] if len(matched) \
                else result[:0]
            if f.filter.matches(""):
                has_label = self._label_all_ids(f.column)
                absent = result[~np.isin(result, has_label)] \
                    if len(has_label) else result
                keep = np.union1d(keep, absent)
            result = keep
        if not len(result):
            return []
        ok = (self._start[result] <= end_time) & (self._end[result] >= start_time)
        return result[ok].tolist()

    def _part_ids_set_path(self, filters, start_time, end_time) -> list[int]:
        result: set[int] | None = None
        negatives: list[ColumnFilter] = []
        for f in filters:
            flt = f.filter
            if isinstance(flt, (Equals, In)):
                ids = self._ids_for_filter_set(f)
                result = set(ids) if result is None else result & ids
                if not result:
                    return []
            elif isinstance(flt, EqualsRegex) and not flt.matches(""):
                ids = self._ids_for_filter_set(f)
                result = ids if result is None else result & ids
                if not result:
                    return []
            else:
                negatives.append(f)
        if result is None:
            result = set(self._all_live_ids().tolist())
        for f in negatives:
            keep = set()
            for pid in result:
                key = self.part_key(pid)
                if key is not None and f.filter.matches(
                        key.label_map.get(f.column, "")):
                    keep.add(pid)
            result = keep
        if not result:
            return []
        ids = np.fromiter(result, dtype=np.int64)
        ok = (self._start[ids] <= end_time) & (self._end[ids] >= start_time)
        return sorted(int(i) for i in ids[ok])

    # ---- label introspection --------------------------------------------

    def label_names(self) -> list[str]:
        if self._nt is not None:
            return sorted(set(self._nt.labels()))
        names = {k for k, v in self._tail.items() if any(v.values())}
        names |= set(self._frozen.keys())
        return sorted(names)

    def label_values(self, label: str,
                     filters: list[ColumnFilter] | None = None,
                     start_time: int = 0, end_time: int = INGESTING) -> list[str]:
        if self._nt is not None:
            return self._label_values_native(label, filters, start_time,
                                             end_time)
        fr = self._frozen.get(label)
        tail = self._tail.get(label)
        if fr is None and not tail:
            return []
        if not filters:
            out = {v for v, s in (tail or {}).items() if s}
            if fr is not None:
                if self._deleted:
                    dead = _from_set(self._deleted)
                    for vb, vi in fr.values():
                        sl = fr.pid_slice(vi)
                        if len(sl) and not np.isin(
                                sl, dead, assume_unique=True).all():
                            out.add(vb.decode())
                else:
                    out |= {vb.decode() for vb, _ in fr.values()}
            return sorted(out)
        ids = np.asarray(
            self.part_ids_from_filters(filters, start_time, end_time),
            np.int64)
        out = set()
        if len(ids):
            if fr is not None:
                for vb, vi in fr.values():
                    if np.isin(fr.pid_slice(vi), ids).any():
                        out.add(vb.decode())
            for value, s in (tail or {}).items():
                if s and not s.isdisjoint(ids.tolist()):
                    out.add(value)
        return sorted(out)

    def _label_values_native(self, label, filters, start_time,
                             end_time) -> list[str]:
        values = self._nt.values(label)
        if not values:
            return []
        if not filters:
            if not self._deleted:
                return sorted(set(values))
            dead = _from_set(self._deleted)
            out = set()
            for v in values:
                sl = self._nt.equals(label, v).astype(np.int64)
                if len(sl) and not np.isin(sl, dead,
                                           assume_unique=True).all():
                    out.add(v)
            return sorted(out)
        ids = np.asarray(
            self.part_ids_from_filters(filters, start_time, end_time),
            np.int64)
        if not len(ids):
            return []
        out = set()
        for v in values:
            sl = self._nt.equals(label, v).astype(np.int64)
            if len(sl) and np.isin(sl, ids).any():
                out.add(v)
        return sorted(out)

    # ---- snapshot support -----------------------------------------------

    def frozen_labels(self):
        """Yield (label, FrozenLabel) merging the frozen and tail tiers with
        deletions applied — the snapshot writer's view. A frozen label with
        no tail additions and no deletions is yielded as-is (re-serialized
        wholesale, no per-value work)."""
        if self._nt is not None:
            dead = np.asarray(sorted(self._deleted), np.int32) \
                if self._deleted else np.empty(0, np.int32)
            for name in sorted(set(self._nt.labels())):
                voff, vblob, poff, pids = self._nt.export_label(name, dead)
                if len(voff) > 1:
                    yield name, FrozenLabel(voff, vblob, poff, pids)
            return
        dead = _from_set(self._deleted) if self._deleted else None
        labels = set(self._tail.keys()) | set(self._frozen.keys())
        for name in sorted(labels):
            fr = self._frozen.get(name)
            tail = {v: s for v, s in (self._tail.get(name) or {}).items()
                    if s}
            if fr is not None and not tail and dead is None:
                yield name, fr
                continue
            merged: dict[bytes, list] = {}
            if fr is not None:
                for vb, vi in fr.values():
                    sl = fr.pid_slice(vi)
                    if dead is not None and len(sl):
                        sl = sl[~np.isin(sl, dead, assume_unique=True)]
                    if len(sl):
                        merged[vb] = [sl]
            for value, s in tail.items():
                merged.setdefault(value.encode(), []).append(sorted(s))
            pairs = []
            for vb, seqs in merged.items():
                seq = seqs[0] if len(seqs) == 1 \
                    else np.unique(np.concatenate(
                        [np.asarray(a, np.int64) for a in seqs]))
                pairs.append((vb, seq))
            if pairs:
                yield name, FrozenLabel.build(pairs)

    def load_frozen(self, label: str, frozen: FrozenLabel) -> None:
        if self._nt is not None:
            self._nt.load_label(label, frozen.voff,
                                bytes(frozen.vblob), frozen.poff,
                                frozen.pids)
            return
        self._frozen[label] = frozen
