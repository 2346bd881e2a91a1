"""The bulk loader: a copy of ``chip_smoke._record_templates`` and
``chip_smoke.phase_load`` (PR 21), taught the record layout of every value
column (PR 36). A default-layout memstore (``conf/server.json``'s dataset
block, read through ``ServerConfig.load``) loaded through serialized record
containers, routed to shards with the gateway's own hash — what a shard's
WAL consumer hands to ``memstore.ingest`` — shards one after another.
Threads do not help: two fifths of a load is a Python loop under the GIL
(``PERF.md``).

A metric's ``vals`` is either f64 ``[N, S]`` (the schema's one value column)
or a mapping in the schema's column order, a column either f64 ``[N, S]``
or a histogram ``{"les": f64 [B], "counts": int64 [N, S, B]}`` (cumulative
buckets, the ``les`` the same for every series of the metric)."""

from __future__ import annotations

import dataclasses
import struct
import time

import numpy as np

DATASET = "timeseries"

# A container's bytes exist three times while it is made (the tiled
# templates, ``tobytes``, the header joined on), so they are bounded: by
# steps (40, as since PR 21) and by bytes. 512 MiB is above the largest
# container a listed cell builds (tsbs-cpu-10k: 25,000 series a shard x 40
# steps x ~320 B = 320 MB), so the scalar cells' containers, and with them
# their bytes and their load time, are what they were; the draft
# ``histo-fleet`` (2,500 series a shard x 40 steps x 1.2 KB = 118 MB) is
# under it too. It is there for a histogram deployment at 100,000 series,
# whose 40-step container would be 4.7 GB and 14 GB in flight.
STEPS_PER_CONTAINER = 40
MAX_CONTAINER_BYTES = 512 << 20


def value_columns(metric: dict) -> list:
    """The metric's value columns in the schema's order, each ``(name,
    array, les)``: f64 ``[N, S]`` and None, or a histogram's int64 counts
    ``[N, S, B]`` and its f64 ``les`` ``[B]``."""
    vals = metric["vals"]
    if isinstance(vals, np.ndarray):
        return [("value", vals, None)]
    return [(name, c["counts"], np.asarray(c["les"], np.float64))
            if isinstance(c, dict) else (name, c, None)
            for name, c in vals.items()]


def _record_templates(keys: list, idx, columns: list) -> tuple:
    """The given series' container records with zero timestamp and values,
    as ``RecordContainer.serialize`` writes them, concatenated; the byte
    columns where each record's timestamp goes; and, a value column each,
    the byte columns where its value goes. The layout is the one
    ``core/record.py`` documents (v2)::

        u32 len | u32 hash | i64 ts | u16 schema | labels | u8 nvals | values
        value: u8 0 | f64                       the 8 bytes after the tag
               u8 1 | u16 nb | f64*nb | i64*nb  the 8*nb bytes of counts;
                                                the les stay the template's

    The values are a record's tail, so each is found from the record's end."""
    from filodb_tpu.core.record import IngestRecord, RecordContainer

    header = len(RecordContainer().serialize())
    zeros = tuple(0.0 if les is None else (les, np.zeros(len(les), np.int64))
                  for _, _, les in columns)
    recs = [RecordContainer([IngestRecord(keys[i], 0, zeros)])
            .serialize()[header:] for i in idx]
    lens = np.fromiter(map(len, recs), np.int64, len(recs))
    starts = np.concatenate([[0], np.cumsum(lens)[:-1]])
    ts_cols = (starts[:, None] + 8 + np.arange(8)).ravel()  # after len + hash
    val_cols, tail = [], 0
    for _, _, les in reversed(columns):
        width = 8 if les is None else 8 * len(les)
        tail += width
        val_cols.append((starts[:, None] + lens[:, None] - tail
                         + np.arange(width)).ravel())
        tail += 1 if les is None else 3 + width   # the tag; u16 nb, the les
    return (np.frombuffer(b"".join(recs), np.uint8), ts_cols,
            val_cols[::-1])


def _part_keys(metric: dict) -> list:
    from filodb_tpu.core.partkey import PartKey

    names = list(metric["labels"])
    cols = [metric["labels"][n].tolist() for n in names]
    return [PartKey.create(metric["schema"],
                           {"_metric_": metric["name"],
                            **dict(zip(names, row))})
            for row in zip(*cols)]


def server_layout() -> dict:
    """What a default-config server runs with, in the configuration file's
    words."""
    from filodb_tpu.config import ServerConfig

    cfg = ServerConfig.load(None)
    ing = cfg.datasets[DATASET]
    return {"dataset": DATASET, "num_shards": ing.num_shards,
            "spread": cfg.spreads[DATASET],
            "max_chunk_size": ing.store.max_chunk_size,
            "engine": cfg.engines[DATASET],
            "result_cache": bool(cfg.result_cache)}


def containers(metric: dict, keys: list, shard_of, num_shards: int):
    """(shard, container bytes) of the whole metric, shard after shard, each
    container some whole scrape steps of every series of the shard."""
    ts = metric["ts"]
    columns = value_columns(metric)
    samples = ts.shape[1]
    for s in range(num_shards):
        idx = np.nonzero(shard_of == s)[0]
        if not len(idx):
            continue
        base, ts_cols, val_cols = _record_templates(keys, idx, columns)
        steps = max(1, min(STEPS_PER_CONTAINER,
                           MAX_CONTAINER_BYTES // len(base)))
        for c0 in range(0, samples, steps):
            c1 = min(c0 + steps, samples)
            blob = np.tile(base, (c1 - c0, 1))
            blob[:, ts_cols] = np.ascontiguousarray(
                ts[idx, c0:c1].T).view(np.uint8).reshape(c1 - c0, -1)
            for cols, (_, a, _) in zip(val_cols, columns):
                # [series, steps(, buckets)] -> a row a step
                blob[:, cols] = np.ascontiguousarray(
                    np.swapaxes(a[idx, c0:c1], 0, 1)).view(
                        np.uint8).reshape(c1 - c0, -1)
            yield s, struct.pack("<BI", 2, blob.shape[0] * len(idx)) \
                + blob.tobytes()


def load(metrics: dict):
    """Returns (memstore, report). Every loaded row is counted back: rows
    ingested == series × samples, and the index holds every series.
    ``native_shards``: every shard has its native core and no partition
    the host made — a container the core declined would have made some."""
    from filodb_tpu.config import ServerConfig
    from filodb_tpu.core.memstore.memstore import TimeSeriesMemStore
    from filodb_tpu.core.partkey import ingestion_shard
    from filodb_tpu.core.record import BytesContainer, SomeData
    from filodb_tpu.memory import native

    cfg = ServerConfig.load(None)
    ing = cfg.datasets[DATASET]
    num_shards, spread = ing.num_shards, cfg.spreads[DATASET]
    ms = TimeSeriesMemStore()
    for s in range(num_shards):
        ms.setup(DATASET, s, dataclasses.replace(ing.store))
    rows = want = n_series = offset = 0
    t_keys = ingest_s = 0.0
    for metric in metrics.values():
        t0 = time.perf_counter()
        keys = _part_keys(metric)
        shard_of = np.fromiter(
            (ingestion_shard(k.shard_key_hash(("_ws_", "_ns_", "_metric_")),
                             k.part_hash, num_shards, spread)
             for k in keys), np.int64, len(keys))
        t_keys += time.perf_counter() - t0
        n_series += len(keys)
        want += len(keys) * metric["ts"].shape[1]
        t0 = time.perf_counter()
        for s, raw in containers(metric, keys, shard_of, num_shards):
            rows += ms.ingest(DATASET, s,
                              SomeData(BytesContainer(raw), offset))
            offset += 1
        ingest_s += time.perf_counter() - t0
    shards = ms.shards_for(DATASET)
    indexed = sum(len(sh.index) for sh in shards)
    if rows != want or indexed != n_series:
        raise RuntimeError(f"loaded {rows} of {want} rows and indexed "
                           f"{indexed} of {n_series} series")
    return ms, {"series": n_series, "rows": rows,
                "series_per_shard": [len(sh.index) for sh in shards],
                "have_native": native.HAVE_NATIVE,
                "native_shards": all(sh._native_core is not None
                                     and not sh._host_pids
                                     for sh in shards),
                "keys_s": t_keys, "ingest_s": ingest_s}
