"""The bulk loader: a copy of ``chip_smoke._record_templates`` and
``chip_smoke.phase_load`` (PR 21). A default-layout memstore
(``conf/server.json``'s dataset block, read through ``ServerConfig.load``)
loaded through serialized record containers, routed to shards with the
gateway's own hash — what a shard's WAL consumer hands to
``memstore.ingest`` — shards one after another. Threads do not help: two
fifths of a load is a Python loop under the GIL (``PERF.md``)."""

from __future__ import annotations

import dataclasses
import struct
import time

import numpy as np

DATASET = "timeseries"


def _record_templates(keys: list, idx) -> tuple:
    """The given series' container records with zero timestamp and value,
    as ``RecordContainer.serialize`` writes them (v2: ``u32 len | u32 hash
    | i64 ts | ... | u8 tag | f64 value``), concatenated, and the byte
    columns where each record's timestamp and value go."""
    from filodb_tpu.core.record import IngestRecord, RecordContainer

    header = len(RecordContainer().serialize())
    recs = [RecordContainer([IngestRecord(keys[i], 0, (0.0,))])
            .serialize()[header:] for i in idx]
    lens = np.fromiter(map(len, recs), np.int64, len(recs))
    starts = np.concatenate([[0], np.cumsum(lens)[:-1]])
    eight = np.arange(8)
    ts_cols = (starts[:, None] + 8 + eight).ravel()      # after len + hash
    val_cols = (starts[:, None] + lens[:, None] - 8 + eight).ravel()
    return np.frombuffer(b"".join(recs), np.uint8), ts_cols, val_cols


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


def load(metrics: dict, steps_per_container: int = 40):
    """Returns (memstore, report). Every loaded row is counted back: rows
    ingested == series × samples, and the index holds every series."""
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
        ts, vals = metric["ts"], metric["vals"]
        samples = ts.shape[1]
        n_series += len(keys)
        want += len(keys) * samples
        t0 = time.perf_counter()
        for s in range(num_shards):
            idx = np.nonzero(shard_of == s)[0]
            if not len(idx):
                continue
            base, ts_cols, val_cols = _record_templates(keys, idx)
            for c0 in range(0, samples, steps_per_container):
                c1 = min(c0 + steps_per_container, samples)
                blob = np.tile(base, (c1 - c0, 1))
                blob[:, ts_cols] = np.ascontiguousarray(
                    ts[idx, c0:c1].T).view(np.uint8).reshape(c1 - c0, -1)
                blob[:, val_cols] = np.ascontiguousarray(
                    vals[idx, c0:c1].T).view(np.uint8).reshape(c1 - c0, -1)
                raw = struct.pack("<BI", 2, blob.shape[0] * len(idx)) \
                    + blob.tobytes()
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
                                     for sh in shards),
                "keys_s": t_keys, "ingest_s": ingest_s}
