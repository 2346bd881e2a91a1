"""Durable local column store + meta store (sqlite-backed).

Counterpart of the reference's Cassandra plugin (``cassandra/`` module) with
the same four-table data model:

- ``chunks``      — (partition, chunkid) → encoded chunkset
  (reference ``TimeSeriesChunksTable.scala:34``)
- ``ingestion_time_index`` — (partition, ingestion_time, chunkid) for
  downsampler/ODP scans by ingestion window
  (reference ``IngestionTimeIndexTable.scala:31``)
- ``partkeys``    — partKey → (startTime, endTime) per shard
  (reference ``PartitionKeysTable.scala:26``)
- ``checkpoints`` — (shard, group) → offset
  (reference ``metastore/CheckpointTable.scala:24``)

sqlite (stdlib) provides the durable KV substrate the way Cassandra does for
the reference; the store interface (``ColumnStore``/``MetaStore``) is the
pluggable seam for object-store/Cassandra backends later.
"""

from __future__ import annotations

import os
import sqlite3
import threading
import time

from filodb_tpu.core.partkey import PartKey
from filodb_tpu.core.store.api import ColumnStore, MetaStore, PartKeyRecord
from filodb_tpu.memory.chunk import Chunk


def _pk_blob(pk: PartKey) -> bytes:
    return pk.serialized


def _pk_from_blob(blob: bytes) -> PartKey:
    parts = blob.split(b"\x00")
    schema = parts[0].decode()
    labels = []
    for p in parts[1:]:
        k, v = p.split(b"\x01", 1)
        labels.append((k.decode(), v.decode()))
    return PartKey(schema, tuple(labels))


class _Db:
    """One sqlite database per (dataset, shard), lazily opened."""

    def __init__(self, root: str):
        self.root = root
        self._conns: dict[tuple[str, int], sqlite3.Connection] = {}
        self._read_locks: dict[tuple[str, int], threading.Lock] = {}
        self._lock = threading.Lock()

    def conn(self, dataset: str, shard: int) -> sqlite3.Connection:
        key = (dataset, shard)
        with self._lock:
            c = self._conns.get(key)
            if c is None:
                d = os.path.join(self.root, dataset)
                os.makedirs(d, exist_ok=True)
                c = sqlite3.connect(os.path.join(d, f"shard-{shard}.db"),
                                    check_same_thread=False)
                # the meta store and the column store hold SEPARATE
                # connections to one shard file; concurrent group flushes
                # interleave chunk and checkpoint writes, so lock waits
                # must block-and-retry instead of raising immediately
                c.execute("PRAGMA busy_timeout=10000")
                # ... except here: the switch of a fresh file to WAL wants
                # an exclusive lock and sqlite raises at once, without the
                # busy handler, when the other store's connection is
                # mid-write (parallel group flushes open both at the same
                # moment) — so this one statement retries by hand. The
                # sleep is under _lock by design: whoever waits for the
                # lock waits for this very connection
                deadline = time.monotonic() + 10.0
                while True:
                    try:
                        c.execute("PRAGMA journal_mode=WAL")
                        break
                    except sqlite3.OperationalError as e:
                        if "locked" not in str(e) \
                                or time.monotonic() > deadline:
                            raise
                        time.sleep(0.005)  # filolint: disable=LD101
                c.execute("PRAGMA synchronous=NORMAL")
                c.execute("""CREATE TABLE IF NOT EXISTS chunks (
                    partition BLOB, chunkid INTEGER, start_time INTEGER,
                    end_time INTEGER, data BLOB,
                    PRIMARY KEY (partition, chunkid))""")
                c.execute("""CREATE TABLE IF NOT EXISTS ingestion_time_index (
                    partition BLOB, ingestion_time INTEGER, chunkid INTEGER,
                    PRIMARY KEY (partition, ingestion_time, chunkid))""")
                c.execute("""CREATE TABLE IF NOT EXISTS partkeys (
                    partition BLOB PRIMARY KEY, start_time INTEGER,
                    end_time INTEGER)""")
                c.execute("""CREATE TABLE IF NOT EXISTS checkpoints (
                    grp INTEGER PRIMARY KEY, offset INTEGER)""")
                # monotonic write counters for snapshot delta-replay
                for tbl in ("chunks", "partkeys"):
                    try:
                        c.execute(f"ALTER TABLE {tbl} ADD COLUMN upd "
                                  "INTEGER DEFAULT 0")
                    except sqlite3.OperationalError:
                        pass  # column already present
                self._read_locks[key] = threading.Lock()
                self._conns[key] = c
            return c

    def read(self, dataset: str, shard: int, sql: str, params=()) -> list:
        """Every row of one SELECT. The connection is shared by all the
        threads of the process (the chunk server answers each client on a
        thread of its own), and sqlite3 keeps ONE prepared statement for
        each SQL text on a connection: two threads that run the same
        SELECT step that one statement at once and get each other's rows,
        or none. So a read holds the connection's read lock from execute
        to the last row. Writers run other statements, under the store's
        write lock."""
        c = self.conn(dataset, shard)
        with self._read_locks[(dataset, shard)]:
            return c.execute(sql, params).fetchall()

    def close(self):
        with self._lock:
            for c in self._conns.values():
                c.close()
            self._conns.clear()
            self._read_locks.clear()


class LocalDiskColumnStore(ColumnStore):
    def __init__(self, root: str):
        self.root = root
        self._db = _Db(root)
        self._wlock = threading.Lock()
        self._upd: dict[tuple[str, int], int] = {}

    def initialize(self, dataset: str, num_shards: int) -> None:
        for s in range(num_shards):
            self._db.conn(dataset, s)

    def _upd_peek(self, c, dataset, shard) -> int:
        """Current write counter, initializing from the db once (caller
        holds _wlock)."""
        key = (dataset, shard)
        cur = self._upd.get(key)
        if cur is None:
            cur = c.execute(
                "SELECT MAX(m) FROM (SELECT COALESCE(MAX(upd),0) m FROM "
                "chunks UNION ALL SELECT COALESCE(MAX(upd),0) FROM partkeys)"
            ).fetchone()[0] or 0
            self._upd[key] = cur
        return cur

    def _next_upd(self, c, dataset, shard) -> int:
        cur = self._upd_peek(c, dataset, shard) + 1
        self._upd[(dataset, shard)] = cur
        return cur

    def write_chunks(self, dataset, shard, part_key, chunks, ingestion_time):
        c = self._db.conn(dataset, shard)
        blob = _pk_blob(part_key)
        with self._wlock:
            upd = self._next_upd(c, dataset, shard)
            c.executemany(
                "INSERT OR IGNORE INTO chunks(partition, chunkid, "
                "start_time, end_time, data, upd) VALUES (?,?,?,?,?,?)",
                [(blob, ch.id, ch.start_time, ch.end_time, ch.serialize(),
                  upd) for ch in chunks])
            c.executemany(
                "INSERT OR IGNORE INTO ingestion_time_index VALUES (?,?,?)",
                [(blob, ingestion_time, ch.id) for ch in chunks])
            c.commit()

    def read_chunks(self, dataset, shard, part_key, start_time, end_time):
        rows = self._db.read(
            dataset, shard,
            "SELECT data FROM chunks WHERE partition=? AND end_time>=? AND "
            "start_time<=? ORDER BY chunkid", (_pk_blob(part_key), start_time,
                                               end_time))
        return [Chunk.deserialize(r[0]) for r in rows]

    def write_part_keys(self, dataset, shard, records):
        c = self._db.conn(dataset, shard)
        with self._wlock:
            upd = self._next_upd(c, dataset, shard)
            for r in records:
                c.execute(
                    "INSERT INTO partkeys(partition, start_time, end_time, "
                    "upd) VALUES (?,?,?,?) ON CONFLICT(partition)"
                    " DO UPDATE SET start_time=MIN(start_time, excluded."
                    "start_time), end_time=excluded.end_time, "
                    "upd=excluded.upd",
                    (_pk_blob(r.part_key), r.start_time, r.end_time, upd))
            c.commit()

    def scan_part_keys(self, dataset, shard):
        rows = self._db.read(
            dataset, shard,
            "SELECT partition, start_time, end_time FROM partkeys")
        return [PartKeyRecord(_pk_from_blob(b), st, et) for b, st, et in rows]

    def scan_chunks_by_ingestion_time(self, dataset, shard, start, end):
        parts = self._db.read(
            dataset, shard,
            "SELECT DISTINCT partition FROM ingestion_time_index WHERE "
            "ingestion_time>=? AND ingestion_time<?", (start, end))
        for (blob,) in parts:
            ids = [r[0] for r in self._db.read(
                dataset, shard,
                "SELECT chunkid FROM ingestion_time_index WHERE partition=? "
                "AND ingestion_time>=? AND ingestion_time<?",
                (blob, start, end))]
            if not ids:
                continue
            q = ",".join("?" * len(ids))
            rows = self._db.read(
                dataset, shard,
                f"SELECT data FROM chunks WHERE partition=? AND chunkid IN "
                f"({q}) ORDER BY chunkid", (blob, *ids))
            yield _pk_from_blob(blob), [Chunk.deserialize(r[0]) for r in rows]

    def truncate(self, dataset):
        import glob
        import os as _os
        self._db.close()
        for f in glob.glob(os.path.join(self.root, dataset, "shard-*.db*")):
            _os.remove(f)

    def delete_part_keys(self, dataset, shard, part_keys):
        c = self._db.conn(dataset, shard)
        with self._wlock:
            for pk in part_keys:
                blob = _pk_blob(pk)
                c.execute("DELETE FROM partkeys WHERE partition=?", (blob,))
                c.execute("DELETE FROM chunks WHERE partition=?", (blob,))
                c.execute("DELETE FROM ingestion_time_index WHERE "
                          "partition=?", (blob,))
            c.commit()

    def max_persisted_ts(self, dataset, shard):
        rows = self._db.read(
            dataset, shard,
            "SELECT partition, MAX(end_time) FROM chunks GROUP BY partition")
        return {_pk_from_blob(b): int(mx) for b, mx in rows}

    def max_persisted_ts_since(self, dataset, shard, chunk_token):
        rows = self._db.read(
            dataset, shard,
            "SELECT partition, MAX(end_time) FROM chunks WHERE upd > ? "
            "GROUP BY partition", (chunk_token,))
        return {_pk_from_blob(b): int(mx) for b, mx in rows}

    def scan_part_keys_since(self, dataset, shard, pk_token):
        rows = self._db.read(
            dataset, shard,
            "SELECT partition, start_time, end_time FROM partkeys "
            "WHERE upd > ?", (pk_token,))
        return [PartKeyRecord(_pk_from_blob(b), st, et) for b, st, et in rows]

    def update_tokens(self, dataset, shard):
        c = self._db.conn(dataset, shard)
        with self._wlock:
            cur = self._upd_peek(c, dataset, shard)
        return (cur, cur)

    def write_index_snapshot(self, dataset, shard, data):
        d = os.path.join(self.root, dataset)
        os.makedirs(d, exist_ok=True)
        path = os.path.join(d, f"index-shard-{shard}.snap")
        tmp = path + ".tmp"
        with open(tmp, "wb") as f:
            f.write(data)
        os.replace(tmp, path)  # atomic: readers never see a partial file

    def read_index_snapshot(self, dataset, shard):
        path = os.path.join(self.root, dataset, f"index-shard-{shard}.snap")
        try:
            with open(path, "rb") as f:
                return f.read()
        except FileNotFoundError:
            return None

    # migration manifests: atomic-replace files beside the shard db, so a
    # crashed handoff resumes from durable phase state after restart
    def write_migration_manifest(self, dataset, shard, data):
        d = os.path.join(self.root, dataset)
        os.makedirs(d, exist_ok=True)
        path = os.path.join(d, f"migration-shard-{shard}.json")
        tmp = path + ".tmp"
        with open(tmp, "wb") as f:
            f.write(data)
        os.replace(tmp, path)

    def read_migration_manifest(self, dataset, shard):
        path = os.path.join(self.root, dataset,
                            f"migration-shard-{shard}.json")
        try:
            with open(path, "rb") as f:
                return f.read()
        except FileNotFoundError:
            return None

    def delete_migration_manifest(self, dataset, shard):
        path = os.path.join(self.root, dataset,
                            f"migration-shard-{shard}.json")
        try:
            os.remove(path)
        except FileNotFoundError:
            pass

    def close(self):
        self._db.close()


class LocalDiskMetaStore(MetaStore):
    def __init__(self, root: str):
        self._db = _Db(root)
        self._wlock = threading.Lock()

    def write_checkpoint(self, dataset, shard, group, offset):
        c = self._db.conn(dataset, shard)
        with self._wlock:
            c.execute("INSERT INTO checkpoints VALUES (?,?) ON CONFLICT(grp) "
                      "DO UPDATE SET offset=excluded.offset", (group, offset))
            c.commit()

    def read_checkpoints(self, dataset, shard):
        return dict(self._db.read(dataset, shard,
                                  "SELECT grp, offset FROM checkpoints"))

    # cost-model snapshots: atomic-replace file beside the dataset's shard
    # dbs, so learned estimates survive a restart (query/cost_model.py)
    def write_cost_model(self, dataset, data):
        d = os.path.join(self._db.root, dataset)
        os.makedirs(d, exist_ok=True)
        path = os.path.join(d, "costmodel.json")
        tmp = path + ".tmp"
        with open(tmp, "wb") as f:
            f.write(data)
        os.replace(tmp, path)

    def read_cost_model(self, dataset):
        path = os.path.join(self._db.root, dataset, "costmodel.json")
        try:
            with open(path, "rb") as f:
                return f.read()
        except FileNotFoundError:
            return None

    def close(self):
        self._db.close()
