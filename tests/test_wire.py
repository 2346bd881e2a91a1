"""Wire codec + transport hardening tests.

Reference counterpart: the Kryo serializer registration
(``client/Serializer.scala:23-64``) — a closed class registry. Unlike Kryo
-over-Akka, the transport also enforces a shared-secret handshake and frame
caps.
"""

import struct

import numpy as np
import pytest

from filodb_tpu.coordinator.remote import (
    PlanExecutorServer,
    RemotePlanDispatcher,
)
from filodb_tpu.coordinator.wire import MAX_FRAME, decode, encode


class TestWireCodec:
    def test_primitives(self):
        for v in (None, True, False, 0, -5, 2**40, 1.5, "héllo", b"\x00ab",
                  [1, "a"], (1, (2, 3)), {"k": [1.0]}, frozenset({"x", "y"})):
            assert decode(encode(v)) == v

    def test_ndarrays(self):
        for a in (np.arange(5), np.zeros((2, 3), np.float32),
                  np.array([], np.int64), np.ones((2, 2, 2), bool)):
            b = decode(encode(a))
            assert b.dtype == a.dtype and b.shape == a.shape
            np.testing.assert_array_equal(a, b)

    def test_unknown_class_rejected_on_decode(self):
        # forge an object frame naming a class outside the registry
        name = b"OsSystemPwner"
        forged = b"O" + struct.pack("<I", len(name)) + name + \
            struct.pack("<H", 0)
        with pytest.raises(ValueError, match="unknown wire class"):
            decode(forged)

    def test_unregistered_class_rejected_on_encode(self):
        class NotRegistered:
            pass
        with pytest.raises(TypeError, match="not wire-serializable"):
            encode(NotRegistered())

    def test_exec_plan_round_trip(self):
        from filodb_tpu.core.filters import ColumnFilter, Equals
        from filodb_tpu.query.exec.plan import SelectRawPartitionsExec
        from filodb_tpu.query.exec.transformers import PeriodicSamplesMapper
        plan = SelectRawPartitionsExec(
            shard=1, filters=(ColumnFilter("_metric_", Equals("m")),),
            chunk_start=5, chunk_end=10,
            transformers=[PeriodicSamplesMapper(start=5, step=1, end=10,
                                                window=2, function="rate")])
        p2 = decode(encode(plan))
        assert repr(p2) == repr(plan)
        assert p2.transformers[0].function == "rate"


class TestTransportHardening:
    def test_auth_required_when_secret_set(self):
        srv = PlanExecutorServer(None, secret="s3cret").start()
        try:
            d = RemotePlanDispatcher("127.0.0.1", srv.port)
            # no auth (env secret unset on the client side): server rejects
            with pytest.raises((ConnectionError, RuntimeError, OSError)):
                d.call("ping")
        finally:
            srv.stop()

    def test_auth_succeeds_with_secret(self, monkeypatch):
        monkeypatch.setenv("FILODB_CLUSTER_SECRET", "topsecret")
        srv = PlanExecutorServer(None).start()  # picks up env secret
        try:
            d = RemotePlanDispatcher("127.0.0.1", srv.port)
            d._drop_conn()  # force a fresh (authenticated) connection
            assert d.ping()
        finally:
            srv.stop()
            d._drop_conn()

    def test_wrong_secret_rejected(self, monkeypatch):
        srv = PlanExecutorServer(None, secret="right").start()
        monkeypatch.setenv("FILODB_CLUSTER_SECRET", "wrong")
        try:
            d = RemotePlanDispatcher("127.0.0.1", srv.port)
            d._drop_conn()
            assert not d.ping()  # auth rejected → no pong
        finally:
            srv.stop()
            d._drop_conn()

    def test_oversized_frame_rejected(self):
        srv = PlanExecutorServer(None).start()
        try:
            import socket
            s = socket.create_connection(("127.0.0.1", srv.port), timeout=5)
            s.sendall(struct.pack("<I", MAX_FRAME + 1))
            # server drops the connection without reading the body
            s.settimeout(2)
            assert s.recv(4) == b""
            s.close()
        finally:
            srv.stop()

    def test_truncated_frame_rejected(self):
        b = encode("hello world")
        with pytest.raises(ValueError, match="truncated"):
            decode(b[:-4])

    def test_trailing_bytes_rejected(self):
        with pytest.raises(ValueError, match="trailing"):
            decode(encode(1) + b"XX")

    def test_stateful_dispatcher_rejected_at_encode(self):
        from filodb_tpu.coordinator.cluster import Node, NodeDispatcher
        nd = NodeDispatcher(Node("n", None))
        with pytest.raises(TypeError, match="no wire fields"):
            encode(nd)

    def test_preauth_frame_cap(self):
        import socket
        from filodb_tpu.coordinator.remote import AUTH_FRAME_CAP
        srv = PlanExecutorServer(None, secret="s").start()
        try:
            s = socket.create_connection(("127.0.0.1", srv.port), timeout=5)
            s.sendall(struct.pack("<I", AUTH_FRAME_CAP + 1))
            s.settimeout(2)
            assert s.recv(4) == b""  # dropped before reading the body
            s.close()
        finally:
            srv.stop()

    def test_no_pickle_on_the_wire(self):
        # the encoded execute message must not contain pickle opcodes
        from filodb_tpu.query.model import QueryContext
        b = encode(("execute", "ds", None, QueryContext()))
        assert not b.startswith(b"\x80")
        assert b"\x80\x05" not in b


class TestLegacyContainerGate:
    def test_v1_pickle_rejected_by_default(self, monkeypatch):
        import pickle, struct as _s
        from filodb_tpu.core.record import RecordContainer
        monkeypatch.delenv("FILODB_ALLOW_LEGACY_WAL", raising=False)
        payload = pickle.dumps([("gauge", (("_metric_", "old"),), 1, (1.0,))])
        legacy = _s.pack("<BI", 1, len(payload)) + payload
        with pytest.raises(ValueError, match="legacy v1"):
            RecordContainer.deserialize(legacy)

    def test_v1_allowed_when_opted_in(self, monkeypatch):
        import pickle, struct as _s
        from filodb_tpu.core.record import RecordContainer
        monkeypatch.setenv("FILODB_ALLOW_LEGACY_WAL", "1")
        payload = pickle.dumps([("gauge", (("_metric_", "old"),), 1, (1.0,))])
        legacy = _s.pack("<BI", 1, len(payload)) + payload
        c = RecordContainer.deserialize(legacy)
        assert list(c)[0].timestamp == 1
