"""DNS SRV discovery against a stub UDP resolver.

The third seed-discovery strategy must be real, testable
code — a stdlib wire-format resolver (``utils/dns_srv.py``), exercised here
against a canned-response DNS server including name compression.
Reference: ``akka-bootstrapper/.../DnsSrvClusterSeedDiscovery.scala:1-122``.
"""

import socket
import struct
import threading

import pytest

from filodb_tpu.coordinator.bootstrap import DnsSrvDiscovery
from filodb_tpu.utils.dns_srv import (
    DnsError,
    build_query,
    encode_qname,
    parse_srv_response,
    read_name,
    resolve_srv,
)


def _srv_rdata(prio, weight, port, target: bytes) -> bytes:
    return struct.pack(">HHH", prio, weight, port) + target


def _answer(name_bytes: bytes, rdata: bytes) -> bytes:
    return name_bytes + struct.pack(">HHIH", 33, 1, 60, len(rdata)) + rdata


def _response(query: bytes, answers: list[bytes], rcode=0) -> bytes:
    txid = struct.unpack(">H", query[:2])[0]
    q_section = query[12:]
    header = struct.pack(">HHHHHH", txid, 0x8180 | rcode, 1, len(answers),
                         0, 0)
    return header + q_section + b"".join(answers)


class StubResolver:
    """One-shot UDP DNS server answering every query with canned SRV
    records (compression pointer to the question name exercised)."""

    def __init__(self, records):
        self.records = records  # list of (prio, weight, port, target_str)
        self.sock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        self.sock.bind(("127.0.0.1", 0))
        self.port = self.sock.getsockname()[1]
        self._thread = threading.Thread(target=self._serve, daemon=True)
        self._thread.start()

    def _serve(self):
        try:
            while True:
                query, addr = self.sock.recvfrom(4096)
                # name pointer to offset 12 (the question name)
                ptr = struct.pack(">H", 0xC000 | 12)
                answers = [
                    _answer(ptr, _srv_rdata(p, w, port,
                                            encode_qname(target)))
                    for (p, w, port, target) in self.records
                ]
                self.sock.sendto(_response(query, answers), addr)
        except OSError:
            pass  # socket closed

    def close(self):
        self.sock.close()


class TestWireFormat:
    def test_qname_roundtrip(self):
        raw = encode_qname("_filodb._tcp.example.com")
        name, off = read_name(raw, 0)
        assert name == "_filodb._tcp.example.com"
        assert off == len(raw)

    def test_compression_pointer(self):
        # message: [2 pad bytes][example.com][label "a" + ptr->2]
        base = b"xx" + encode_qname("example.com")
        ptr_name = b"\x01a" + struct.pack(">H", 0xC000 | 2)
        msg = base + ptr_name
        name, off = read_name(msg, len(base))
        assert name == "a.example.com"
        assert off == len(msg)

    def test_compression_loop_rejected(self):
        # pointer at offset 2 pointing to offset 0, which points to 2 …
        msg = struct.pack(">H", 0xC000 | 2) + struct.pack(">H", 0xC000 | 0)
        with pytest.raises(DnsError):
            read_name(msg, 2)

    def test_txid_mismatch_rejected(self):
        q = build_query("x.example.com", 7)
        resp = _response(q, [])
        with pytest.raises(DnsError):
            parse_srv_response(resp, 8)


class TestStubResolution:
    def test_resolves_and_orders_by_priority_weight(self):
        stub = StubResolver([
            (10, 5, 9001, "node-b.example.com"),
            (5, 1, 9000, "node-a.example.com"),
            (5, 9, 9002, "node-c.example.com"),
        ])
        try:
            recs = resolve_srv("_filodb._tcp.example.com",
                               server="127.0.0.1", port=stub.port)
            assert [(r.target, r.port) for r in recs] == [
                ("node-c.example.com", 9002),   # prio 5, weight 9 first
                ("node-a.example.com", 9000),
                ("node-b.example.com", 9001),
            ]
        finally:
            stub.close()

    def test_discovery_strategy(self):
        stub = StubResolver([(1, 1, 7070, "seed.example.com")])
        try:
            d = DnsSrvDiscovery("_filodb._tcp.example.com",
                                server="127.0.0.1", port=stub.port)
            assert d.discover() == [("seed.example.com", 7070)]
        finally:
            stub.close()

    def test_unreachable_resolver_yields_no_seeds(self):
        # closed port: discovery must swallow the timeout and return []
        d = DnsSrvDiscovery("_filodb._tcp.example.com",
                            server="127.0.0.1", port=1)
        import filodb_tpu.utils.dns_srv as mod
        orig = mod.resolve_srv

        def fast_timeout(name, server=None, port=None, timeout=2.0):
            return orig(name, server=server, port=port, timeout=0.2)

        mod.resolve_srv = fast_timeout
        try:
            assert d.discover() == []
        finally:
            mod.resolve_srv = orig

    def test_nxdomain_is_empty(self):
        class NxStub(StubResolver):
            def _serve(self):
                try:
                    while True:
                        query, addr = self.sock.recvfrom(4096)
                        self.sock.sendto(_response(query, [], rcode=3), addr)
                except OSError:
                    pass

        stub = NxStub([])
        try:
            assert resolve_srv("_nope._tcp.example.com",
                               server="127.0.0.1", port=stub.port) == []
        finally:
            stub.close()
