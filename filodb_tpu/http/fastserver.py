"""Single-threaded selector HTTP front end with event-loop query batching.

The TPU-native serving design: one thread owns every socket (no handler
threads, no GIL hand-offs), and all hot queries (``query``/``query_range``)
that arrive within one readiness pass are evaluated as ONE
``QueryService.query_range_many`` engine batch — the device executes a
single micro-batched program and results come back in one coalesced fetch.
This replaces thread-per-connection + ``QueryBatcher`` coalescing with the
event loop's natural batching: under load a pass drains every ready socket,
so batch size tracks concurrency with zero added latency when idle.

Reference boundary replaced: the Akka-HTTP dispatcher pool in
``http/src/main/scala/filodb/http/FiloHttpServer.scala:23`` (thread-pool
concurrency → event-loop + device micro-batching).

Cold paths (metadata, admin, remote-read, POST forms) run inline through
the shared ``HttpDispatcher`` — identical routing to the threaded server.

Every request carries its arrival on the wall clock (the clock of a
flight-recorder entry's ``t0_unix_ns``): when its bytes turned the socket
readable, as ``_Doorman`` saw it, else the read that took them. The loop
observes ``now − arrival`` once a request, as it takes the request up, into
``filodb_http_queue_wait_seconds``: how long the one thread kept it waiting.
"""

from __future__ import annotations

import fcntl
import json
import logging
import select
import selectors
import socket
import struct
import termios
import threading
import time
from urllib.parse import parse_qs, urlparse

from filodb_tpu.http import promjson
from filodb_tpu.http.server import (
    JSON_CT,
    HttpDispatcher,
    ResponseCache,
    queue_wait_seconds,
    render_seconds,
    response_cache_key,
    retry_after_headers,
    service_version,
)
from filodb_tpu.promql.parser import ParseError
from filodb_tpu.query.model import QueryLimitExceeded
from filodb_tpu.utils.governor import QueryRejected
from filodb_tpu.utils.resilience import DeadlineExceeded
from filodb_tpu.utils.tracing import watch_collector

log = logging.getLogger(__name__)

_MAX_BUF = 1 << 20          # drop connections with >1MB of pending request
_MAX_BODY = 10 << 20
_STATUS = {200: b"200 OK", 400: b"400 Bad Request", 404: b"404 Not Found",
           413: b"413 Content Too Large", 422: b"422 Unprocessable Entity",
           429: b"429 Too Many Requests", 431: b"431 Headers Too Large",
           500: b"500 Internal Server Error", 501: b"501 Not Implemented",
           503: b"503 Service Unavailable"}

_INT = struct.Struct("i")


class _Doorman:
    """Notes when a connection's bytes arrive while the loop is busy.

    Bytes that reach a socket while the loop serves others sit in the kernel
    unseen by it, and the kernel's own receive stamp (``SO_TIMESTAMPING``)
    is refused where the benchmark runs (gVisor: ``ENOPROTOOPT``). This
    thread blocks in an edge-triggered epoll on every connection and, when
    one turns readable with bytes unread (``FIONREAD``), keeps the wall
    clock of that moment until the loop's read takes it. It reads nothing.
    Its check with the stamp, and the loop's taking of the stamp after a
    read, hold one lock: a stamp set for bytes the read took is taken with
    them, never left for the next read. The stamp is read once this thread
    has the interpreter back, so it is late by whatever stretch the loop
    then holds it: a switch interval (5 ms) of bytecode, or the whole of a
    C call that keeps the GIL. The wait it gives is short by as much. A new
    connection is watched from its accept.

    Epoll is Linux's: elsewhere :class:`_ReadClock` stands in."""

    def __init__(self):
        self._wake_r, self._wake_w = socket.socketpair()
        self._ep = select.epoll()
        self._ep.register(self._wake_r.fileno(),
                          select.EPOLLIN | select.EPOLLET)
        self._lock = threading.Lock()
        self._seen: dict[int, int] = {}
        self._running = True
        self._thread = threading.Thread(target=self._watch, daemon=True,
                                        name="fast-http-doorman")
        self._thread.start()

    def watch(self, sock: socket.socket) -> None:
        self._ep.register(sock.fileno(), select.EPOLLIN | select.EPOLLET)

    def forget(self, fd: int) -> None:
        with self._lock:
            self._seen.pop(fd, None)

    def read(self, sock: socket.socket, n: int) -> tuple[bytes, int]:
        """``sock.recv(n)`` and the wall-clock ns its bytes arrived."""
        data = sock.recv(n)
        with self._lock:
            arrived = self._seen.pop(sock.fileno(), None)
        return data, arrived if arrived is not None else time.time_ns()

    def stop(self) -> None:
        self._running = False
        self._wake_w.send(b"x")
        self._thread.join(timeout=5)
        self._ep.close()
        self._wake_r.close()
        self._wake_w.close()

    def _watch(self) -> None:
        while self._running:
            events = self._ep.poll()
            now = time.time_ns()
            with self._lock:
                for fd, _ in events:
                    if fd not in self._seen and _unread(fd):
                        self._seen[fd] = now


class _ReadClock:
    """Where there is no epoll (macOS, BSD): a request arrives when the
    read that takes its bytes returns, so its wait counts from there."""

    def watch(self, sock: socket.socket) -> None:
        pass

    def forget(self, fd: int) -> None:
        pass

    def read(self, sock: socket.socket, n: int) -> tuple[bytes, int]:
        return sock.recv(n), time.time_ns()

    def stop(self) -> None:
        pass


def _unread(fd: int) -> bool:
    try:
        return _INT.unpack(fcntl.ioctl(fd, termios.FIONREAD,
                                       bytes(_INT.size)))[0] > 0
    except OSError:  # closed under us
        return False


def _queue_wait_ms(arrived_ns: int) -> float:
    """Observe a request's wait from its arrival to now, once a request,
    as the loop takes it up; the wait in ms."""
    wait_s = max(0, time.time_ns() - arrived_ns) / 1e9
    queue_wait_seconds.observe(wait_s)
    return round(wait_s * 1000, 3)


def _response_bytes(code: int, headers: dict, body: bytes,
                    close: bool) -> bytes:
    head = [b"HTTP/1.1 " + _STATUS.get(code, str(code).encode())]
    for k, v in headers.items():
        head.append(f"{k}: {v}".encode())
    head.append(b"Content-Length: " + str(len(body)).encode())
    if close:
        head.append(b"Connection: close")
    return b"\r\n".join(head) + b"\r\n\r\n" + body


class _Conn:
    __slots__ = ("sock", "inbuf", "out", "slots", "base", "close_after")

    def __init__(self, sock: socket.socket):
        self.sock = sock
        self.inbuf = b""
        self.out = b""
        # responses must leave in request order (HTTP/1.1 pipelining):
        # each parsed request claims an ABSOLUTE slot number; completed
        # prefix slots are shifted out by _flush, so ``base`` tracks the
        # absolute number of slots[0] (hot queries fill theirs after the
        # batch runs, by which time earlier slots may have flushed)
        self.slots: list[bytes | None] = []
        self.base = 0
        self.close_after = False

    def fill(self, slot: int, resp: bytes) -> None:
        i = slot - self.base
        if 0 <= i < len(self.slots):
            self.slots[i] = resp

    def is_last(self, slot: int) -> bool:
        return slot == self.base + len(self.slots) - 1


class _HotReq:
    __slots__ = ("conn", "slot", "svc", "kind", "params", "ckey", "version",
                 "arrived_ns")

    def __init__(self, conn, slot, svc, kind, params):
        self.conn = conn
        self.slot = slot
        self.svc = svc
        self.kind = kind          # "range" | "instant"
        self.params = params      # (query, start, step, end)
        self.ckey = None          # response-cache key (set when cache is on)
        self.version = 0
        self.arrived_ns = 0       # wall-clock ns its bytes arrived (_Doorman)


class FastHttpServer:
    """Drop-in alternative front end to ``FiloHttpServer`` (same
    constructor surface and attributes; ``standalone`` picks via config)."""

    def __init__(self, services: dict, host="127.0.0.1", port=8080,
                 cluster=None, shard_maps=None, reuse_port: bool = False,
                 response_cache: bool = True, rule_managers=None):
        self.services = services
        self.cluster = cluster
        # dataset -> RuleManager (standing queries); serves /api/v1/rules
        self.rule_managers = rule_managers or {}
        self.shard_maps = shard_maps or {}
        self.response_cache = ResponseCache() if response_cache else None
        self.dispatcher = HttpDispatcher(self)
        self._listen = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._listen.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        if reuse_port:
            self._listen.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEPORT, 1)
        self._listen.bind((host, port))
        self._listen.listen(512)
        self._listen.setblocking(False)
        self.port = self._listen.getsockname()[1]
        self._sel = selectors.DefaultSelector()
        self._wake_r, self._wake_w = socket.socketpair()
        self._wake_r.setblocking(False)
        self._running = False
        self._thread: threading.Thread | None = None
        self._door: _Doorman | _ReadClock | None = None

    # the dispatcher's cold query paths call app.batched(svc).query_range;
    # on the event loop the service itself is the right executor (no
    # cross-thread coalescing needed — hot batching happens per pass)
    def batched(self, svc):
        return svc

    def start(self) -> "FastHttpServer":
        watch_collector()
        self._running = True
        self._door = _Doorman() if hasattr(select, "epoll") else _ReadClock()
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="fast-http")
        self._thread.start()
        return self

    def stop(self):
        if not self._running:
            return
        self._running = False
        try:
            self._wake_w.send(b"x")
        except OSError:
            pass
        if self._thread is not None:
            self._thread.join(timeout=5)
        self._door.stop()
        for key in list(self._sel.get_map().values()):
            if isinstance(key.data, _Conn):
                try:
                    key.data.sock.close()
                except OSError:
                    pass
        self._sel.close()
        self._listen.close()
        self._wake_r.close()
        self._wake_w.close()

    # -- event loop --

    def _loop(self):
        self._sel.register(self._listen, selectors.EVENT_READ, None)
        self._sel.register(self._wake_r, selectors.EVENT_READ, "wake")
        while self._running:
            try:
                events = self._sel.select(timeout=1.0)
                hot: list[_HotReq] = []
                for key, mask in events:
                    if key.data is None:
                        self._accept()
                    elif key.data == "wake":
                        try:
                            self._wake_r.recv(4096)
                        except OSError:
                            pass
                    else:
                        conn: _Conn = key.data
                        if mask & selectors.EVENT_READ:
                            self._read(conn, hot)
                        if mask & selectors.EVENT_WRITE:
                            self._flush(conn)
                if hot:
                    self._run_hot_batch(hot)
                    for req in hot:
                        self._flush(req.conn)
            except Exception:  # pragma: no cover — the loop must survive
                # any per-connection handler bug; affected sockets are
                # dropped, everything else keeps serving
                log.exception("event loop pass failed")
                for req in locals().get("hot") or []:
                    self._close(req.conn)

    def _accept(self):
        while True:
            try:
                sock, _ = self._listen.accept()
            except (BlockingIOError, OSError):
                return
            sock.setblocking(False)
            sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            self._door.watch(sock)
            self._sel.register(sock, selectors.EVENT_READ, _Conn(sock))

    def _close(self, conn: _Conn):
        try:
            self._sel.unregister(conn.sock)
        except (KeyError, ValueError):
            pass
        self._door.forget(conn.sock.fileno())
        try:
            conn.sock.close()
        except OSError:
            pass

    def _read(self, conn: _Conn, hot: list[_HotReq]):
        try:
            data, arrived_ns = self._door.read(conn.sock, 65536)
        except (BlockingIOError, InterruptedError):
            return
        except OSError:
            self._close(conn)
            return
        if not data:
            self._close(conn)
            return
        conn.inbuf += data
        self._parse_requests(conn, hot, arrived_ns)
        self._flush(conn)

    def _reject(self, conn: _Conn, code: int, message: str):
        conn.slots.append(_response_bytes(
            code, {"Content-Type": JSON_CT},
            json.dumps(promjson.error_json(message)).encode(), True))
        conn.close_after = True
        conn.inbuf = b""

    def _parse_requests(self, conn: _Conn, hot: list[_HotReq],
                        arrived_ns: int):
        while conn.inbuf and not conn.close_after:
            end = conn.inbuf.find(b"\r\n\r\n")
            if end < 0:
                if len(conn.inbuf) > _MAX_BUF:
                    # unterminated header block — the body limit is
                    # enforced separately once Content-Length is known
                    self._reject(conn, 431, "headers too large")
                return
            head = conn.inbuf[:end]
            lines = head.split(b"\r\n")
            try:
                method, target, version = lines[0].split(b" ", 2)
            except ValueError:
                self._close(conn)
                return
            clen = 0
            seen_clen = None
            ctype = ""
            keep = version.strip() == b"HTTP/1.1"
            chunked = False
            for ln in lines[1:]:
                lower = ln.lower()
                if lower.startswith(b"transfer-encoding:"):
                    # chunked bodies are not framed by this parser; treating
                    # them as body-less would desync the pipeline (the body
                    # bytes would parse as the next request)
                    chunked = True
                elif lower.startswith(b"content-length:"):
                    try:
                        clen = int(ln.split(b":", 1)[1])
                    except ValueError:
                        self._close(conn)
                        return
                    if seen_clen is not None and seen_clen != clen:
                        # differing duplicate Content-Length is the CL.CL
                        # smuggling vector (RFC 9112 §6.3: must reject)
                        self._close(conn)
                        return
                    seen_clen = clen
                elif lower.startswith(b"content-type:"):
                    ctype = ln.split(b":", 1)[1].strip().decode(
                        "latin-1", "replace")
                elif lower.startswith(b"connection:"):
                    v = lower.split(b":", 1)[1].strip()
                    keep = v != b"close" if keep else v == b"keep-alive"
            if chunked:
                self._reject(conn, 501, "Transfer-Encoding not supported")
                return
            if clen < 0:
                # a negative length would rewind the request boundary into
                # the current header block — classic smuggling vector
                self._close(conn)
                return
            if clen > _MAX_BODY:
                self._reject(conn, 413, "request body too large")
                return
            total = end + 4 + clen
            if len(conn.inbuf) < total:
                return  # wait for the body
            body = conn.inbuf[end + 4:total]
            conn.inbuf = conn.inbuf[total:]
            if not keep:
                conn.close_after = True
            slot = conn.base + len(conn.slots)
            conn.slots.append(None)
            path = target.decode("latin-1", "replace")
            req = self._classify_hot(conn, slot, method, path)
            if req is not None:
                req.arrived_ns = arrived_ns
                cache = self.response_cache
                svc_version = service_version(req.svc) \
                    if cache is not None else None
                if svc_version is not None:
                    req.ckey = response_cache_key(req.svc, req.kind,
                                                  req.params)
                    req.version = svc_version
                    body = cache.get(req.ckey, req.version)
                    if body is not None:
                        _queue_wait_ms(arrived_ns)
                        conn.fill(slot, _response_bytes(
                            200, {"Content-Type": JSON_CT}, body,
                            conn.close_after and conn.is_last(slot)))
                        continue
                hot.append(req)
            else:
                _queue_wait_ms(arrived_ns)
                code, headers, resp = self.dispatcher.handle(
                    method.decode("latin-1", "replace"), path, body, ctype)
                conn.fill(slot, _response_bytes(
                    code, headers, resp,
                    conn.close_after and conn.is_last(slot)))

    def _classify_hot(self, conn, slot, method: bytes, path: str):
        """A GET query/query_range for a known dataset with well-formed
        parameters; anything else takes the generic dispatcher."""
        if method != b"GET" or not path.startswith("/promql/"):
            return None
        url = urlparse(path)
        parts = url.path.split("/")
        # ['', 'promql', ds, 'api', 'v1', endpoint]
        if len(parts) != 6 or parts[3] != "api" or parts[4] != "v1" \
                or parts[5] not in ("query_range", "query"):
            return None
        svc = self.services.get(parts[2])
        if svc is None:
            return None
        qs = parse_qs(url.query)
        if qs.get("stats", [""])[0] == "all":
            # expanded-stats rendering isn't batched — generic path
            return None
        try:
            if parts[5] == "query_range":
                q, start, step, end = HttpDispatcher.range_params(qs)
                return _HotReq(conn, slot, svc, "range",
                               (q, start, step, end))
            q, t = HttpDispatcher.instant_params(qs)
            return _HotReq(conn, slot, svc, "instant", (q, t, 0, t))
        except (KeyError, ValueError, IndexError):
            return None  # malformed → generic path renders the 400

    # -- hot batch execution --

    def _run_hot_batch(self, hot: list[_HotReq]):
        by_svc: dict[int, list[_HotReq]] = {}
        for req in hot:
            by_svc.setdefault(id(req.svc), []).append(req)
        for reqs in by_svc.values():
            svc = reqs[0].svc
            queued_ms = [_queue_wait_ms(r.arrived_ns) for r in reqs]
            try:
                results = svc.query_range_many([r.params for r in reqs],
                                               queued_ms=queued_ms)
            except Exception:
                # isolate the failing query: run each alone so errors are
                # attributed to their own request
                results = None
            for i, req in enumerate(reqs):
                if results is not None:
                    code, headers, body = (200, {"Content-Type": JSON_CT},
                                           self._render(req, results[i]))
                else:
                    code, headers, body = self._run_single(req)
                if code == 200 and req.ckey is not None \
                        and self.response_cache is not None:
                    self.response_cache.put(req.ckey, req.version, body)
                req.conn.fill(req.slot, _response_bytes(
                    code, headers, body,
                    req.conn.close_after and req.conn.is_last(req.slot)))

    @staticmethod
    def _render(req: _HotReq, result) -> bytes:
        with render_seconds.time():
            if req.kind == "range":
                return promjson.matrix_json_bytes(result)
            return promjson.vector_json_str(result).encode()

    def _run_single(self, req: _HotReq) -> tuple[int, dict, bytes]:
        ct = {"Content-Type": JSON_CT}
        try:
            return (200, ct,
                    self._render(req, req.svc.query_range(*req.params)))
        except (ParseError, ValueError) as e:
            return 400, ct, json.dumps(promjson.error_json(str(e))).encode()
        except QueryLimitExceeded as e:
            return 422, ct, json.dumps(
                promjson.error_json(str(e), "query_limit")).encode()
        except QueryRejected as e:
            # shed by the admission gate: distinct errorType + Retry-After
            # so clients back off instead of hammering an overloaded node
            return 503, {**ct, **retry_after_headers(e.retry_after_s)}, \
                json.dumps(promjson.error_json(str(e), "unavailable")).encode()
        except DeadlineExceeded as e:
            return 503, {**ct, **retry_after_headers()}, json.dumps(
                promjson.error_json(str(e), "timeout")).encode()
        except Exception as e:  # noqa: BLE001
            log.exception("hot query failed")
            return 500, ct, json.dumps(
                promjson.error_json(str(e), "internal")).encode()

    # -- writes --

    def _flush(self, conn: _Conn):
        # move contiguous completed slots into the out buffer
        done = 0
        for resp in conn.slots:
            if resp is None:
                break
            conn.out += resp
            done += 1
        if done:
            del conn.slots[:done]
            conn.base += done
        if not conn.out:
            if conn.close_after and not conn.slots:
                self._close(conn)
            return
        try:
            sent = conn.sock.send(conn.out)
            conn.out = conn.out[sent:]
        except (BlockingIOError, InterruptedError):
            sent = 0
        except OSError:
            self._close(conn)
            return
        try:
            if conn.out:
                self._sel.modify(conn.sock,
                                 selectors.EVENT_READ | selectors.EVENT_WRITE,
                                 conn)
            else:
                self._sel.modify(conn.sock, selectors.EVENT_READ, conn)
                if conn.close_after and not conn.slots:
                    self._close(conn)
        except (KeyError, ValueError):
            pass
