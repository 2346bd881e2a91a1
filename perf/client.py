"""The load generator: a closed loop of N clients, standard library only.

It never imports ``jax`` or ``filodb_tpu``, so it cannot take the chip or
the server's GIL: ``perf/run.py`` starts it as a child process. Each client
is one thread with one persistent ``http.client`` connection; it sends its
stream's next request as soon as the last byte of the previous answer is
read, until ``--seconds`` have passed since the common start — then the request in flight is finished and the client stops. The
window is from the common start to the last byte of the last answer, so
every request sent is answered inside it.

    python perf/client.py --port P --streams FILE --seconds S

FILE holds one JSON array of request objects a client, one client a line;
each request needs only ``path``. Prints one JSON object: ``t0``/``t_end``
(wall clock), ``window_s``, and ``requests``: ``[client, index in its
stream, wall clock at send, seconds to the last byte, ok, bytes]`` each.
A request is ok when it is HTTP 200 with ``"status":"success"`` and not
partial.
"""

from __future__ import annotations

import argparse
import http.client
import json
import sys
import threading
import time


def answer_ok(status: int, body: bytes) -> bool:
    return (status == 200 and b'"status":"success"' in body[:64]
            and b'"partial":true' not in body[-4096:]
            and b'"partial": true' not in body[-4096:])


def _client(cid, port, stream, start, seconds, out):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
    start.wait()
    t0 = time.perf_counter()
    i = 0
    while time.perf_counter() - t0 < seconds:
        req = stream[i % len(stream)]
        wall = time.time()
        t = time.perf_counter()
        try:
            conn.request("GET", req["path"])
            resp = conn.getresponse()
            body = resp.read()
            took = time.perf_counter() - t
            ok = answer_ok(resp.status, body)
        except (OSError, http.client.HTTPException):
            took, ok, body = time.perf_counter() - t, False, b""
            conn.close()
            conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
        out.append([cid, i, wall, took, ok, len(body)])
        i += 1
    conn.close()


def run(port: int, streams: list, seconds: float) -> dict:
    start = threading.Barrier(len(streams) + 1)
    outs = [[] for _ in streams]
    threads = [threading.Thread(
        target=_client,
        args=(c, port, s, start, seconds, outs[c]))
        for c, s in enumerate(streams)]
    for th in threads:
        th.start()
    start.wait()
    t0_wall, t0 = time.time(), time.perf_counter()
    for th in threads:
        th.join()
    window_s = time.perf_counter() - t0
    return {"t0": t0_wall, "t_end": t0_wall + window_s, "window_s": window_s,
            "clients": len(streams),
            "requests": [r for o in outs for r in o]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--streams", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    with open(args.streams) as f:
        streams = [json.loads(line) for line in f if line.strip()]
    json.dump(run(args.port, streams, args.seconds), sys.stdout)
    return 0


if __name__ == "__main__":
    sys.exit(main())
