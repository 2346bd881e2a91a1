"""Host staging buffers of the mesh engine.

A batch-cache miss builds ``[P, S]`` host arrays for ONE placement —
``build_batch``'s ``ts`` and ``vals``, the validity mask, and what
``mesh-pad`` makes of them: the ``split`` lane's copy in the device's float
dtype and, for a histogram batch ``[P, S, B]``, the values and ``ts``
flattened to bucket rows ``[P·B, S]`` — and nothing reads them once the
device holds its copy. Arrays of that size (64 MB at the ``[16384, 1024]``
of a 10,000-series query; 8-17 MB each at the ``[128, 256, 64]`` of a
service's 100 latency histograms) come from ``mmap``, so an allocation a
request pays a page fault for every 4 KiB it writes and gives the pages
back when the arrays die. ``StagingPool`` keeps the arrays instead: a
placement takes them through a :class:`Lease` and the engine gives them
back when the placed arrays are ready, so the next build of the same shape
writes into memory that is already mapped.

A buffer is found by dtype and shape, which power-of-two bucketing keeps to
a few values; a request no buffer fits allocates as before. The pool holds
at most ``POOL_CAP_BYTES``, least recently used shape out first.
"""

from __future__ import annotations

import threading
from collections import OrderedDict

import numpy as np

from filodb_tpu.query.engine.batch import fresh_array
from filodb_tpu.utils.metrics import BATCH_BUFFER_FRESH, BATCH_BUFFER_REUSED
from filodb_tpu.utils.tracing import tag_add

# two placements in flight at the largest shape a one-chip cell builds:
# ts int32 + vals f32 + mask bool of [16384, 1024] = 64 + 64 + 16 MiB.
# A histogram placement of [128, 256, 64] leases ~36 MB (vals f64 16.9,
# ts 0.1; the bucket rows [8192, 256]: values f32 8.4, ts 8.4, mask 2.1),
# of one shape an extent: several fit beside the above
POOL_CAP_BYTES = 2 * (64 + 64 + 16) << 20


class StagingPool:
    """Free host arrays by (dtype, shape), bounded in bytes. Safe to call
    from several threads: a buffer is in the pool or with ONE lease."""

    def __init__(self, cap_bytes: int = POOL_CAP_BYTES):
        self.cap_bytes = cap_bytes
        self.held_bytes = 0
        # least recently used shape first
        self._free: OrderedDict[tuple, list[np.ndarray]] = OrderedDict()
        self._lock = threading.Lock()

    def lease(self) -> Lease:
        return Lease(self)

    def _take(self, shape, dtype, fill) -> np.ndarray:
        key = (np.dtype(dtype).str, tuple(shape))
        with self._lock:
            bufs = self._free.get(key)
            buf = bufs.pop() if bufs else None
            if buf is not None:
                self.held_bytes -= buf.nbytes
                if bufs:
                    self._free.move_to_end(key)
                else:
                    del self._free[key]
        reused = buf is not None
        if not reused:
            buf = fresh_array(shape, dtype, fill)
        elif fill is not None:
            # whatever the last placement left here is padding now: a stale
            # timestamp below a step would be counted into a window
            buf.fill(fill)
        (BATCH_BUFFER_REUSED if reused else BATCH_BUFFER_FRESH).inc(buf.nbytes)
        tag_add("reused_bytes", buf.nbytes if reused else 0)
        return buf

    def _give(self, bufs: list) -> None:
        with self._lock:
            for buf in bufs:
                if buf.nbytes > self.cap_bytes:
                    continue
                key = (buf.dtype.str, buf.shape)
                self._free.setdefault(key, []).append(buf)
                self._free.move_to_end(key)
                self.held_bytes += buf.nbytes
            while self.held_bytes > self.cap_bytes:
                key, olds = next(iter(self._free.items()))
                self.held_bytes -= olds.pop().nbytes
                if not olds:
                    del self._free[key]


class Lease:
    """The buffers one placement took. ``take`` is the allocator
    ``build_batch`` is handed; ``give_back`` returns what was taken, once —
    a lease that is dropped instead (an early return, an exception) leaves
    its buffers to the allocator."""

    def __init__(self, pool: StagingPool):
        self._pool = pool
        self._taken: list[np.ndarray] = []

    def take(self, shape, dtype, fill=None) -> np.ndarray:
        buf = self._pool._take(shape, dtype, fill)
        self._taken.append(buf)
        return buf

    def give_back(self) -> None:
        taken, self._taken = self._taken, []
        self._pool._give(taken)
