"""``max_over_time``: the largest sample of the window (t-w, t], NaN where
it holds none."""

from __future__ import annotations

import numpy as np


def bounds(ref, ts, vals, steps_ms, window_ms, interval_ms):
    out = np.full((ts.shape[0], len(steps_ms)), np.nan)
    kind = vals.dtype.type
    for k, _, cols, m in ref.windows(ts, steps_ms, window_ms, interval_ms):
        v = np.where(m, vals[:, cols], kind(-np.inf)).max(1)
        out[:, k] = np.where(m.any(1), v, np.nan)
    return out, out
