"""``avg_over_time``: the mean of the window's samples (t-w, t], NaN where
it holds none. The sum keeps the samples' type: f64, or the control's
lower one."""

from __future__ import annotations

import numpy as np


def bounds(ref, ts, vals, steps_ms, window_ms, interval_ms):
    out = np.full((ts.shape[0], len(steps_ms)), np.nan)
    kind = vals.dtype.type
    for k, _, cols, m in ref.windows(ts, steps_ms, window_ms, interval_ms):
        with np.errstate(invalid="ignore", divide="ignore"):
            v = np.where(m, vals[:, cols], kind(0)).sum(1) \
                / m.sum(1).astype(kind)
        out[:, k] = np.where(m.any(1), v, np.nan)
    return out, out
