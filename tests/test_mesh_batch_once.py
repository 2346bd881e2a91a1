"""The mesh batch is materialised once: ``build_batch`` writes the placed
shape and dtype, and what reaches ``shard_batch_arrays`` equals, bit for
bit, what the parent's ``build_batch`` → ``pad_for_mesh`` → ``device_put``
chain made (kept as the oracle in ``mesh_oracle.py``) — on every value
lane, for histograms, staleness NaNs, empty series and ragged counts, on
meshes 1×1, 4×1, 2×2, 4×2 and 3×1 (which divides no power of two) over the
eight CPU devices ``conftest`` forces. x64 is on here; the same matrix runs
with x64 off, as a server does, in ``test_f32_mode.py``."""

import numpy as np
import pytest
from mesh_oracle import CASES, MESHES, STORES, placed_mismatches, run_case

from filodb_tpu.coordinator.query_service import QueryService
from filodb_tpu.query.engine.batch import _next_pow2, build_batch, device_float

RAW = [c for c, v in CASES.items() if v[2] == "raw" and v[0] != "histogram"]


@pytest.fixture(scope="module")
def stores():
    return {}


@pytest.mark.parametrize("mesh_name", MESHES)
@pytest.mark.parametrize("case", CASES)
def test_placed_arrays_equal_the_parents(case, mesh_name, stores):
    cap = run_case(case, mesh_name, stores)
    assert placed_mismatches(cap) == []
    ds, dtm = MESHES[mesh_name]
    Pp, S = cap.got[0].shape
    assert Pp % ds == 0 and S % dtm == 0
    # no later step changed a shape: the builder's is the placed one
    # (histograms flatten their buckets into the series axis)
    B = cap.batch.vals.shape[2] if cap.batch.is_histogram else 1
    assert (cap.batch.ts.shape[0] * B, cap.batch.ts.shape[1]) == (Pp, S)
    assert cap.tags["mesh-pad"]["shape"] == [Pp, S]


@pytest.mark.parametrize("mesh_name", ["1x1", "4x2", "3x1"])
@pytest.mark.parametrize("case", RAW)
def test_raw_lane_places_the_builders_own_arrays(case, mesh_name, stores):
    cap = run_case(case, mesh_name, stores)
    ts, vals = cap.got[0], cap.got[1]
    assert ts is cap.batch.ts and vals is cap.batch.vals
    assert vals.dtype == device_float()
    assert cap.got[4] is None
    assert cap.tags["mesh-pad"]["copied_bytes"] == 0
    # every in-count sample, nothing else: padding is 0, never NaN
    assert not np.isnan(vals).any()
    assert int(cap.batch.counts.sum()) == int(cap.got[2].sum())
    # ragged counts, an empty series and a series axis that had to pad
    real = cap.batch.counts[: cap.batch.num_series]
    assert real.min() == 0 and len(set(real.tolist())) > 2
    assert cap.batch.num_series < ts.shape[0]


@pytest.mark.parametrize("case,arrays", [
    ("split-small", 1), ("split-delta", 1), ("split-delta-sum", 1),
    ("split-delta-counter", 1), ("split-big-rate", 1),
])
def test_delta_lanes_copy_one_value_array(case, arrays, stores):
    """The split lane keeps the f64 batch (``delta_host`` and the magnitude
    check read it) and makes each placed value array from it in one pass:
    ``vals`` — the raw values under x64, whatever their magnitude — and
    ``raw`` beside it only where the host pre-pass ran (x64 off,
    ``test_f32_mode.py``)."""
    cap = run_case(case, "2x2", stores)
    vals, raw = cap.got[1], cap.got[4]
    assert cap.batch.vals.dtype == np.float64
    assert np.isnan(cap.batch.vals).any()      # NaN padding, as delta_host wants
    assert cap.got[0] is cap.batch.ts
    assert not np.shares_memory(vals, cap.batch.vals)
    assert (raw is not None) == (arrays == 2)
    assert cap.tags["mesh-pad"]["copied_bytes"] == arrays * vals.nbytes


def test_histogram_copies_are_counted(stores):
    cap = run_case("histogram-split", "4x1", stores)
    ts, vals = cap.got[0], cap.got[1]
    assert cap.batch.is_histogram and cap.batch.vals.dtype == np.float64
    assert cap.tags["mesh-pad"]["copied_bytes"] == ts.nbytes + vals.nbytes


@pytest.mark.parametrize("multiples,shape", [
    ((1, 1), (16, 256)), ((4, 2), (16, 256)), ((3, 1), (18, 256)),
    ((3, 5), (18, 260)), ((32, 1), (32, 256)),
])
def test_build_batch_rounds_its_one_allocation(multiples, shape, stores):
    """A power of two, then the next multiple of a mesh axis that does not
    divide it; the exec tree's call (no arguments) is today's batch."""
    if "gauge" not in stores:
        stores["gauge"] = STORES["gauge"]()
    ms = stores["gauge"]
    parts = [p for sh in ms.shards_for("timeseries")
             for p in (sh.partition(i) for i in range(16)) if p is not None]
    assert len(parts) == 13
    lo, hi = 1_600_000_000_000, 1_600_000_000_000 + 2_000_000
    plain = build_batch(parts, lo, hi)
    assert plain.vals.dtype == np.float64 and np.isnan(plain.vals).any()
    assert plain.ts.shape == (_next_pow2(13), _next_pow2(int(plain.counts.max())))
    b = build_batch(parts, lo, hi, mesh_multiples=multiples, host_f64=False)
    assert b.ts.shape == b.vals.shape == shape and b.counts.shape == shape[:1]
    P_, S_ = plain.ts.shape
    np.testing.assert_array_equal(b.ts[:P_, :S_], plain.ts)
    np.testing.assert_array_equal(b.counts[:P_], plain.counts)
    np.testing.assert_array_equal(b.vals[:P_, :S_],
                                  np.nan_to_num(plain.vals, nan=0.0))
    assert not b.vals[P_:].any() and not b.vals[:, S_:].any()
    assert (b.ts[P_:] == np.iinfo(np.int32).max).all()


@pytest.mark.parametrize("case", ["raw-avg", "split-small",
                                  "split-delta-sum", "histogram-split"])
def test_a_mesh_that_divides_no_power_of_two_answers_like_exec(case, stores):
    """3×1: the same allocation rounded up to the axis, not a fallback copy
    — and the answer is the exec tree's."""
    store, query, _ = CASES[case]
    cap = run_case(case, "3x1", stores, run=True)
    assert cap.got[0].shape[0] % 3 == 0
    assert cap.tags["mesh-place"]["bytes"] == sum(
        a.nbytes for a in cap.got if a is not None)
    exec_svc = QueryService(stores[store], "timeseries", 4, spread=1)
    want = exec_svc.query_range(query, 1_600_000_600, 60,
                                1_600_002_400).result
    got = cap.result
    order_w = np.argsort([str(k) for k in want.keys])
    order_g = np.argsort([str(k) for k in got.keys])
    assert [str(want.keys[i]) for i in order_w] == \
        [str(got.keys[i]) for i in order_g]
    np.testing.assert_allclose(np.asarray(got.values)[order_g],
                               np.asarray(want.values)[order_w],
                               rtol=1e-6, atol=1e-9, equal_nan=True)
