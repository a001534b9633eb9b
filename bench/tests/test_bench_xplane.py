"""The trace reduction: interval arithmetic, and a small trace recorded
on a TPU v5e (``data/small.xplane.pb``, made by ``data/record_trace.py``:
three ``bench.decode`` spans, each around a jitted matmul, inside one
``bench.window``)."""
import os

import pytest

from bench.harness import xplane

SMALL = os.path.join(os.path.dirname(__file__), "data", "small.xplane.pb")


def test_merge_and_cover():
    m = xplane.merge([(5, 7, "a"), (0, 2, "b"), (1, 3, "c"), (9, 10, "d")])
    assert m == [(0, 3), (5, 7), (9, 10)]
    assert xplane.covered(m, [(0, 10)]) == 6
    assert xplane.covered(m, [(2, 6), (9.5, 20)]) == 1 + 1 + 0.5
    assert xplane.clip(m, 1, 6) == [(1, 3), (5, 6)]


def test_busy_and_gaps_of_synthetic_trace():
    tr = xplane.Trace(
        ops={"/device:TPU:0": [(10, 20, "f"), (30, 40, "g")],
             "/device:TPU:1": [(10, 30, "f")]},
        modules={"/device:TPU:0": [(10, 40, "jit_step")]},
        spans=[(0, 100, "bench.window"), (25, 45, "bench.decode")])
    assert xplane.window(tr) == (0, 100)
    assert xplane.busy_ns(tr, [(0, 100)]) == 20
    assert xplane.busy_ns(tr, [(25, 45)]) == (10 + 5) / 2
    gaps = dict(xplane.idle_gaps(tr, 0, 100))
    assert gaps["host inside decode"] == pytest.approx(10e-9)
    assert gaps["host outside the model calls"] == pytest.approx(70e-9)
    ops = dict(xplane.top_ops(tr, 0, 100))
    # named by the enclosing program where the plane has one; per chip
    assert ops["jit_step/f"] == pytest.approx(5e-9)
    assert ops["f"] == pytest.approx(10e-9)


def test_recorded_tpu_trace():
    tr = xplane.load(SMALL)
    assert len(tr.ops) == 1 and all(n.startswith("/device:TPU:")
                                    for n in tr.ops)
    lo, hi = xplane.window(tr)
    decode = xplane.spans_named(tr, "bench.decode")
    assert len(decode) == 3 and all(lo <= s < e <= hi for s, e in decode)
    busy = xplane.busy_ns(tr, [(lo, hi)])
    inside = xplane.busy_ns(tr, decode)
    assert 0 < inside <= busy < hi - lo
    # each span ends on its result: once the device clock is aligned to
    # the host's, the matmuls lie inside them and only the small ops
    # between the spans lie outside
    assert tr.shift_ns != 0
    assert inside >= 0.95 * busy
    assert xplane.top_ops(tr, lo, hi)
    idle = sum(s for _, s in xplane.idle_gaps(tr, lo, hi))
    assert idle == pytest.approx((hi - lo - busy) / 1e9, rel=1e-6)
