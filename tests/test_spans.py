"""What the program writes for a profiler: region programs compiled under
their region's name, the ``region_captures`` counter, and the
``repro.tapir.capture`` span with where each program came from."""
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import tapir
from repro.core.lowering import emit
from repro.core.tapir import cache_stats, clear_cache


def setup_function(_):
    clear_cache()


def _xw(rows=8):
    x = jnp.asarray(np.random.default_rng(0).normal(size=(rows, 16)),
                    jnp.float32)
    w = jnp.asarray(np.random.default_rng(1).normal(size=(16, 32)),
                    jnp.float32)
    return x, w


def slot_blk(x, w):
    return tapir.linear(x, w, activation="gelu")


def test_region_program_module_is_named_for_its_region():
    x, w = _xw()
    g = tapir.trace_region(slot_blk, x, w)
    vals = {"a0": x, "a1": w}
    for name, module in (("slot_blk", "jit_tapir_slot_blk"),
                         ("slot_blk#1", "jit_tapir_slot_blk_1")):
        g.name = name
        jitted, names = tapir._positional_jit(emit(g, "cpu"), g)
        text = jitted.lower(*[vals[n] for n in names]).as_text()
        assert f"module @{module} " in text, text[:200]
        assert "_positional" not in text.split("\n")[0]


def test_region_captures_count_captures_not_replays():
    x, w = _xw()
    blk = tapir.parallel_region(slot_blk, name="slot_blk")
    c0 = cache_stats()["region_captures"]
    blk(x, w)
    assert cache_stats()["region_captures"] == c0 + 1
    for _ in range(3):
        blk(x, w)                       # replays: no trace, no count
    assert cache_stats()["region_captures"] == c0 + 1
    blk(*_xw(rows=4))                   # a new shape captures again
    assert cache_stats()["region_captures"] == c0 + 2
    clear_cache()
    assert cache_stats()["region_captures"] == 0


def _capture_spans(path):
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            out.extend((e.start_ns, dict(e.stats)) for e in line.events
                       if e.name == "repro.tapir.capture")
    return [args for _, args in sorted(out, key=lambda t: t[0])]


def test_capture_span_names_region_and_source(tmp_path):
    x, w = _xw()

    def twin(x, w):                     # same graph, another call site
        return tapir.linear(x, w, activation="gelu")

    blk = tapir.parallel_region(slot_blk, name="slot_blk")
    other = tapir.parallel_region(twin, name="slot_blk")
    jax.profiler.start_trace(str(tmp_path))
    try:
        blk(x, w)                       # compiled
        blk(x, w)                       # replayed: no span
        other(x, w)                     # captured, program from memory
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                      recursive=True)
    assert _capture_spans(path) == [
        {"region": "slot_blk", "source": "compiled"},
        {"region": "slot_blk", "source": "memory"}]


class _PosRecorder:
    """The model, recording each decode step's device lengths first."""

    def __init__(self, model):
        self._model = model
        self.pos = []

    def __getattr__(self, name):
        return getattr(self._model, name)

    def decode_step_slots(self, sp, tokens, cache):
        self.pos.append(np.asarray(cache["pos"]).copy())
        return self._model.decode_step_slots(sp, tokens, cache)


def _decode_span_args(path):
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            out.extend((e.start_ns, dict(e.stats)) for e in line.events
                       if e.name == "repro.serve.decode")
    return [args for _, args in sorted(out, key=lambda t: t[0])]


def test_decode_span_counts_live_kv_pages(tmp_path):
    """``serve.decode``'s ``kv_pages`` is the live pages of every slot,
    from the host's mirror of the lengths: it equals what the device's
    lengths give at every step, through admission, release and a
    preemption that parks a request and resumes it."""
    import repro.configs as C
    from repro.models.base import get_model
    from repro.serve import Request, ServeConfig, ServingEngine
    model = get_model(C.get_smoke("qwen2_5_3b"))
    params = model.init_params(jax.random.PRNGKey(0))
    rec = _PosRecorder(model)
    max_len, page_len = 64, 8
    eng = ServingEngine(rec, params, batch=2, max_len=max_len,
                        cfg=ServeConfig(target="cpu", page_len=page_len,
                                        preempt_mode="park"))
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=rng.integers(1, 100, size=n)
                    .astype(np.int32), max_new=m, priority=p,
                    arrival_step=a)
            for i, (n, m, p, a) in enumerate([(7, 14, 0, 0), (17, 5, 0, 0),
                                              (3, 4, 5, 2)])]
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.run(reqs)
    finally:
        jax.profiler.stop_trace()
    assert eng.last_stats["parked"] == 1
    path, = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                      recursive=True)
    got = [a["kv_pages"] for a in _decode_span_args(path)]
    want = [int(np.sum(-(-np.clip(p + 1, 1, max_len) // page_len)))
            for p in rec.pos]
    assert got == want and len(got) == eng.last_stats["decode_steps"]
    assert min(got) >= 2 and max(got) < 2 * max_len // page_len


def _span_args(path, name):
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            out.extend((e.start_ns, dict(e.stats)) for e in line.events
                       if e.name == name)
    return [args for _, args in sorted(out, key=lambda t: t[0])]


def test_state_rows_in_decode_admit_park_and_resume_spans(tmp_path):
    """A model with per-slot state rows (granite's hybrid at smoke size):
    ``serve.decode``'s ``state_rows`` is the slots holding a request, from
    the host's lengths; each ``serve.admit`` resets one slot's rows
    (``state_reset``, bytes), and ``serve.pages.park`` / ``.resume`` move
    them (``state_bytes``)."""
    import dataclasses

    import repro.configs as C
    from repro.models.base import get_model
    from repro.serve import Request, ServeConfig, ServingEngine
    from repro.serve.pages import state_row_bytes
    cfg = dataclasses.replace(C.get_smoke("granite_4_0_h_small"),
                              compute_dtype="float32")
    model = get_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    rec = _PosRecorder(model)
    eng = ServingEngine(rec, params, batch=2, max_len=64,
                        cfg=ServeConfig(target="cpu", page_len=8,
                                        preempt_mode="park"))
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=rng.integers(1, 100, size=n)
                    .astype(np.int32), max_new=m, priority=p,
                    arrival_step=a)
            for i, (n, m, p, a) in enumerate([(7, 14, 0, 0), (17, 5, 0, 0),
                                              (3, 4, 5, 2)])]
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.run(reqs)
    finally:
        jax.profiler.stop_trace()
    assert eng.last_stats["parked"] == 1
    path, = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                      recursive=True)
    rows = state_row_bytes(model.init_slot_cache(2, 64, 8))
    din = cfg.ssm_expand * cfg.d_model
    assert rows == 3 * (cfg.ssm_expand * cfg.d_model * cfg.ssm_state * 4
                        + 3 * (din + 2 * cfg.ssm_state) * 4)
    got = [a["state_rows"] for a in _span_args(path, "repro.serve.decode")]
    assert got == [int(np.count_nonzero(p)) for p in rec.pos]
    assert max(got) == 2
    admits = _span_args(path, "repro.serve.admit")
    assert len(admits) == 4                  # three requests, one resumed
    assert sorted(a.get("state_reset", 0) for a in admits) == [0] + [rows] * 3
    for name in ("repro.serve.pages.park", "repro.serve.pages.resume"):
        args, = _span_args(path, name)
        assert args["state_bytes"] == rows
