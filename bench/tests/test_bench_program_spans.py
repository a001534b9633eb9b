"""The program's spans as the benchmark reads them: the idle split and
self time on synthetic traces, the serving engine's spans recorded on the
CPU (nesting, counts, what a warm tick costs), the engine's trace
recorded on a v5e (``data/program.xplane.pb``, made by
``data/record_program_trace.py``: named region programs, program spans
on the harness's clock), and the reduction of the recorded v5e trace that
existing metrics read, unchanged."""
import os

import numpy as np
import pytest

from bench.harness import program, spec, xplane
from bench.tests import engine_trace

DATA = os.path.join(os.path.dirname(__file__), "data")
SMALL = os.path.join(DATA, "small.xplane.pb")
PROGRAM = os.path.join(DATA, "program.xplane.pb")


def _span(s, e, name, **args):
    return program.Span(float(s), float(e), name, args)


def _synthetic():
    tr = xplane.Trace(ops={"/device:TPU:0": [(10, 20, "a"), (30, 40, "b"),
                                             (70, 80, "c")]},
                      modules={}, spans=[(0, 100, "bench.window")])
    spans = sorted([
        _span(-5, 60, "serve.tick"),          # opened before the window
        _span(25, 45, "serve.decode"),
        _span(26, 28, "model.decode"),
        _span(50, 58, "serve.admit", rid=7),
        _span(52, 56, "serve.prefill", rid=7),
        _span(60, 100, "serve.tick"),
        _span(62, 64, "tapir.capture", region="slot_head"),
        _span(120, 130, "serve.tick"),        # after the window
    ], key=lambda sp: (sp.start, -sp.end))
    return tr, spans


def test_idle_split_gives_every_idle_ns_one_label():
    tr, spans = _synthetic()
    split = program.idle_split(tr, spans, 0, 100)
    assert split == {"serve.tick": 50.0, "serve.decode": 8.0,
                     "model.decode": 2.0, "serve.admit": 4.0,
                     "serve.prefill": 4.0, "tapir.capture": 2.0}
    busy = xplane.busy_ns(tr, [(0, 100)])
    assert sum(split.values()) == 100 - busy
    # with no program spans the whole idle time has no span
    assert program.idle_split(tr, [], 0, 100) == {program.NO_SPAN: 70.0}
    assert program.idle_split(tr, spans, 0, 5) == {"serve.tick": 5.0}
    assert program.engine_idle_share(split, 0, 100) == 54.0


def test_idle_split_of_a_busy_device_is_empty():
    tr = xplane.Trace(ops={"/device:TPU:0": [(0, 100, "a")]}, modules={},
                      spans=[])
    assert program.idle_split(tr, [_span(10, 20, "serve.tick")], 0, 100) \
        == {}
    assert program.idle_split(xplane.Trace({}, {}, []), [], 0, 1) == {}


def test_self_time_and_span_metrics():
    _, spans = _synthetic()
    tick = spans[0]
    assert program.self_ns(spans, tick) == 65 - 20 - 8
    admit, = program.named(spans, "serve.admit")
    assert program.self_ns(spans, admit, "serve.prefill") == 4
    assert [sp.name for sp in program.inside(spans, admit)] == \
        ["serve.prefill"]
    assert program.admit_host_ms(spans, 0, 100) == 4e-6
    assert program.decode_dispatch_ms(spans, 0, 100) == 2e-6
    assert program.window_captures(spans, 0, 100) == 1
    assert program.window_captures(spans, 0, 60) == 0
    assert program.admit_host_ms(spans, 0, 40) is None
    assert program.decode_dispatch_ms(spans, 60, 100) is None
    assert program.is_engine("serve.pages.publish")
    assert not program.is_engine("serve.decode")


def test_small_trace_reduces_as_before():
    """The reduction every existing metric reads, pinned on the recorded
    v5e trace: host spans, alignment, busy and idle time, top operations
    and the device idle metric."""
    tr = xplane.load(SMALL)
    assert tr.shift_ns == 1400000.0
    assert tr.spans == [(43114850.0, 133875982.0, "bench.window"),
                        (43122701.0, 44342730.0, "bench.decode"),
                        (130174791.0, 130990581.0, "bench.decode"),
                        (132219221.0, 132881682.0, "bench.decode")]
    lo, hi = xplane.window(tr)
    assert xplane.busy_ns(tr, [(lo, hi)]) == 79565.0
    assert xplane.busy_ns(tr, xplane.spans_named(tr, "bench.decode")) == \
        78882.0
    assert xplane.idle_gaps(tr, lo, hi) == [
        ["host outside the model calls", 0.09014236],
        ["host inside decode", 0.000539207]]
    top = xplane.top_ops(tr, lo, hi, 2)
    assert [n for n, _ in top] == [
        "jit__lambda(18084989565708003084)/%fusion bf16[1024,1024] kOutput",
        "jit__lambda(18084989565708003084)/%convolution_tanh_fusion "
        "bf16[1024,1024] kOutput"]
    assert [s for _, s in top] == [3.7842e-05, 3.4667e-05]
    idle = spec.metric_reader("device_idle.serve").read(
        {"kind": "serve", "trace": tr})
    assert idle == pytest.approx(100.0 * (1 - 79565.0 / (hi - lo)),
                                 rel=1e-12)
    # no region programs in it: the head's share finds nothing to read
    assert spec.metric_reader("decode_head_share").read(
        {"kind": "serve", "trace": tr}) is None
    assert program.load(SMALL) == []


def test_engine_spans_nest_and_count(tmp_path):
    eng, obs, stream = engine_trace.engine(2**40 + 11)
    # cold: every region program is captured, and each capture is a span
    _, _, path = engine_trace.traced_run(eng, obs, stream,
                                         str(tmp_path / "cold"))
    cold = program.load(path)
    n_cap = eng.last_stats["region_captures"]
    assert n_cap > 0
    assert len(program.named(cold, "tapir.capture")) == n_cap

    reqs, win, path = engine_trace.traced_run(eng, obs, stream,
                                              str(tmp_path / "warm"))
    st = eng.last_stats
    spans = program.load(path)
    tr = xplane.load(path)              # the harness's spans, same file
    lo, hi = xplane.window(tr)
    assert all(lo <= sp.start and sp.end <= hi for sp in spans)
    assert len(xplane.spans_named(tr, "bench.decode")) == st["decode_steps"]

    def one_around(sp, name):
        outer = [o for o in spans if o.name == name and o is not sp
                 and o.start <= sp.start and sp.end <= o.end]
        assert len(outer) == 1, (sp, name)
        return outer[0]

    admits = program.named(spans, "serve.admit")
    prefills = program.named(spans, "serve.prefill")
    assert len(admits) == len(prefills) == st["admitted"] == len(reqs)
    for p in prefills:
        a = one_around(p, "serve.admit")
        assert a.args["rid"] == p.args["rid"]
        assert a.args["bucket"] >= p.args["tokens"]
        one_around(p, "serve.tick")
        assert len(program.inside(spans, p, "model.prefill")) == 1
    decodes = program.named(spans, "serve.decode")
    assert len(decodes) == st["decode_steps"]
    model_decodes = program.named(spans, "model.decode")
    assert len(model_decodes) == st["decode_steps"]
    for m in model_decodes:
        one_around(one_around(m, "serve.decode"), "serve.tick")
        assert m.args["slots"] == eng.slots
    assert {sp.args["rid"] for sp in admits} == {r.rid for r in reqs}
    assert len(program.named(spans, "serve.release")) == len(reqs)
    # warm: no capture, and the counter agrees
    assert st["region_captures"] == 0
    assert program.window_captures(spans, lo, hi) == 0
    # a tick that admits nothing writes a handful of spans
    for tick in program.named(spans, "serve.tick"):
        held = program.inside(spans, tick)
        if not any(sp.name == "serve.admit" for sp in held):
            assert len(held) + 1 <= 8, [sp.name for sp in held]
    # the engine's token times are the observer's, token for token
    assert [len(r.token_times) for r in reqs] == \
        [len(t) for t in win.token_times] == [len(r.out) for r in reqs]
    assert all(np.all(np.diff(r.token_times) >= 0) for r in reqs)
    assert program.decode_dispatch_ms(spans, lo, hi) > 0
    assert program.admit_host_ms(spans, lo, hi) > 0


def test_phase_split_reads_a_traced_cell(cpu_harness):
    """The tool's run: the harness's own traced run, its result line, and
    the program's spans of the same trace (a CPU trace has no device
    plane, so no idle time to split)."""
    from bench import phase_split
    from bench.tests import tiny
    load = xplane.load
    result, _, rep = phase_split.run_split(tiny.cell("serve"), 2**40 + 21,
                                           1.0, cpu_harness)
    assert xplane.load is load
    assert result["correct"] and "decode_step_ms" in result["metrics"]
    assert rep["phases"] == {} and rep["engine_idle_share"] == 0.0
    assert rep["decode_dispatch_ms"] > 0 and rep["admit_host_ms"] > 0
    # the harness warms every shape up: nothing is captured in the window
    assert rep["window_captures"] == 0
    assert rep["decode_dispatch_ms"] < result["metrics"]["decode_step_ms"][
        "value"]


def test_recorded_engine_trace_names_programs_and_splits_idle():
    tr, spans = xplane.load(PROGRAM), program.load(PROGRAM)
    mods = {m[2].split("(")[0] for v in tr.modules.values() for m in v}
    assert {"jit_tapir_slot_dense_block", "jit_tapir_slot_dense_prefill",
            "jit_tapir_slot_head"} <= mods
    assert not any(m.startswith("jit__positional") for m in mods)
    # the Pallas GEMM's device operations carry the kernel's name
    assert any(op[2].startswith("%fused_matmul.")
               for v in tr.ops.values() for op in v)
    # program spans share the harness's clock: every model dispatch lies
    # in exactly one observer span, which lies in the engine's own
    dec = xplane.spans_named(tr, "bench.decode")
    model = program.named(spans, "model.decode")
    assert len(model) == len(dec) == len(program.named(spans,
                                                       "serve.decode")) > 0
    for m in model:
        outer = [(s, e) for s, e in dec if s <= m.start and m.end <= e]
        assert len(outer) == 1
        assert len([sd for sd in program.named(spans, "serve.decode")
                    if sd.start <= outer[0][0]
                    and outer[0][1] <= sd.end]) == 1
    lo, hi = xplane.window(tr)
    split = program.idle_split(tr, spans, lo, hi)
    idle = hi - lo - xplane.busy_ns(tr, [(lo, hi)])
    assert sum(split.values()) == pytest.approx(idle, rel=1e-9)
    assert program.report(tr, spans)["window_captures"] == 0


def test_decode_head_share_on_the_recorded_trace():
    tr = xplane.load(PROGRAM)
    share = spec.metric_reader("decode_head_share").read(
        {"kind": "serve", "trace": tr})
    # the same number the slow way: every operation that starts inside a
    # head module (as ``top_ops`` places operations), inside the decode
    # spans
    lo, hi = xplane.window(tr)
    dec = [s for s in xplane.spans_named(tr, "bench.decode")
           if s[0] >= lo and s[1] <= hi]
    (plane, ops), = tr.ops.items()
    heads = [(s, e) for s, e, n in tr.modules[plane]
             if n.startswith("jit_tapir_slot_head(")]
    head_ops = [op for op in ops
                if any(s <= op[0] < e for s, e in heads)]
    want = 100.0 * xplane.covered(xplane.merge(head_ops), dec) / \
        xplane.busy_ns(tr, dec)
    assert share == pytest.approx(want, rel=1e-12)
    assert 0 < share < 100
