"""The tiny serving cell's engine behind the harness's observer, run under
the profiler: what the CPU tests of the program's spans read, and what
``data/record_program_trace.py`` records on a chip."""
from __future__ import annotations

import jax

from bench.harness import common, serve, spec, traffic, xplane
from bench.tests import tiny


def engine(seed: int, n_requests: int = 10) -> tuple:
    """``(engine, observer, stream)``: the first ``n_requests`` of the tiny
    chat mix, drawn from ``seed``, and an engine with their slots."""
    from repro.models.base import get_model
    from repro.serve import ServingEngine
    cell = tiny.cell("serve")
    ad = spec.adapter(cell.conf)
    D = spec.reference(cell.conf).dims(cell.conf)
    model = get_model(ad.model_config(cell.conf, cell.config_name))
    obs = serve.Observer(model, trace=True)
    eng = ServingEngine(obs, ad.program_params(cell.conf, seed),
                        batch=cell.traffic["slots"],
                        max_len=cell.traffic["max_len"])
    stream = traffic.serve_requests(cell.traffic, seed, D["V"])[:n_requests]
    return eng, obs, stream


def traced_run(eng, obs, stream: list, trace_dir: str) -> tuple:
    """Serves ``stream`` once under the profiler, in a ``bench.window``
    with the observer's spans; returns ``(requests, window, xplane
    path)``."""
    from repro.serve import Request
    reqs = [Request(rid=k, prompt=r.prompt, max_new=r.max_new)
            for k, r in enumerate(stream)]

    def noop():
        pass

    obs.window = win = serve.ServeWindow(stream, eng.slots, 1e9, noop, noop,
                                         noop)
    common.start_trace(trace_dir)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            eng.run(reqs, max_steps=max(r.max_new for r in stream) + 1)
    finally:
        jax.profiler.stop_trace()
        obs.window = None
    return reqs, win, xplane.find_xplane(trace_dir)
