"""A serving cell: ``ServingEngine.run`` over a seeded backlog, one timed
window, then the served tokens against the float32 reference.

The engine's model is wrapped in an ``Observer``: a proxy that forwards
every attribute and times the two slot entry points,
``prefill_into_slot`` and ``decode_step_slots``, blocking on their logits
(the engine reads them right after anyway).  It keeps the slot books
(which request holds which slot, how many tokens it still owes, how many
positions it has cached), so every output token gets a time stamp.  The
window opens at the first decode step once every slot is busy and closes
at the end of the first call that ends ``seconds`` later; the observer
then raises ``WindowClosed`` out of ``run``.  A traced run traces the
window's first ``TRACE_SECONDS``.  Nothing in the program is
changed.
"""
from __future__ import annotations

import dataclasses
import gc

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import common, spec, traffic

#: rows of the LM head's logits computed at once by the reference
HEAD_ROWS = 256
#: seconds of the window that a traced run traces: collecting the trace
#: takes the profiler several times as long as the traced span
TRACE_SECONDS = 10.0


class WindowClosed(Exception):
    """Raised from the observer when the measured window has closed."""


@dataclasses.dataclass
class Call:
    kind: str            # "prefill" | "decode"
    t0: float
    t1: float
    keys: list           # positions attended by each request served


class ServeWindow:
    """The slot books and the window's clock."""

    def __init__(self, reqs: list, slots: int, seconds: float, on_open,
                 on_close, on_trace_end):
        self.reqs, self.seconds = reqs, float(seconds)
        self.on_open, self.on_close = on_open, on_close
        self.on_trace_end = on_trace_end
        self.slot_req = [-1] * slots
        self.left = [0] * slots
        self.ctx = [0] * slots
        self.admitted = 0
        self.token_times = [[] for _ in reqs]
        self.admit_time = [None] * len(reqs)
        self.calls: list = []
        self.t_open = self.t_close = self.t_trace_end = None

    def before(self, kind: str) -> None:
        if (self.t_open is None and kind == "decode"
                and min(self.slot_req) >= 0):
            self.on_open()
            self.t_open = common.now()

    def after_prefill(self, t0, t1, slot, plen, start) -> None:
        k = self.admitted
        if k >= len(self.reqs) or len(self.reqs[k].prompt) != plen \
                or start != 0:
            raise RuntimeError(f"admission {k} (slot {slot}, plen {plen}, "
                               f"start {start}) is not the next request")
        self.admitted += 1
        self.admit_time[k] = t1
        self.token_times[k].append(t1)
        self.slot_req[slot], self.ctx[slot] = k, plen
        self.left[slot] = self.reqs[k].max_new - 1
        if self.left[slot] == 0:
            self.slot_req[slot] = -1
        self.calls.append(Call("prefill", t0, t1, [plen]))
        self._maybe_close(t1)

    def after_decode(self, t0, t1) -> None:
        keys = []
        for s, k in enumerate(self.slot_req):
            if k < 0:
                continue
            keys.append(self.ctx[s] + 1)
            self.token_times[k].append(t1)
            self.ctx[s] += 1
            self.left[s] -= 1
            if self.left[s] == 0:
                self.slot_req[s] = -1
        self.calls.append(Call("decode", t0, t1, keys))
        self._maybe_close(t1)

    def _maybe_close(self, t1) -> None:
        if self.t_open is None:
            return
        if self.t_trace_end is None and (
                t1 - self.t_open >= min(TRACE_SECONDS, self.seconds)):
            self.t_trace_end = t1
            self.on_trace_end()
        if t1 - self.t_open >= self.seconds:
            self.t_close = t1
            self.on_close()
            raise WindowClosed()

    def in_window(self, t: float) -> bool:
        return self.t_open < t <= self.t_close

    def in_traced(self, t: float) -> bool:
        """In the traced part, which the per-layer metrics read: stopping
        the profiler stalls the engine, so what follows it is not read."""
        return self.t_open < t <= self.t_trace_end


class Observer:
    """The engine's model, with its two slot entry points timed."""

    def __init__(self, model, trace: bool):
        self._model, self._trace = model, trace
        self.window = None           # a ServeWindow while the main run goes

    def __getattr__(self, name):
        return getattr(self._model, name)

    def prefill_into_slot(self, sp, tokens, cache, slot, plen, start=0):
        w = self.window
        if w is not None:
            w.before("prefill")
        with common.span("prefill", self._trace and w is not None):
            t0 = common.now()
            logits, cache = self._model.prefill_into_slot(
                sp, tokens, cache, slot, plen, start=start)
            jax.block_until_ready(logits)
            t1 = common.now()
        if w is not None:
            w.after_prefill(t0, t1, slot, plen, start)
        return logits, cache

    def decode_step_slots(self, sp, tokens, cache):
        w = self.window
        if w is not None:
            w.before("decode")
        with common.span("decode", self._trace and w is not None):
            t0 = common.now()
            logits, cache = self._model.decode_step_slots(sp, tokens, cache)
            jax.block_until_ready(logits)
            t1 = common.now()
        if w is not None:
            w.after_decode(t0, t1)
        return logits, cache


def warmup_lengths(lengths, max_len: int) -> list:
    """One prompt length for every program shape the stream can reach:
    each (prefill bucket, prompt pages published) pair the engine will
    meet, read from the program's own bucketing and page geometry."""
    from repro.models.layers import bucket_pow2
    from repro.serve.pages import page_geometry
    page_len, pps = page_geometry(max_len)
    seen = {}
    for n in sorted(set(int(x) for x in lengths)):
        key = (min(bucket_pow2(n), max_len), min(n // page_len, pps))
        seen.setdefault(key, n)
    return sorted(seen.values())


def _reference_hidden(conf, ad, ref, seed, tokens, mode, layer_fn):
    x = ref.embed(ad.reference_globals(conf, seed)["embed"],
                  jnp.asarray(tokens))
    for l in range(int(conf["num_hidden_layers"])):
        x = layer_fn[mode](ad.reference_layer(conf, seed, l), x)
    return x


def logit_gaps(conf: dict, seed: int, seqs: list, T: int,
               modes=("f32",)) -> dict:
    """The reference over each ``(prompt, served)`` pair of ``seqs``, one
    layer at a time.  For each position that produced a served token:
    ``{"served": best reference logit - reference logit of the served
    token}``, and per lower-precision mode ``m`` in ``modes``
    ``{m: best reference logit - reference logit of m's first choice}``.
    Sequences are padded to ``T`` positions, so every run of a cell
    compiles the same shapes."""
    ad, ref = spec.adapter(conf), spec.reference(conf)
    N = len(seqs)
    tokens = np.zeros((N, T), np.int32)
    rows = []
    for i, (prompt, out) in enumerate(seqs):
        seq = np.concatenate([prompt, np.asarray(out[:-1], np.int32)])
        tokens[i, :len(seq)] = seq
        rows.extend((i, len(prompt) - 1 + j) for j in range(len(out)))
    served = np.concatenate([np.asarray(o, np.int32) for _, o in seqs])
    R = len(rows)
    pad = -R % HEAD_ROWS
    ri = np.array([r[0] for r in rows] + [0] * pad, np.int32)
    rj = np.array([r[1] for r in rows] + [0] * pad, np.int32)
    layer_fn = {m: jax.jit(lambda w, x, m=m: ref.layer(conf, w, x, m))
                for m in modes}
    hidden = {m: _reference_hidden(conf, ad, ref, seed, tokens, m, layer_fn)
              [ri, rj] for m in modes}
    glob = ad.reference_globals(conf, seed)
    head = glob["embed"].T
    head_fn = jax.jit(lambda ln, w, x, m: ref.logits(conf, ln, w, x, m),
                      static_argnums=3)
    out = {"served": []}
    out.update({m: [] for m in modes if m != "f32"})
    for c in range(0, R + pad, HEAD_ROWS):
        sl = slice(c, c + HEAD_ROWS)
        z = head_fn(glob["ln_f"], head, hidden["f32"][sl], "f32")
        best = jnp.max(z, axis=-1)
        tok = jnp.asarray(np.concatenate([served, np.zeros(pad, np.int32)])
                          [sl])
        out["served"].append(best - jnp.take_along_axis(
            z, tok[:, None], axis=-1)[:, 0])
        for m in modes:
            if m == "f32":
                continue
            pick = jnp.argmax(head_fn(glob["ln_f"], head, hidden[m][sl], m),
                              axis=-1)
            out[m].append(best - jnp.take_along_axis(
                z, pick[:, None], axis=-1)[:, 0])
    return {k: np.concatenate([np.asarray(a) for a in v])[:R]
            for k, v in out.items()}


def _sample(finished: list, reqs: list, n: int, seed: int) -> list:
    """``n`` of the finished requests, drawn from the seed, always with
    the one that served the most tokens."""
    if not finished:
        return []
    longest = max(finished, key=lambda k: reqs[k].max_new)
    rest = [k for k in finished if k != longest]
    rng = np.random.default_rng([seed, 7])
    pick = list(rng.choice(rest, size=min(n - 1, len(rest)), replace=False)) \
        if rest else []
    return sorted([longest] + [int(k) for k in pick])


def score(numbers: dict, conf: dict, mix: dict) -> list:
    """The checks of ``correct`` over one side's ``numbers``
    (``max_logit_gap``, ``served_tokens_compared``), each beside its
    limit: the program's in a run, the control's in calibration."""
    widest = numbers["max_logit_gap"]
    limit = float(conf["correct"]["max_logit_gap"])
    n = numbers["served_tokens_compared"]
    least = int(mix["check"]["min_tokens"])
    return [{"name": "max_logit_gap", "value": widest, "limit": limit,
             "ok": widest is not None and widest <= limit},
            {"name": "served_tokens_compared", "value": n, "limit": least,
             "ok": n >= least}]


def run(cell: spec.Cell, seed: int, seconds: float, trace: bool,
        t_start: float, device: dict, wrap_model=None,
        control: str = None) -> tuple:
    """One run of a serving cell.  Returns ``(result, checks, ctx)``.
    ``wrap_model`` (tests) wraps the program's model to plant a fault;
    ``control`` (calibration) also reads the gaps of a reference computed
    in that lower precision (a ``reference.einsum`` mode) into
    ``ctx["control"]``."""
    from repro.models.base import get_model
    from repro.serve import Request, ServeConfig, ServingEngine
    conf, mix = cell.conf, cell.traffic
    ad = spec.adapter(conf)
    D = spec.reference(conf).dims(conf)
    slots, max_len = int(mix["slots"]), int(mix["max_len"])

    model = get_model(ad.model_config(conf, cell.config_name))
    if wrap_model is not None:
        model = wrap_model(model)
    stream = traffic.serve_requests(mix, seed, D["V"])
    counter, gcw = common.CompileCounter(), common.GcWatch()
    obs = Observer(model, trace)
    params = ad.program_params(conf, seed)
    jax.block_until_ready(params)
    eng = ServingEngine(obs, params, batch=slots, max_len=max_len,
                        cfg=ServeConfig(program_cache_dir=common.PROGRAM_CACHE))
    del params          # the engine's slot slices become the only weights

    warm_rng = np.random.default_rng([seed, 1])
    warm = [Request(rid=-1 - i, prompt=warm_rng.integers(
                0, D["V"], size=n).astype(np.int32), max_new=2)
            for i, n in enumerate(warmup_lengths(
                [len(r.prompt) for r in stream], max_len))]
    eng.run(warm, max_steps=4)
    common.log(f"warmed {len(warm)} prompt shapes in "
               f"{common.now() - t_start:.2f} s since start")

    tr = {"dir": None, "window": None, "in_use": 0}

    def on_open():
        common.settle_heap()
        if tr["dir"] is not None:
            common.start_trace(tr["dir"])
            tr["window"] = jax.profiler.TraceAnnotation("bench.window")
            tr["window"].__enter__()
        counter.active = gcw.active = True

    def on_trace_end():
        if tr["window"] is not None:
            tr["window"].__exit__(None, None, None)
            jax.profiler.stop_trace()
            tr["window"] = None

    def on_close():
        counter.active = gcw.active = False
        tr["in_use"] = common.memory_in_use_bytes(device["count"])

    reqs = [Request(rid=k, prompt=r.prompt, max_new=r.max_new)
            for k, r in enumerate(stream)]
    win = ServeWindow(stream, slots, seconds, on_open, on_close,
                      on_trace_end)
    obs.window = win
    with common.profiler_session(trace) as tdir:
        tr["dir"] = tdir
        try:
            eng.run(reqs, max_steps=max(r.max_new for r in stream) + 1)
        except WindowClosed:
            pass
        else:
            raise RuntimeError("the request stream ran dry before the "
                               "window closed; raise the mix's epochs")
        obs.window = None
        trace_data = None
        if tdir is not None:
            from bench.harness import xplane
            t_load = common.now()
            trace_data = xplane.load(xplane.find_xplane(tdir))
            common.log(f"trace read in {common.now() - t_load:.1f} s")
    device = dict(device, memory_peak_bytes=common.memory_peak_bytes(
        device["count"]))
    if counter.count:
        common.log(f"WARNING: {counter.count} programs compiled inside the "
                   f"window")
    dec = np.array([c.t1 - c.t0 for c in win.calls
                    if c.kind == "decode" and win.in_window(c.t1)]) * 1e3
    pre = [c for c in win.calls if c.kind == "prefill" and win.in_window(c.t1)]
    common.log(f"window: {len(dec)} decode steps, ms: median "
               f"{np.median(dec):.2f}, max {dec.max():.2f}; {len(pre)} "
               f"prefills, {sum(c.t1 - c.t0 for c in pre):.3f} s; "
               f"collector: {gcw.close()}; bytes in use at the close "
               f"{tr['in_use']}, peak {device['memory_peak_bytes']}")
    setup_s = win.t_open - t_start
    del eng, obs, model
    gc.collect()

    # finished in the window, and read back by the engine: the tokens of
    # the call that closed the window never reach a request's ``out``
    finished = [k for k, tt in enumerate(win.token_times)
                if len(tt) == stream[k].max_new and win.in_window(tt[-1])
                and len(reqs[k].out) == stream[k].max_new]
    sample = _sample(finished, stream, int(mix["check"]["requests"]), seed)
    modes = ("f32",) if control is None else ("f32", control)
    t_ref = common.now()
    gaps = logit_gaps(conf, seed, [(stream[k].prompt, reqs[k].out)
                                   for k in sample], max_len, modes) \
        if sample else {"served": np.zeros(0)}
    common.log(f"reference over {len(sample)} requests took "
               f"{common.now() - t_ref:.2f} s")
    widest = float(gaps["served"].max()) if gaps["served"].size else None
    checks = score({"max_logit_gap": widest,
                    "served_tokens_compared": int(gaps["served"].size)},
                   conf, mix)
    admitted = [k for k, t in enumerate(win.admit_time)
                if t is not None and win.in_window(t)]
    ctx = dict(kind="serve", D=D, conf=conf, mix=mix, window=win,
               setup_s=setup_s, trace=trace_data, device=device,
               peaks=common.peaks_for(device["kind"]),
               window_compiles=counter.count,
               score=lambda numbers: score(numbers, conf, mix))
    if control is not None and control in gaps:
        ctx["control"] = {"max_logit_gap": float(gaps[control].max()),
                          "served_tokens_compared": int(gaps[control].size)}
    result = {"correct": all(c["ok"] for c in checks),
              "attempted": len(admitted), "failed": 0, "device": device}
    return result, checks, ctx
