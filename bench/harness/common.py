"""Plumbing every cell shares: the chip check, the compile caches, the
compile counter, the peaks table, the trace window and the result line."""
from __future__ import annotations

import contextlib
import gc
import json
import os
import shutil
import sys
import tempfile
import time

from bench.harness import spec as spec_mod

#: jax's persistent compilation cache and the program's own compiled-program
#: store, at fixed paths inside the checkout (their paths are part of the
#: cache keys, so they never move)
JAX_CACHE = os.path.join(spec_mod.BENCH_DIR, ".cache", "jax")
PROGRAM_CACHE = os.path.join(spec_mod.BENCH_DIR, ".cache", "programs")

#: jax events that mark a jit cache miss: lowering (every miss) and a
#: backend compile (a miss that the persistent cache did not serve)
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


class NoChip(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def configure_caches() -> None:
    """Point jax's persistent cache into the checkout; call before jax
    compiles anything.  Every program is cached, however fast it
    compiled, so a second run of a cell compiles nothing."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = JAX_CACHE
    import jax
    jax.config.update("jax_compilation_cache_dir", JAX_CACHE)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def require_chips(n: int) -> dict:
    """The device record of the result line; raises ``NoChip`` off a TPU
    or with fewer than ``n`` chips."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found platform "
                     f"{devs[0].platform!r} ({devs[0].device_kind})")
    if len(devs) < n:
        raise NoChip(f"the cell asks for {n} chips; JAX sees {len(devs)}")
    return device_record(n)


def device_record(n: int) -> dict:
    import jax
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": n}


def memory_peak_bytes(n: int) -> int:
    """Peak bytes in use on the fullest of the first ``n`` devices (0
    where the backend keeps no such count)."""
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:n]]
    return int(max(peaks))


def memory_in_use_bytes(n: int) -> int:
    """Bytes in use now on the fullest of the first ``n`` devices (0 where
    the backend keeps no such count): what the window holds, where the
    peak may be a transient of the set-up."""
    import jax
    return int(max((d.memory_stats() or {}).get("bytes_in_use", 0)
                   for d in jax.devices()[:n]))


def peaks_for(kind: str) -> dict:
    table = spec_mod.load_json(os.path.join(spec_mod.BENCH_DIR,
                                            "peaks.json"))["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in "
                       f"bench/peaks.json (have {sorted(table)})")
    return table[kind]


class CompileCounter:
    """Counts jit cache misses while ``active``."""

    def __init__(self):
        import jax.monitoring as mon
        self.count, self.active = 0, False
        mon.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if self.active and event in COMPILE_EVENTS:
            self.count += 1


class GcWatch:
    """Counts the collector's passes and their time while ``active``, by
    generation."""

    def __init__(self):
        self.count, self.secs = [0, 0, 0], [0.0, 0.0, 0.0]
        self.active, self._t0 = False, None
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self.active and self._t0 is not None:
            g = info["generation"]
            self.count[g] += 1
            self.secs[g] += time.perf_counter() - self._t0

    def close(self) -> str:
        """Stops watching; returns what was counted."""
        gc.callbacks.remove(self._on_gc)
        return ", ".join(f"gen{g} {n} in {1e3 * t:.1f} ms" for g, (n, t)
                         in enumerate(zip(self.count, self.secs)))


def settle_heap() -> None:
    """The last step of set-up, as a long-running server takes it once it
    is warm: collect, then freeze what set-up allocated (captured graphs,
    compiled programs, caches), so that the collector's full passes in the
    window scan only what the window allocates."""
    gc.collect()
    gc.freeze()


def start_trace(trace_dir: str) -> None:
    """The profiler with device and host events and no Python call
    tracing (which slows every call and swells the trace)."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


@contextlib.contextmanager
def profiler_session(enabled: bool):
    """Yields the trace directory (None when tracing is off); removed on
    exit, so a run leaves no trace behind."""
    if not enabled:
        yield None
        return
    d = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        yield d
    finally:
        shutil.rmtree(d, ignore_errors=True)


def span(name: str, enabled: bool):
    """A host span in the profiler's trace (``bench.<name>``)."""
    if not enabled:
        return contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation("bench." + name)


def emit_result(result: dict, checks: list) -> None:
    """Print the compared numbers last on standard error, then the result
    line last on standard output, with ``checks`` as its last key."""
    for c in checks:
        log(f"check {c['name']}: {c['value']!r} limit {c['limit']!r} "
            f"{'ok' if c['ok'] else 'FAILED'}")
    out = dict(result)
    out["checks"] = checks
    sys.stdout.flush()
    print(json.dumps(out), flush=True)


def now() -> float:
    return time.perf_counter()
