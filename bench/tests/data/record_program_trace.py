"""Records the serving trace with program spans that
``test_bench_program_spans.py`` reads: the tiny serving cell through the
real engine behind the harness's observer, warmed up, then traced, on
whatever device JAX finds.  Its region programs carry their names
(``jit_tapir_slot_head``) and the engine its ``repro.`` spans.

    python3 bench/tests/data/record_program_trace.py <out.xplane.pb>
"""
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
from bench.tests import engine_trace  # noqa: E402

SEED = 2**40 + 17
REQUESTS = 6


def main(out: str) -> None:
    eng, obs, stream = engine_trace.engine(SEED, REQUESTS)
    d = tempfile.mkdtemp()
    try:
        engine_trace.traced_run(eng, obs, stream, os.path.join(d, "cold"))
        _, _, path = engine_trace.traced_run(eng, obs, stream,
                                             os.path.join(d, "warm"))
        shutil.copy(path, out)
    finally:
        shutil.rmtree(d, ignore_errors=True)


if __name__ == "__main__":
    main(sys.argv[1])
