"""Multi-device subprocess harness, shared by the tests and the examples.

Mesh code needs more than one device, and
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` must be set BEFORE
jax initializes — while the calling process must keep seeing ONE device
(smoke tests assume it).  So mesh bodies run
in a subprocess with a common preamble and hand their findings back as a
``result`` dict printed behind a ``RESULT::`` marker.

Pre-imported in the subprocess: ``os``, ``json``, ``dataclasses``,
``jax``, ``jnp``, ``np``; the repo's ``src`` is on PYTHONPATH.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

#: the repo's src dir (this file lives at src/repro/testing.py)
SRC_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

MESH_DEVICE_COUNT = 8


def _preamble(devices: int) -> str:
    return textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = \
            "--xla_force_host_platform_device_count={devices}"
        import json
        import dataclasses
        import jax
        import jax.numpy as jnp
        import numpy as np
        result = {{}}
    """)


def run_mesh_subprocess(body: str, timeout: int = 580,
                        devices: int = MESH_DEVICE_COUNT) -> dict:
    """Run ``body`` under ``devices`` forced host devices and return the
    ``result`` dict it populated."""
    script = (_preamble(devices) + textwrap.dedent(body)
              + "\nprint('RESULT::' + json.dumps(result))\n")
    # a CPU-emulated mesh: the child never reaches for an accelerator the
    # parent process may hold
    env = dict(os.environ, PYTHONPATH=SRC_DIR, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=timeout)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    for line in out.stdout.splitlines():
        if line.startswith("RESULT::"):
            return json.loads(line[len("RESULT::"):])
    raise AssertionError(f"no RESULT:: line in\n{out.stdout[-2000:]}")
