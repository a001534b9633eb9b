"""Shared test plumbing: the 8-host-device subprocess harness.

Mesh tests need more than one device, and the XLA_FLAGS device-count
override must be set before jax initializes — while the main pytest
process must keep seeing ONE device so smoke tests stay honest.  The
harness itself lives in :mod:`repro.testing`; this conftest re-exports it so every mesh test can just
``from conftest import run_mesh_subprocess`` without per-file
boilerplate (pytest puts this directory on ``sys.path``).
"""
from repro.testing import (MESH_DEVICE_COUNT,  # noqa: F401
                           run_mesh_subprocess)
