"""CPU runs of the harness: peaks for the CPU (no device metric is read
from them), and the program's compiled-program store in a temporary
directory."""
import pytest

from bench.harness import common


@pytest.fixture
def cpu_harness(monkeypatch, tmp_path):
    monkeypatch.setattr(common, "peaks_for", lambda kind: {
        "bf16_flops": 197e12, "hbm_bytes_per_s": 819e9})
    monkeypatch.setattr(common, "PROGRAM_CACHE", str(tmp_path / "programs"))
    return common.device_record(1)
