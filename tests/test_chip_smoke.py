"""``chip_smoke.py``'s phases at a tiny size on the CPU: the same code paths
the chip run takes (engine, region prefill vs opaque baseline, captured
train step, TP mesh vs one device), with the smoke config in place of the
published one.  The script itself must refuse a CPU."""
import dataclasses
import importlib.util
import json
import os
import subprocess
import sys

import repro.configs as C
from conftest import run_mesh_subprocess

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")
TINY = dict(slots=2, max_len=64, n_req=3, lens=(5, 20), max_new=4, seed=0)


def _load():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_serve_phase_tiny():
    cs = _load()
    cfg = dataclasses.replace(C.get_smoke("qwen2_5_3b"),
                              param_dtype="bfloat16")
    res = cs.serve_phase(cfg, **TINY)
    assert res["tokens_per_run"] == TINY["n_req"] * TINY["max_new"]
    err = res["logits_err"]
    assert err["region_vs_f32"] <= cs.BF16_ERR_FACTOR * err["opaque_vs_f32"]
    assert cs.impls_bound()["matmul"]


def test_shallow_phase_tiny():
    cs = _load()
    cfg = dataclasses.replace(C.get_smoke("qwen2_5_3b"),
                              param_dtype="bfloat16")
    res = cs.shallow_phase(cfg, n_layers=cs.SHALLOW_LAYERS,
                           slots=TINY["slots"], max_len=TINY["max_len"],
                           lens=TINY["lens"], seed=0)
    assert res["logits_err"]["region_vs_f32"] <= cs.SHALLOW_TOL


def test_train_phase_tiny():
    cs = _load()
    cfg = dataclasses.replace(C.get_smoke("qwen2_5_3b"), n_layers=1)
    res = cs.train_phase(cfg, seq=16, steps=3, seed=0)
    assert len(res["losses"]) == 3


def test_mesh_phase_tiny_on_four_cpu_devices():
    res = run_mesh_subprocess(f"""
        import sys
        sys.path.insert(0, {ROOT!r})
        import chip_smoke as cs
        import repro.configs as C
        # f32 compute: XLA:CPU's f32 dots are not bitwise under a column
        # split, and bf16 rounding would amplify that into token flips
        cfg = dataclasses.replace(C.get_smoke("qwen2_5_3b"),
                                  compute_dtype="float32")
        with jax.default_matmul_precision("highest"):
            out = cs.mesh_phase(cfg, model_axis=4, **{TINY!r})
        result["same"] = out["tokens_identical"]
        result["err"] = out["logits_err"]["mesh_vs_one_device"]
        result["tol"] = cs.MESH_F32_TOL
        result["shards"] = out["shards"]
    """, devices=4)
    assert res["same"]     # f32 on CPU: TP keeps the greedy tokens
    assert res["err"] <= res["tol"]
    assert len(res["shards"]["wq"]["per_device"]) == 4


def test_script_refuses_cpu():
    out = subprocess.run([sys.executable, SCRIPT],
                         env=dict(os.environ, JAX_PLATFORMS="cpu"),
                         capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert "'cpu'" in out.stderr
    for line in out.stdout.splitlines():
        assert not (line.startswith("{") and json.loads(line).get("ok"))
