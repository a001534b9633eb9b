"""Operations and bytes a decode step of a ``granitemoehybrid``
configuration needs, from shapes alone, as ``work.py`` counts them for
``qwen2``: what the work itself requires of any implementation.
Multiply-adds count two operations.  ``D`` is
``reference.granitemoehybrid.dims(conf)``; the chip's share is what it
holds (``experts_held``).
"""
from __future__ import annotations

#: bytes of one element of the carried SSM state (float32)
STATE_BYTES = 4


def _kinds(D: dict) -> tuple:
    return (sum(t == "mamba" for t in D["types"]),
            sum(t == "attention" for t in D["types"]))


def mamba_params(D: dict) -> int:
    """A Mamba2 mixer's parameters: in/out projections, conv and its
    bias, dt_bias, A_log, D, the gated norm and the input norm."""
    d, din, N, H, K = D["d"], D["din"], D["N"], D["mH"], D["conv"]
    return (d * (2 * din + 2 * N + H) + din * d + (K + 1) * (din + 2 * N)
            + 3 * H + din + d)


def attention_params(D: dict) -> int:
    d, H, Hkv, hd = D["d"], D["H"], D["Hkv"], D["hd"]
    return d * H * hd + 2 * d * Hkv * hd + H * hd * d + d


def ffn_params(D: dict) -> int:
    """Router, the held experts, the shared expert and the norm."""
    d = D["d"]
    held = D["held"][1] - D["held"][0]
    return d * D["E"] + 3 * d * (held * D["ff"] + D["sff"]) + d


def held_weight_bytes(D: dict, dtype_bytes: int) -> int:
    """Every weight the chip holds, read once by a decode step whose
    routes hit every held expert: mixers, FFNs, final norm, tied head."""
    n_m, n_a = _kinds(D)
    return (n_m * mamba_params(D) + n_a * attention_params(D)
            + D["L"] * ffn_params(D) + D["d"] + D["d"] * D["V"]) * dtype_bytes


def state_row_bytes(D: dict, conv_dtype_bytes: int) -> int:
    """One slot's recurrent state, every Mamba2 layer: the f32 SSM state
    and the conv window."""
    n_m, _ = _kinds(D)
    ssm = D["mH"] * D["mP"] * D["N"] * STATE_BYTES
    conv = (D["conv"] - 1) * (D["din"] + 2 * D["N"]) * conv_dtype_bytes
    return n_m * (ssm + conv)


def token_flops(D: dict, keys: int) -> int:
    """One decode position through every layer and the head: matrix
    weights (the routed experts at their expected share of the top-k
    held here), the SSM update and read, attention over ``keys``."""
    n_m, n_a = _kinds(D)
    d = D["d"]
    held = D["held"][1] - D["held"][0]
    experts = D["K"] * held / D["E"] * 3 * d * D["ff"]
    mm = (n_m * (d * (2 * D["din"] + 2 * D["N"] + D["mH"]) + D["din"] * d)
          + n_a * (attention_params(D) - d)
          + D["L"] * (d * D["E"] + experts + 3 * d * D["sff"]) + d * D["V"])
    ssm = n_m * 4 * D["mH"] * D["mP"] * D["N"]
    attn = 4 * n_a * D["H"] * D["hd"] * keys
    return int(2 * mm + ssm + attn)


def decode_step_work(D: dict, keys_per_slot: list, weight_bytes: int,
                     kv_dtype_bytes: int, conv_dtype_bytes: int) -> tuple:
    """``(flops, bytes)`` of one decode step serving
    ``len(keys_per_slot)`` live requests: the held weights read once,
    each request's state rows read and written, its cached keys and
    values read (``keys_per_slot[i]`` positions) and one position
    written."""
    _, n_a = _kinds(D)
    kv = 2 * n_a * D["Hkv"] * D["hd"] * kv_dtype_bytes
    n = len(keys_per_slot)
    flops = sum(token_flops(D, k) for k in keys_per_slot)
    nbytes = (weight_bytes + 2 * n * state_row_bytes(D, conv_dtype_bytes)
              + kv * sum(keys_per_slot) + kv * n)
    return flops, nbytes


def ssm_step_bytes(D: dict, slots: int) -> int:
    """Bytes one ``ssm_state_step`` call over ``slots`` rows of one layer
    needs: the f32 state read and written, the decay, dt*x, B and C it
    reads and the y it writes."""
    H, P, N = D["mH"], D["mP"], D["N"]
    return slots * STATE_BYTES * (2 * H * P * N + H + 2 * H * P + 2 * N)
