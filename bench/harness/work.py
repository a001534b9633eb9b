"""Operations and bytes that the work itself needs, from shapes alone.

These count what a token or a step requires of any implementation, not
what the program happens to do: a kernel that reads more than it needs,
or recomputes, does not raise its own count.  Multiply-adds count two
operations.  ``D`` is ``reference.<model_type>.dims(conf)``.
"""
from __future__ import annotations


def layer_matmul_params(D: dict) -> int:
    """Weights that multiply activations in one decoder layer."""
    d, H, Hkv, hd, ff = D["d"], D["H"], D["Hkv"], D["hd"], D["ff"]
    return d * H * hd + 2 * d * Hkv * hd + H * hd * d + 3 * d * ff


def layer_params(D: dict) -> int:
    """Every parameter of one decoder layer (matrices, biases, norms)."""
    return (layer_matmul_params(D) + (D["H"] + 2 * D["Hkv"]) * D["hd"]
            + 2 * D["d"])


def head_params(D: dict) -> int:
    return D["d"] * D["V"]


def matmul_params(D: dict) -> int:
    """Matrix weights a token passes through, LM head included."""
    return D["L"] * layer_matmul_params(D) + head_params(D)


def attn_flops(D: dict, keys: int) -> int:
    """Scores and weighted sum for one query position against ``keys``
    cached positions, all layers."""
    return 4 * D["L"] * D["H"] * D["hd"] * keys


def decode_token_flops(D: dict, keys: int) -> int:
    """One new token whose attention reads ``keys`` positions (its own
    included), through every layer and the head."""
    return 2 * matmul_params(D) + attn_flops(D, keys)


def prefill_flops(D: dict, prompt_len: int) -> int:
    """A prompt's forward pass: every position through every layer with
    causal attention, and the head for the last position only (the only
    logits a prompt needs)."""
    n = prompt_len
    body = 2 * D["L"] * layer_matmul_params(D) * n
    attn = 4 * D["L"] * D["H"] * D["hd"] * n * (n + 1) // 2
    return body + attn + 2 * head_params(D)


def kv_bytes_per_position(D: dict, dtype_bytes: int) -> int:
    """Keys and values one position keeps, all layers."""
    return 2 * D["L"] * D["Hkv"] * D["hd"] * dtype_bytes


def decode_step_work(D: dict, keys_per_slot: list, weight_bytes: int,
                     kv_dtype_bytes: int) -> tuple:
    """``(flops, bytes)`` of one decode step serving ``len(keys_per_slot)``
    live requests, request ``i`` attending over ``keys_per_slot[i]``
    positions: every weight read once, each request's cached keys and
    values read, one new position written per request."""
    flops = sum(decode_token_flops(D, k) for k in keys_per_slot)
    kv = kv_bytes_per_position(D, kv_dtype_bytes)
    nbytes = (weight_bytes + kv * sum(keys_per_slot)
              + kv * len(keys_per_slot))
    return flops, nbytes


def decode_weight_bytes(D: dict, dtype_bytes: int) -> int:
    """Weights a decode step must read: every layer, the final norm, the
    head; the embedding rows it looks up are negligible."""
    return (D["L"] * layer_params(D) + D["d"] + head_params(D)) * dtype_bytes


def train_token_flops(D: dict, seq: int) -> int:
    """Forward and backward for one token of a ``seq``-long causal
    sequence: 6 operations per matrix weight (head included) and three
    times the forward attention, averaged over positions.  Recomputation
    does not count."""
    attn_fwd = 4 * D["L"] * D["H"] * D["hd"] * (seq + 1) / 2
    return 6 * matmul_params(D) + 3 * attn_fwd
