"""Operations and bytes of a Mistral/Llama-style decoder step, from shapes.

Nothing here reads the program: the numbers follow from the published
configuration (``config.json`` keys) and the deployment's weight and cache
types alone, so a roofline share stands on arithmetic a later PR cannot move.

Matrix parameters of one layer: q and o are ``hidden x heads*head_dim``, k
and v are ``hidden x kv_heads*head_dim``, and gate, up and down are
``hidden x intermediate``. One token through one matrix of P parameters is
2P floating-point operations (a multiply and an add each).
"""

from __future__ import annotations

from typing import Any, Dict


def layer_matrix_params(m: Dict[str, Any]) -> int:
    h, hd = m["hidden_size"], m["head_dim"]
    q = h * m["num_attention_heads"] * hd
    kv = h * m["num_key_value_heads"] * hd
    return 2 * q + 2 * kv + 3 * h * m["intermediate_size"]


def head_params(m: Dict[str, Any]) -> int:
    return m["hidden_size"] * m["vocab_size"]


def matmul_flops_per_token(m: Dict[str, Any]) -> int:
    """FLOPs of the layers' matrix multiplications for one token position.
    The output head runs on one position per sequence and attention's own
    score and value products depend on the context; both are left out, so a
    share built on this reads a few per cent low and never high."""
    return 2 * m["num_hidden_layers"] * layer_matrix_params(m)


def prefill_flops(m: Dict[str, Any], tokens_walked: int) -> int:
    """FLOPs of prefill programs that walked ``tokens_walked`` token slots,
    padding included (a padded slot is multiplied like a real one)."""
    return matmul_flops_per_token(m) * int(tokens_walked)


def weight_bytes_per_step(m: Dict[str, Any], weight_bytes: float,
                          chips: int = 1) -> float:
    """Bytes of weights one decode step must read on one chip: every layer
    matrix and the output head once, whatever the batch. The embedding table
    is a gather of a few rows and is left out. Under tensor parallelism each
    chip holds, and reads, its ``1/chips`` of every matrix."""
    params = (m["num_hidden_layers"] * layer_matrix_params(m)
              + head_params(m))
    return params * weight_bytes / chips


def kv_bytes_per_token(m: Dict[str, Any], kv_bytes: float,
                       chips: int = 1) -> float:
    """Bytes of cached keys and values of one context token on one chip."""
    return (2 * m["num_hidden_layers"] * m["num_key_value_heads"]
            * m["head_dim"] * kv_bytes / chips)


def decode_bytes(m: Dict[str, Any], steps: int, context_tokens: int,
                 weight_bytes: float, kv_bytes: float, chips: int = 1
                 ) -> float:
    """Bytes ``steps`` decode steps must read on one chip: the weights once
    a step, and the keys and values of every live context token the steps
    attended over (``context_tokens`` summed over steps and rows)."""
    return (steps * weight_bytes_per_step(m, weight_bytes, chips)
            + context_tokens * kv_bytes_per_token(m, kv_bytes, chips))


FUNCTIONS = {"prefill_flops": prefill_flops, "decode_bytes": decode_bytes}
