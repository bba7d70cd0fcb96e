"""Bytes of an AFMoE (Trinity-Mini) decode step, from shapes and from what
the program's counters say routing and the window did. ``shapes.py`` prices
a dense decoder, whose step reads every matrix once; here what a step reads
depends on the experts its rows touched and on the window.

Nothing here reads the program: the sizes follow from the published
configuration's keys and the deployment's weight and cache types. One decode
step on one chip reads

- every layer's attention matrices once: q, o and the output gate
  ``hidden x heads*head_dim``, k and v ``hidden x kv_heads*head_dim``;
- a dense layer's MLP (gate, up, down: ``hidden x intermediate_size``);
- an expert layer's shared expert (``hidden x moe_intermediate_size x
  num_shared_experts``, three matrices) and its float32 router;
- the three matrices of every expert that got at least one assignment, and
  of no other: the counter ``moe.experts_touched`` (summed over expert layers
  and steps) says how many;
- the output head, ``hidden x vocab_size`` (the embedding is a gather of a
  few rows and is left out, as are the norms' scales);
- the cached keys and values its queries see: every live token in a full
  layer (``pad_by_phase.decode.real``), the last ``sliding_window`` of them
  in a window layer (the counter ``window.tokens_visible``, summed over
  window layers). The kernel copies whole 16-token blocks from the window's
  lower edge on, a few per cent more than is counted here: the share reads
  low, never high.
"""

from __future__ import annotations

from typing import Any, Dict


def attention_params(m: Dict[str, Any]) -> int:
    h, hd = m["hidden_size"], m["head_dim"]
    q = h * m["num_attention_heads"] * hd
    kv = h * m["num_key_value_heads"] * hd
    return 3 * q + 2 * kv                  # q, o, the output gate; k, v


def expert_params(m: Dict[str, Any]) -> int:
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def fixed_bytes_per_step(m: Dict[str, Any], weight_bytes: float) -> float:
    """What a step reads whatever it routed: attention everywhere, the
    dense layers' MLP, the shared experts, the float32 routers, the head."""
    n_dense = m["num_dense_layers"]
    n_moe = m["num_hidden_layers"] - n_dense
    dense = 3 * m["hidden_size"] * m["intermediate_size"]
    router = m["hidden_size"] * m["num_experts"] * 4.0
    return ((m["num_hidden_layers"] * attention_params(m)
             + n_dense * dense
             + n_moe * m["num_shared_experts"] * expert_params(m)
             + m["hidden_size"] * m["vocab_size"]) * weight_bytes
            + n_moe * router)


def kv_bytes_per_layer_token(m: Dict[str, Any], kv_bytes: float) -> float:
    return 2 * m["num_key_value_heads"] * m["head_dim"] * kv_bytes


def decode_bytes(m: Dict[str, Any], steps: int, context_tokens: int,
                 experts_touched: int, window_visible: int,
                 weight_bytes: float, kv_bytes: float) -> float:
    """Bytes ``steps`` decode steps must read: the fixed part once a step,
    ``experts_touched`` experts (summed over layers and steps), every live
    token (``context_tokens``, summed over steps and rows) in each full
    layer, and ``window_visible`` token-layers in the window layers."""
    n_full = sum(t == "full_attention" for t in m["layer_types"])
    return (steps * fixed_bytes_per_step(m, weight_bytes)
            + experts_touched * expert_params(m) * weight_bytes
            + (context_tokens * n_full + window_visible)
            * kv_bytes_per_layer_token(m, kv_bytes))


def _decode_work(cfg, *, programs, real, pad, chips, experts_touched,
                 window_visible):
    # one chip holds every layer whole (the configuration's deployment):
    # nothing divides by ``chips``
    return decode_bytes(cfg, programs, real, experts_touched, window_visible,
                        cfg["bytes"]["weight"], cfg["bytes"]["kv"])


# Found by ``readers/trace_roofline_routed.py`` through a metric file's
# ``shape``: ``shapes.py``'s signature plus the two counters that reader
# reads off the program, by keyword.
FUNCTIONS = {
    "decode_bytes": {"work": _decode_work, "peak": "hbm_bytes_per_s"},
}
