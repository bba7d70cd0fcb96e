"""Operations and bytes of Kimi-Linear-48B-A3B's stage (``kimi_linear``:
KDA linear attention three layers of four, MLA without positional embedding
the fourth, routed experts of which this chip holds a share), from shapes
and from what the program's counters say its recurrent layers did.

Nothing here reads the program: the sizes follow from the published
configuration's keys.

The KDA recurrence, for one token and head of ``d`` channels, whatever
chunking implements it, MUST decay the state (``d * d`` multiplies), read it
against ``k`` (``2 d * d``), add the rank-one correction (``2 d * d``) and
read it against ``q`` (``2 d * d``, of which the decay's multiply can fold
into the correction's): ``6 d * d`` operations. The chunk kernel does about
2.7 times that (a triangular solve a chunk, the decay's bookkeeping) in
float32 products of several MXU passes each, so its share of the MXU's bf16
peak reads LOW by construction and never high; it is there to be watched,
not to be near 100.

One decode step of one row in one KDA layer MUST read and write the row's
state: ``2 * heads * d * d * 4`` bytes (2 MiB at 32 heads of 128); the
convolution's tail (74 KB a row and layer, read and written) and the
projections' weights are left out, so this reads low too.

A prefill or continuation program's matrix products a token slot (padding
is multiplied like a real token): every layer's attention matrices (a KDA
layer's q, k, v, the two low-rank pairs, beta and o; the MLA layer's q,
kv_a, kv_b over the chunk's own rows and o), the dense layer's MLP, and in
an expert layer the router, the shared expert and ``num_experts_per_token *
held / published`` routed experts: the EXPECTATION of a token's assignments
to the experts held here (the seeded router's choices are uniform over the
published experts; a pad slot is routed nowhere, so this reads a pad
fraction high in the routed part and the program's own pad counter says by
how much). Left out: attention's score and value products (they grow with
the prefix: 0.2 GFLOP a token at 8k against 0.79 here), the MLA layer's
up-projection of a continuation's gathered prefix, the KDA scan itself, the
output head. The share therefore reads low, never high.
"""

from __future__ import annotations

from typing import Any, Dict


def kda_dims(m: Dict[str, Any]):
    lin = m["linear_attn_config"]
    return lin["num_heads"], lin["head_dim"]


def kda_attention_params(m: Dict[str, Any]) -> int:
    h = m["hidden_size"]
    heads, d = kda_dims(m)
    wide = heads * d
    return (3 * h * wide            # q, k, v
            + 2 * (h * d + d * wide)  # the decay's and the gate's low rank
            + h * heads             # beta
            + wide * h)             # o


def mla_attention_params(m: Dict[str, Any]) -> int:
    h, heads = m["hidden_size"], m["num_attention_heads"]
    return (h * heads * (m["qk_nope_head_dim"] + m["qk_rope_head_dim"])
            + h * (m["kv_lora_rank"] + m["qk_rope_head_dim"])
            + m["kv_lora_rank"] * heads
            * (m["qk_nope_head_dim"] + m["v_head_dim"])
            + heads * m["v_head_dim"] * h)


def expert_params(m: Dict[str, Any]) -> int:
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def held_assignments_per_token(m: Dict[str, Any]) -> float:
    """A token's expected assignments to the experts held here."""
    return (m["num_experts_per_token"] * m["num_experts"]
            / m["published"]["num_experts"])


def product_params_per_token(m: Dict[str, Any]) -> float:
    """Matrix parameters one token slot is multiplied with on this stage."""
    lin = m["linear_attn_config"]
    n_dense = m["first_k_dense_replace"]
    n_moe = m["num_hidden_layers"] - n_dense
    return (len(lin["kda_layers"]) * kda_attention_params(m)
            + len(lin["full_attn_layers"]) * mla_attention_params(m)
            + n_dense * 3 * m["hidden_size"] * m["intermediate_size"]
            + n_moe * (m["hidden_size"] * m["published"]["num_experts"]
                       + (m["num_shared_experts"]
                          + held_assignments_per_token(m))
                       * expert_params(m)))


def prefill_flops(m: Dict[str, Any], tokens_walked: int) -> float:
    return 2.0 * product_params_per_token(m) * int(tokens_walked)


def recurrence_flops(m: Dict[str, Any], layer_tokens: int) -> float:
    """``layer_tokens``: real tokens x KDA layers (``kda.prefill_tokens``)."""
    heads, d = kda_dims(m)
    return 6.0 * heads * d * d * int(layer_tokens)


def state_step_bytes(m: Dict[str, Any], layer_rows: int) -> float:
    """``layer_rows``: live rows x KDA layers (``kda.rows_stepped``)."""
    heads, d = kda_dims(m)
    return 2.0 * heads * d * d * 4 * int(layer_rows)


def _prefill_work(cfg, *, programs, real, pad, chips):
    return prefill_flops(cfg, real + pad) / chips


def _recurrence_work(cfg, *, programs, counters):
    return recurrence_flops(cfg, counters["layer_tokens"])


def _state_work(cfg, *, programs, counters):
    return state_step_bytes(cfg, counters["layer_rows"])


# ``prefill_flops`` is found by ``readers/trace_roofline.py`` (``work(cfg,
# programs=, real=, pad=, chips=)``), the other two by
# ``readers/trace_roofline_counted.py`` (``work(cfg, programs=, counters=)``).
FUNCTIONS = {
    "prefill_flops": {"work": _prefill_work, "peak": "bf16_flops_per_s"},
    "kda_recurrence_flops": {"work": _recurrence_work,
                             "peak": "bf16_flops_per_s"},
    "kda_state_bytes": {"work": _state_work, "peak": "hbm_bytes_per_s"},
}
