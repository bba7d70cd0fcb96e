"""Bytes and operations of a latent-attention (MLA), routed decode step —
Kanana-2-30B-A3B (``deepseek_v3``) — from shapes and from what the program's
counters say routing and the latent kernel did.

Nothing here reads the program: the sizes follow from the published
configuration's keys and the deployment's weight and cache types.

The absorbed kernel (``mla_paged_decode``), for every cache row a query row
sees (the counter ``mla.tokens_visible``: live tokens, summed over rows,
latent layers and steps), MUST

- read the row once for all heads: ``kv_lora_rank + qk_rope_head_dim``
  values (576: the latent, and the one rotary key);
- multiply it with every head's absorbed query (``2 * heads * 576``
  operations) and add its latent into every head's sum (``2 * heads *
  512``).

The pool holds a row in 640 lanes (a declared pad to the TPU's 128); the
64 zeros are no work the algorithm needs, so the shares count 576 and read
low by the pad, never high. The kernel copies whole 16-token blocks, up to
15 tokens a row more than is counted: low again.

One decode step on one chip reads

- every layer's attention matrices once: q ``hidden x heads * 192``, kv_a
  ``hidden x 576``, kv_b ``512 x heads * 256``, o ``heads * 128 x hidden``;
- a dense layer's MLP (gate, up, down: ``hidden x intermediate_size``);
- an expert layer's shared MLP (``n_shared_experts`` experts wide) and its
  float32 router;
- the three matrices of every expert that got at least one assignment, and
  of no other: ``moe.experts_touched`` (summed over expert layers and steps);
- the output head, ``hidden x vocab_size`` (the embedding is a gather of a
  few rows and is left out, as are the norms' scales);
- the latent rows its queries see, in every layer: ``mla.tokens_visible``.
"""

from __future__ import annotations

from typing import Any, Dict


def latent_values(m: Dict[str, Any]) -> int:
    return m["kv_lora_rank"] + m["qk_rope_head_dim"]


def attention_params(m: Dict[str, Any]) -> int:
    h, heads = m["hidden_size"], m["num_attention_heads"]
    return (h * heads * (m["qk_nope_head_dim"] + m["qk_rope_head_dim"])
            + h * latent_values(m)
            + m["kv_lora_rank"] * heads
            * (m["qk_nope_head_dim"] + m["v_head_dim"])
            + heads * m["v_head_dim"] * h)


def expert_params(m: Dict[str, Any]) -> int:
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def fixed_bytes_per_step(m: Dict[str, Any], weight_bytes: float) -> float:
    """What a step reads whatever it routed and however long its rows."""
    n_dense = m["first_k_dense_replace"]
    n_moe = m["num_hidden_layers"] - n_dense
    dense = 3 * m["hidden_size"] * m["intermediate_size"]
    router = m["hidden_size"] * m["n_routed_experts"] * 4.0
    return ((m["num_hidden_layers"] * attention_params(m)
             + n_dense * dense
             + n_moe * m["n_shared_experts"] * expert_params(m)
             + m["hidden_size"] * m["vocab_size"]) * weight_bytes
            + n_moe * router)


def latent_bytes(m: Dict[str, Any], tokens_visible: int,
                 kv_bytes: float) -> float:
    """Bytes the absorbed kernel must read for ``tokens_visible`` rows."""
    return tokens_visible * latent_values(m) * kv_bytes


def latent_flops(m: Dict[str, Any], tokens_visible: int) -> float:
    """Operations the absorbed kernel must do for ``tokens_visible`` rows:
    scores over 576 lanes and the sum of latents over 512, every head."""
    return (2.0 * m["num_attention_heads"] * tokens_visible
            * (latent_values(m) + m["kv_lora_rank"]))


def decode_bytes(m: Dict[str, Any], steps: int, experts_touched: int,
                 tokens_visible: int, weight_bytes: float,
                 kv_bytes: float) -> float:
    return (steps * fixed_bytes_per_step(m, weight_bytes)
            + experts_touched * expert_params(m) * weight_bytes
            + latent_bytes(m, tokens_visible, kv_bytes))


def _kernel_bytes(cfg, *, programs, counters):
    return latent_bytes(cfg, counters["tokens_visible"], cfg["bytes"]["kv"])


def _kernel_flops(cfg, *, programs, counters):
    return latent_flops(cfg, counters["tokens_visible"])


def _decode_work(cfg, *, programs, counters):
    # one chip holds every layer whole (the configuration's deployment):
    # nothing divides by ``chips``
    return decode_bytes(cfg, programs, counters["experts_touched"],
                        counters["tokens_visible"], cfg["bytes"]["weight"],
                        cfg["bytes"]["kv"])


# Found by ``readers/trace_roofline_counted.py`` through a metric file's
# ``shape``: ``work(cfg, programs=..., counters={name: change over the
# traced window})``, the names the metric file's ``counters`` gives.
FUNCTIONS = {
    "mla_kernel_bytes": {"work": _kernel_bytes, "peak": "hbm_bytes_per_s"},
    "mla_kernel_flops": {"work": _kernel_flops, "peak": "bf16_flops_per_s"},
    "decode_bytes": {"work": _decode_work, "peak": "hbm_bytes_per_s"},
}
