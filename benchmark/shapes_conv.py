"""Bytes of LFM2-24B-A2B's stage (``lfm2_moe``: gated short convolutions on
slot state, attention layers of 64-wide heads, routed three-matrix experts
ALL held here, a head tied to the embedding), from shapes and from what the
program's counters say its recurrent and routed layers did.

Nothing here reads the program: the sizes follow from the published
configuration's keys and the deployment's weight and state types. Every
function prices what the chip MUST do and leaves the rest out, so every
share reads low, never high.

The streamed expert product reads the THREE matrices of every expert that
got an assignment, and every expert the router scores is held here: the
program's count (``moe.experts_touched``, over all published experts) IS
the count of held experts read, with no bound in between.

One decode step of one row in one conv layer MUST read and write the row's
tail: ``2 * (conv_L_cache - 1) * hidden * state bytes`` (16 KiB at 2 x 2048
bf16 values).

One decode step reads, whatever it routed (``fixed_bytes_per_step``): every
conv mixer's two matrices, the attention layers' four, the dense MLP, the
float32 routers and the embedding as the tied head; then the experts touched
and the tail of every row stepped. The attention layers' cached keys and
values (8,192 B a token held in TWO layers of nine), the convolutions' taps,
the embedding rows looked up and the norms' scales are left out.
"""

from __future__ import annotations

from typing import Any, Dict


def conv_mixer_params(m: Dict[str, Any]) -> int:
    """One conv mixer's matrices: in (hidden -> 3 hidden) and out."""
    return 4 * m["hidden_size"] * m["hidden_size"]


def attention_params(m: Dict[str, Any]) -> int:
    h = m["hidden_size"]
    hd = h // m["num_attention_heads"]
    return 2 * h * hd * (m["num_attention_heads"]
                         + m["num_key_value_heads"])


def expert_bytes(m: Dict[str, Any], weight_bytes: float) -> float:
    """One routed expert's gate, up and down matrices."""
    return 3 * m["hidden_size"] * m["moe_intermediate_size"] * weight_bytes


def fixed_bytes_per_step(m: Dict[str, Any], weight_bytes: float) -> float:
    kinds = m["layer_types"]
    h = m["hidden_size"]
    dense = 3 * h * m["intermediate_size"] * m["num_dense_layers"]
    routed = m["num_hidden_layers"] - m["num_dense_layers"]
    return ((kinds.count("conv") * conv_mixer_params(m)
             + kinds.count("full_attention") * attention_params(m)
             + dense + h * m["vocab_size"]) * weight_bytes
            + routed * h * m["num_experts"] * 4.0)


def tail_step_bytes(m: Dict[str, Any], layer_rows: int,
                    state_bytes: float) -> float:
    """``layer_rows``: live rows x conv layers (``conv.rows_stepped``)."""
    return (2.0 * (m["conv_L_cache"] - 1) * m["hidden_size"] * state_bytes
            * int(layer_rows))


def _streamed_work(cfg, *, programs, counters):
    return int(counters["experts_touched"]) * expert_bytes(
        cfg, cfg["bytes"]["weight"])


def _decode_work(cfg, *, programs, counters):
    return (programs * fixed_bytes_per_step(cfg, cfg["bytes"]["weight"])
            + _streamed_work(cfg, programs=programs, counters=counters)
            + tail_step_bytes(cfg, counters["layer_rows"],
                              cfg["bytes"]["state"]))


# Found by ``readers/trace_roofline_counted.py`` through a metric file's
# ``shape``: ``work(cfg, programs=..., counters={name: change over the
# traced window})``, the names the metric file's ``counters`` gives.
FUNCTIONS = {
    "streamed_expert_bytes": {"work": _streamed_work,
                              "peak": "hbm_bytes_per_s"},
    "decode_bytes": {"work": _decode_work, "peak": "hbm_bytes_per_s"},
}
