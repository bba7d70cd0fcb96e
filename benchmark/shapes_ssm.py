"""Operations and bytes of NVIDIA-Nemotron-3-Nano-30B-A3B's stage
(``nemotron_h``: blocks of ONE part each: Mamba-2 state-space mixers,
attention, routed two-matrix ``relu ** 2`` experts of which this chip holds
a share), from shapes and from what the program's counters say its
recurrent and routed blocks did.

Nothing here reads the program: the sizes follow from the published
configuration's keys and the deployment's weight and state types. Every
function prices what the chip MUST do and leaves the rest out, so every
share reads low, never high.

The state-space recurrence, for one token and head of ``P`` channels over
``N`` state lanes, whatever chunking implements it, MUST decay the state
(``P N`` multiplies), add ``dt x (outer) B`` (``2 P N``: the outer product's
multiply folds ``dt`` into ``x``) and read it against ``C`` (``2 P N``, of
which the decay's multiply can fold into the add): ``4 P N`` operations. The
chunk kernel does about 1.6 times that in float32 products of several MXU
passes each, so its share of the MXU's bf16 peak reads LOW by construction.

One decode step of one row in one mixer block MUST read and write the row's
state: ``2 * heads * P * N * 4`` bytes (4 MiB at 64 heads of 64 x 128); the
convolution's tail (37 KB a row and block, read and written) and the
projections' weights are left out.

The streamed expert product reads the TWO matrices of every HELD expert
that got an assignment. The program counts the experts touched over ALL the
published experts (``moe.experts_touched``: every holder routes alike) and
not the held ones among them; of ``published - held`` experts held elsewhere
at most that many can be among a layer step's touched, so ``touched -
(published - held) * layer_steps`` of them ARE held here, never fewer: the
bound is what is priced (at 128 rows nearly every expert is touched, and the
bound is within a few of the count).

One decode step reads, whatever it routed (``fixed_bytes_per_step``): every
mixer block's matrices, the attention block's, the shared experts', the
float32 routers and the head; then the held experts touched and the state
of every row stepped. The attention block's cached keys and values (1,024 B
a token in ONE block of nine), the tails, the embedding rows and the norms'
scales are left out.
"""

from __future__ import annotations

from typing import Any, Dict


def ssm_dims(m: Dict[str, Any]):
    return (m["mamba_num_heads"], m["mamba_head_dim"], m["ssm_state_size"],
            m["n_groups"])


def mixer_params(m: Dict[str, Any]) -> int:
    """One mixer block's matrices: the in and out projections."""
    heads, p, n, groups = ssm_dims(m)
    inner = heads * p
    return m["hidden_size"] * (2 * inner + 2 * groups * n + heads) + (
        inner * m["hidden_size"])


def attention_params(m: Dict[str, Any]) -> int:
    h, hd = m["hidden_size"], m["head_dim"]
    return 2 * h * hd * (m["num_attention_heads"]
                         + m["num_key_value_heads"])


def expert_bytes(m: Dict[str, Any], weight_bytes: float) -> float:
    """One routed expert's up and down matrices (no gate)."""
    return 2 * m["hidden_size"] * m["moe_intermediate_size"] * weight_bytes


def held_experts_touched(m: Dict[str, Any], experts_touched: int,
                         layer_steps: int) -> int:
    """The least number of HELD experts among ``experts_touched`` (counted
    over all the published experts, summed over ``layer_steps`` routed-block
    steps)."""
    elsewhere = m["published"]["n_routed_experts"] - m["n_routed_experts"]
    return max(0, int(experts_touched) - elsewhere * int(layer_steps))


def fixed_bytes_per_step(m: Dict[str, Any], weight_bytes: float) -> float:
    pattern = m["hybrid_override_pattern"]
    h = m["hidden_size"]
    shared = 2 * h * m["moe_shared_expert_intermediate_size"]
    router = h * m["published"]["n_routed_experts"] * 4.0
    return ((pattern.count("M") * mixer_params(m)
             + pattern.count("*") * attention_params(m)
             + pattern.count("E") * shared
             + h * m["vocab_size"]) * weight_bytes
            + pattern.count("E") * router)


def recurrence_flops(m: Dict[str, Any], layer_tokens: int) -> float:
    """``layer_tokens``: real tokens x mixer blocks (``ssm.prefill_tokens``)."""
    heads, p, n, _ = ssm_dims(m)
    return 4.0 * heads * p * n * int(layer_tokens)


def state_step_bytes(m: Dict[str, Any], layer_rows: int,
                     state_bytes: float) -> float:
    """``layer_rows``: live rows x mixer blocks (``ssm.rows_stepped``)."""
    heads, p, n, _ = ssm_dims(m)
    return 2.0 * heads * p * n * state_bytes * int(layer_rows)


def _recurrence_work(cfg, *, programs, counters):
    return recurrence_flops(cfg, counters["layer_tokens"])


def _state_work(cfg, *, programs, counters):
    return state_step_bytes(cfg, counters["layer_rows"],
                            cfg["bytes"]["state"])


def _streamed_work(cfg, *, programs, counters):
    return held_experts_touched(
        cfg, counters["experts_touched"], counters["layer_steps"]
    ) * expert_bytes(cfg, cfg["bytes"]["weight"])


def _decode_work(cfg, *, programs, counters):
    w = cfg["bytes"]["weight"]
    return (programs * fixed_bytes_per_step(cfg, w)
            + _streamed_work(cfg, programs=programs, counters=counters)
            + _state_work(cfg, programs=programs, counters=counters))


# Found by ``readers/trace_roofline_counted.py`` through a metric file's
# ``shape``: ``work(cfg, programs=..., counters={name: change over the
# traced window})``, the names the metric file's ``counters`` gives.
FUNCTIONS = {
    "ssm_recurrence_flops": {"work": _recurrence_work,
                             "peak": "bf16_flops_per_s"},
    "ssm_state_bytes": {"work": _state_work, "peak": "hbm_bytes_per_s"},
    "streamed_expert_bytes": {"work": _streamed_work,
                              "peak": "hbm_bytes_per_s"},
    "decode_bytes": {"work": _decode_work, "peak": "hbm_bytes_per_s"},
}
