"""Speculative decoding: prompt-lookup drafting + acceptance bookkeeping.

The engine commits exactly one token per decode dispatch, so decode
throughput is pinned to one paged-attention call per token. Speculative
decoding breaks that: a *drafter* proposes up to ``num_speculative_tokens``
continuations, one pre-compiled verify executable (``runner.make_verify``)
scores all of them plus the bonus position in a single paged-attention
call, and the engine commits the longest prefix the model itself agrees
with. Worst case costs one verify step per committed token (same dispatch
count as vanilla decode); best case commits ``k + 1`` tokens per step.

The drafter here is vLLM's ``speculative_model: "[ngram]"`` — pure prompt
lookup (match the tail n-gram of prompt+generated against earlier context,
propose what followed last time), no draft model, no extra weights, runs on
the host. It shines on the workloads the reference stack actually serves:
summarization/extraction-style prompts where the output quotes the input,
and the self-repetition every greedy decode drifts into.

Async-decode interplay (``SHAI_ASYNC_DECODE``, engine.resident): drafting
reads each slot's ``pending_token``, so a speculative step is a pipeline
*event* — the engine flushes (retires) any in-flight lookahead dispatch
before ``_spec_step`` runs, ``_spec_step`` reads back first tokens an
admission of the same step left on the device, and the verify dispatch
shares the
device-resident batch view (tables/active/sampling knobs) with decode
instead of re-marshaling it host->device per step.

Acceptance is exact: at temperature 0 a draft survives iff it equals the
model's argmax at its position; at temperature > 0 the standard
delta-proposal rejection rule applies — accept draft ``d`` with probability
``p_target(d)`` (the n-gram proposal is a point mass, so ``q(d) = 1``), and
on rejection resample from the target distribution with ``d`` masked out
(``oex`` below, sampled in-graph). Either way every committed token is
distributed exactly as vanilla decode; drafts only ever change speed.
"""

from __future__ import annotations

import dataclasses
from typing import List, Sequence

import numpy as np


@dataclasses.dataclass
class SpecStats:
    """Cumulative speculative-decoding counters (one per engine).

    ``acceptance_rate`` is accepted/drafted — the knob the cost model keys
    on (perf.model.spec_decode_model); ``tokens_per_verify`` is the realized
    commit rate per verify dispatch (1.0 == vanilla decode pace).
    """

    drafted: int = 0        # draft tokens submitted to verification
    accepted: int = 0       # draft tokens that survived verification
    committed: int = 0      # tokens committed via verify steps (incl. bonus)
    verify_steps: int = 0   # multi-token verify dispatches
    fallback_steps: int = 0  # steps that fell back to vanilla decode

    def record_verify(self, n_drafted: int, n_accepted: int,
                      n_processed: int) -> None:
        """One sequence's verification outcome: drafted/accepted count the
        VERIFICATION result (drafter-quality signal); ``n_processed`` the
        tokens the commit walk actually reached (an EOS/length finish
        mid-run must not inflate tokens_per_verify)."""
        self.drafted += n_drafted
        self.accepted += n_accepted
        self.committed += n_processed

    @property
    def acceptance_rate(self) -> float:
        return self.accepted / self.drafted if self.drafted else 0.0

    @property
    def tokens_per_verify(self) -> float:
        return self.committed / self.verify_steps if self.verify_steps else 0.0

    def as_dict(self) -> dict:
        return {
            "spec_drafted": self.drafted,
            "spec_accepted": self.accepted,
            "spec_committed": self.committed,
            "spec_verify_steps": self.verify_steps,
            "spec_fallback_steps": self.fallback_steps,
            "spec_acceptance_rate": round(self.acceptance_rate, 4),
            "spec_tokens_per_verify": round(self.tokens_per_verify, 4),
        }


class PromptLookupDrafter:
    """Model-free n-gram drafter (vLLM's ``[ngram]`` speculative model).

    ``draft(context)`` matches the last ``n`` tokens of the context
    (``n`` from ``lookup_max`` down to ``lookup_min``) against every earlier
    position, most recent occurrence first, and proposes the up-to-``k``
    tokens that followed that occurrence. No weights, no device traffic —
    the proposal is a pure host-side list scan, cheap next to a decode
    dispatch.
    """

    def __init__(self, k: int, lookup_max: int = 4, lookup_min: int = 1):
        if k < 1:
            raise ValueError("num_speculative_tokens must be >= 1")
        if not 1 <= lookup_min <= lookup_max:
            raise ValueError(
                f"need 1 <= ngram_prompt_lookup_min ({lookup_min}) <= "
                f"ngram_prompt_lookup_max ({lookup_max})")
        self.k = k
        self.lookup_max = lookup_max
        self.lookup_min = lookup_min

    def draft(self, context: Sequence[int]) -> List[int]:
        """Propose up to ``k`` continuation tokens for ``context``; ``[]``
        when the history is too short or no earlier n-gram matches.

        The scan is numpy-vectorized (sliding-window compare, C speed):
        this runs per running slot per decode step, and its worst case —
        no match anywhere, vanilla fallback — is exactly the case that
        must stay cheap next to a decode dispatch.
        """
        ctx = list(context)
        L = len(ctx)
        if L < self.lookup_min + 1:
            return []
        arr = np.asarray(ctx, dtype=np.int64)
        # longest n-grams first: a longer match is a stronger predictor
        for n in range(min(self.lookup_max, L - 1), self.lookup_min - 1, -1):
            tail = arr[L - n:]
            # candidate starts 0..L-n-1: the match must END strictly before
            # the final position so the continuation is non-empty
            windows = np.lib.stride_tricks.sliding_window_view(
                arr[:L - 1], n)
            hits = np.flatnonzero((windows == tail).all(axis=1))
            if hits.size:
                start = int(hits[-1])  # most recent earlier occurrence
                return ctx[start + n:start + n + self.k]
        return []


def accept_drafts(draft: Sequence[int], o, oex, accept_p,
                  temperature: float, uniforms) -> tuple:
    """Host-side acceptance walk for ONE sequence.

    ``o[i]`` is the model's sample at draft position ``i`` (full target
    distribution), ``oex[i]`` a sample with ``draft[i]`` masked out,
    ``accept_p[i]`` the target probability of ``draft[i]`` under the actual
    sampling distribution. ``uniforms`` supplies the rejection draws
    (ignored at temperature 0, where acceptance is exact argmax match).

    Returns ``(n_accepted, next_token)`` — the committed tokens are
    ``pending + draft[:n_accepted]`` and ``next_token`` becomes the new
    pending token (the bonus sample when everything was accepted).
    """
    nd = len(draft)
    for i in range(nd):
        if temperature <= 0.0:
            ok = int(draft[i]) == int(o[i])
        else:
            ok = float(uniforms[i]) < float(accept_p[i])
        if not ok:
            # rejection-resample: at temperature 0 the argmax IS the
            # corrected sample; otherwise sample from p with draft[i] out
            nxt = int(o[i]) if temperature <= 0.0 else int(oex[i])
            return i, nxt
    return nd, int(o[nd])
