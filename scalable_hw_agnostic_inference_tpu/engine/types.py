"""Engine request/response dataclasses (split from engine.py, r4 weak #5)."""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional

import numpy as np

from .config import EngineConfig


@dataclasses.dataclass
class SamplingParams:
    temperature: float = 1.0
    top_k: int = 0
    top_p: float = 1.0
    max_new_tokens: int = 128
    eos_id: int = -1            # -1: never stop on a token
    # report per-token logprobs with this many top alternatives (0 = off,
    # capped at runner.K_LOGPROBS — the OpenAI `logprobs` field)
    logprobs: int = 0

    def clamp(self, ecfg: EngineConfig) -> "SamplingParams":
        from .runner import K_LOGPROBS

        # global_topk == 0 means "cap disabled": leave a user-set top_k alone
        if self.top_k and ecfg.global_topk:
            top_k = min(self.top_k, ecfg.global_topk)
        else:
            top_k = self.top_k or ecfg.global_topk
        return dataclasses.replace(
            self,
            max_new_tokens=min(self.max_new_tokens, ecfg.max_new_tokens),
            top_k=top_k,
            logprobs=min(max(int(self.logprobs), 0), K_LOGPROBS),
        )


@dataclasses.dataclass
class Request:
    req_id: int
    prompt_ids: List[int]
    params: SamplingParams
    # soft-prefix embeddings [P, dim] (vision tokens — multimodal requests,
    # reference ``vllm_model_api_m.py:42-66``); occupy the first P positions
    prefix: Optional[np.ndarray] = None
    # mllama cross-attention states [Lv, dim] (projected vision features);
    # attended by the gated cross layers, never part of the token sequence.
    # cross_len: valid rows (multi-tile images fill a tile-count-dependent
    # prefix of the static buffer; 0/None = all rows valid)
    cross_states: Optional[np.ndarray] = None
    cross_len: int = 0
    # tokens generated before a recompute-preemption (they re-enter the
    # cache as prompt suffix but remain part of the client-visible output)
    already_generated: List[int] = dataclasses.field(default_factory=list)
    orig_n_prompt: int = -1
    # streaming: called (engine-loop thread, must be cheap — a queue put)
    # exactly once per token that will appear in Finished.token_ids, in order
    on_token: Optional[Any] = None
    # absolute monotonic deadline (0 = none): the engine expires the
    # request at step granularity wherever it is — queued, mid-prefill, or
    # decoding — finishing it with stop reason "timeout" so its KV blocks
    # and slot free instead of decoding past a budget nobody is waiting on.
    # Survives preemption (the budget is the request's, not the segment's).
    deadline_at: float = 0.0
    # time the engine took the request in (``add_request``, on the loop
    # thread, between steps): where queue wait starts; survives preemption
    t_submit: float = 0.0
    # time the caller submitted it (``EngineLoop.submit``, on the caller's
    # thread): where TTFT starts, and with t_submit the intake wait. Equal
    # to t_submit for a direct ``add_request``. Survives preemption.
    t_enqueue: float = 0.0
    # first-admission time (monotonic): queue-wait accounting. Survives
    # preemption like t_submit — a resume is not a second queue wait.
    t_admit: float = 0.0
    # true first-token time (monotonic): the prefill/decode boundary in
    # Finished.timing. Survives preemption — a resume's re-prefill belongs
    # to the decode phase it interrupted, not to prefill (the slot-level
    # t_first, which resets per segment, keeps TPOT per-segment-accurate)
    t_first: float = 0.0
    # logprob entries for tokens emitted before a preemption (mirrors
    # already_generated)
    already_lp: List = dataclasses.field(default_factory=list)
    # multi-tenant QoS (resilience.qos): priority class (0=high, 1=normal,
    # 2=low — LOWER is more important) drives the weighted-fair dequeue
    # and lowest-priority-first preemption; tenant attributes the request
    # in per-tenant budgets/metrics. Both survive preemption — the
    # re-queued remainder is the same tenant's same-priority work.
    priority: int = 1
    tenant: str = ""
    # request reliability (resilience.idempotency): the request's
    # idempotency key as minted/forwarded by cova — attribution only at
    # this layer (the serving layer owns the dedup cache), but it rides
    # the Request so the migration manifest can carry it and a resumed
    # duplicate dedupes on the peer through the SAME key. Survives
    # preemption. "" = keyless (replay protection off for this request).
    idem_key: str = ""
    # KV fabric (kvnet.directory): holder URLs the router believes hold
    # this prompt's leading KV run — a pushed-down directory slice. A
    # HINT only: the peer-probe rung tries them under its wall budget
    # and recomputes on any miss; empty = resolve via the pod-local
    # directory (or skip the probe entirely — the cold-fleet fast path)
    kv_holders: List[str] = dataclasses.field(default_factory=list)
    # distributed tracing (obs.trace): the request's W3C traceparent,
    # captured on the serving lane at submit time. The engine loop thread
    # has NO request contextvars, so cross-pod work it initiates itself
    # (the fabric-probe pull rung) forwards THIS header to keep one
    # request one trace. "" = untraced (SHAI_TRACE=0 or no active trace).
    traceparent: str = ""
    # engine-side trace attribution: sub-phase instants/durations the span
    # tree can't see from outside (fabric probe, kv restore, recompute
    # fallback, per-request pipeline flushes, migration cut), merged into
    # Finished.timing by _timing_of and grafted as spans/attrs by the
    # serving layer (Trace.add_phase_spans). Engine-loop-thread-only.
    obs_extra: Dict[str, float] = dataclasses.field(default_factory=dict)
    # n>1 sampling fan-out (SHAI_KV_COW): siblings of one OpenAI request
    # share a parent id (-1 = not a fan-out member). The engine admits a
    # fully-queued group as ONE prefill with copy-on-write KV forks, and
    # the loop cancels/expires the group as a unit. Deliberately NOT
    # carried across preemption re-queues — a resumed sibling has its own
    # generated suffix and must re-admit independently.
    parent_rid: int = -1

    def __post_init__(self):
        if self.orig_n_prompt < 0:
            self.orig_n_prompt = len(self.prompt_ids)

    @property
    def prefix_len(self) -> int:
        return 0 if self.prefix is None else int(self.prefix.shape[0])


@dataclasses.dataclass
class Finished:
    req_id: int
    token_ids: List[int]        # generated tokens, EOS excluded
    n_prompt: int
    # "eos" | "length" | "rejected" | "cancelled" | "timeout" | "migrated"
    stop_reason: str
    # one entry per token_ids element when the request asked for logprobs:
    # {"token", "logprob", "top_ids", "top_logprobs"}
    logprobs: Optional[List[Dict[str, Any]]] = None
    # per-phase timeline (obs): monotonic stamps t_enqueue/t_submit/t_admit/
    # t_first/t_done plus derived intake_s/queue_s/prefill_s/decode_s/total_s — the serving
    # layer turns these into request-trace spans and bench.py aggregates
    # them into per-phase report fields
    timing: Optional[Dict[str, float]] = None
    # live migration (kvnet.migrate): stop_reason "migrated" carries the
    # sequence's resumable manifest — prompt+generated token ids, remaining
    # sampling budget, QoS identity, deadline remainder, and the chain
    # hashes of the KV run banked in the host tier. The serving layer ships
    # it to a peer and the request CONTINUES there; a "migrated" Finished
    # is a handoff, not a terminal outcome.
    migration: Optional[Dict[str, Any]] = None


@dataclasses.dataclass
class _Running:
    req: Request
    slot: int
    generated: List[int]
    pending_token: int          # sampled but not yet written to the cache
    # chunked prefill: prompt position of the next chunk, or None when the
    # prompt is fully encoded (mid-prefill slots don't join the decode batch)
    prefill_cursor: Optional[int] = None
    t_first: float = 0.0        # first-token time (TPOT accounting)
    # logprob entries in sample order (== append order); only populated
    # when the request asked for logprobs
    lps: List = dataclasses.field(default_factory=list)


