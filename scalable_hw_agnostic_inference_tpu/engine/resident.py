"""Device-resident decode batch state + the in-flight lookahead record.

A decode step's inputs reach the device by ONE road, ``LLMEngine._put``
(placed where the step programs were compiled to take them: replicated
over the mesh when the engine holds shardings, the default device when it
holds none; ``_put_step`` is the same put, counted as
``decode_input_uploads``), and only when they changed:

* :class:`ResidentBatch` keeps the composition-dependent arrays
  (``tables/active/temp/topk/topp`` plus the mllama slot tail) as
  persistent DEVICE arrays, keyed by a composition signature. All of them
  go up when the signature changes (join/finish/preempt). Between two
  such events only ``tables`` can go stale, and it is tracked BY ROW: the
  mirror keeps a host ``[Bb, M]`` copy and each row's
  ``SeqAllocation.version``; a step rewrites the rows whose stamp moved
  and puts the table once if any did — O(rows) a step, not O(blocks).
  The speculative verify path shares this cache: same composition, same
  device arrays, whichever executable dispatches next.

  The mirror is COLUMN-AGNOSTIC: whatever dict ``engine.
  _marshal_running`` returns is uploaded wholesale, so per-row metadata
  columns ride along without touching the refresh mechanics.

* :class:`InflightStep` records one dispatched-but-not-retired decode
  step: the device-side sampled tokens (which feed straight back as the
  next dispatch's ``tokens`` input — the host never sees them until one
  step later), the donated next-positions array, the next step's rng
  fold index (a device counter the program advances), and the logprob
  outputs.

* :class:`FirstTokens` records one admission's sampled first tokens, left
  on the device behind the prefill (or final continuation) program that
  made them: the rows are seated at once with an unresolved token, the
  next decode dispatch writes the record's tokens into its token input
  ON the device (:func:`feed_first_tokens`) and goes out, and the engine
  reads the record behind that dispatch, where the HOST needs the token
  (the commit that streams it), not where it is made.

  Retiring the lookahead and resolving the first tokens are the ONLY two
  places the async loop blocks on the device.

Layering: pure data + marshaling helpers; the scheduling policy (when to
flush, when to reuse) lives in ``engine.engine``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import numpy as np


def composition_sig(running, Bb: int) -> Tuple:
    """Identity of the compacted batch view: which request sits in which
    batch row (and slot), at which executable batch bucket. Sampling knobs
    and the cross-attention tail are per-request constants, so the
    ``req_id`` entries cover them; anything this tuple does not capture —
    block-table growth/reassignment — is tracked separately (``blocks``)."""
    return (tuple((s.req.req_id, s.slot) for s in running), Bb)


@dataclasses.dataclass
class InflightStep:
    """One dispatched decode step awaiting retirement (host readback)."""

    sig: Tuple
    running: List[Any]                # _Running snapshot, batch-row order
    nxt: Any                          # device [Bb] sampled tokens (feedback)
    pos_next: Optional[Any]           # device [Bb] pos+1; None once donated
    fold_next: Any                    # device int32: the NEXT step's rng fold
    step: int                         # the engine step that dispatched it
    top_ids: Any
    top_lp: Any
    tok_lp: Any
    want_lp: bool
    t_dispatch: float                 # monotonic enqueue stamp (gap metric)
    # a routed model's step: [Bb + 2] int32, the sampled tokens with the
    # routing counts behind them — what the host reads INSTEAD of ``nxt``
    fetch: Optional[Any] = None

    def device_bytes(self) -> int:
        """Bytes the un-retired step's outputs pin on device (HBM ledger)."""
        # shai-lint: allow(host-sync) .nbytes is host shape metadata
        return sum(int(getattr(a, "nbytes", 0) or 0)
                   for a in (self.nxt, self.pos_next, self.top_ids,
                             self.top_lp, self.tok_lp))


@dataclasses.dataclass
class FirstTokens:
    """One admission's first tokens, sampled and not yet read back."""

    rows: List[Tuple[int, Any]]       # (row of ``toks``, the seated _Running)
    toks: Any                         # device [K] sampled tokens
    logits: Optional[Any]             # kept only where a row wants logprobs


def feed_first_tokens(tokens, dst, toks):
    """A decode step's token input with one record's sampled tokens
    written into their batch rows, traced under ``jax.jit``: ``dst[i]`` is
    the batch row of ``toks[i]``; a dummy row of the sampler's points past
    the batch and is dropped."""
    return tokens.at[dst].set(toks, mode="drop")


class ResidentBatch:
    """Composition-keyed device mirror of the decode batch arrays."""

    def __init__(self) -> None:
        self.sig: Optional[Tuple] = None
        self.arrays: Dict[str, Any] = {}
        #: host copy of ``arrays["tables"]`` and, per batch row, the
        #: ``SeqAllocation.version`` that row was written from
        self.tables: Optional[np.ndarray] = None
        self.versions: List[int] = []
        #: cumulative: table rows rewritten by the by-row path
        self.rows_rewritten = 0

    def invalidate(self) -> None:
        self.sig = None
        self.arrays = {}
        self.tables = None
        self.versions = []

    def device_bytes(self) -> int:
        """Bytes the resident mirror holds on device (HBM ledger feed)."""
        # shai-lint: allow(host-sync) .nbytes is shape metadata (host int)
        return sum(int(getattr(a, "nbytes", 0)) for a in self.arrays.values())

    def refresh(self, engine, running, Bb: int) -> Dict[str, Any]:
        """Device arrays for ``running`` compacted into ``Bb`` rows.

        Composition unchanged: reuse every resident array; rewrite the
        table rows whose allocation's ``version`` moved since the row was
        written, and put the table once if any did. Staleness is keyed on
        the block IDENTITIES, not counts: the allocator's free list is
        LIFO, so a shrink-then-regrow cycle (speculative rollback) can
        hand two slots each other's freed blocks with every per-row count
        unchanged — the stamp moves on every mutation of a block list, so
        both rows are rewritten. Composition changed: one full host
        marshal (the engine's lock-step ``_marshal_running``) uploaded
        wholesale.
        """
        sig = composition_sig(running, Bb)
        seqs = [engine.cache.seq(s.req.req_id) for s in running]
        if sig != self.sig:
            host = engine._marshal_running(running, Bb)
            self.arrays = engine._put_step(host)   # ONE transfer
            self.tables = host["tables"].copy()
            self.versions = [a.version for a in seqs]
            self.sig = sig
            return self.arrays
        done = self.rows_rewritten
        for i, a in enumerate(seqs):
            if a.version != self.versions[i]:
                self.tables[i] = a.table(self.tables.shape[1])
                self.versions[i] = a.version
                self.rows_rewritten += 1
        if self.rows_rewritten != done:
            # a COPY goes up: the transfer may read the host buffer after
            # the put returns, and the next step writes into the mirror
            self.arrays["tables"] = engine._put_step(self.tables.copy())
        return self.arrays
