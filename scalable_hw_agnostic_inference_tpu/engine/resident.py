"""Device-resident decode batch state + the in-flight lookahead record.

The lock-step decode loop re-marshals the full batch view host->device on
EVERY step — seven ``jnp.asarray`` uploads for arrays that change at most
when the batch composition changes — then blocks on ``np.asarray(nxt)``
before doing its host bookkeeping, stacking a fixed serial host gap onto
every HBM-bound decode step. The async pipeline (``SHAI_ASYNC_DECODE``)
removes both halves:

* :class:`ResidentBatch` keeps the composition-dependent arrays
  (``tables/active/temp/topk/topp`` plus the mllama slot tail) as
  persistent DEVICE arrays, keyed by a composition signature. They are
  re-uploaded only when the signature changes (join/finish/preempt) —
  block-table growth alone refreshes just the ``tables`` upload. The
  speculative verify path shares this cache: same composition, same
  device arrays, whichever executable dispatches next.

  The mirror is COLUMN-AGNOSTIC: whatever dict ``engine.
  _marshal_running`` returns is uploaded wholesale, so per-row metadata
  columns ride along without touching the refresh mechanics. The fused
  mixed-phase step (``SHAI_FUSED_STEP``) adds two: ``starts`` (each
  row's decode start — its prompt boundary in cache tokens, constant
  per decode segment by CONTRACT, which is what keeps the tables-only
  refresh path truthful) and ``phase`` (int8, 0 = decode for every
  resident row; the fused dispatch composes its chunk-window rows
  itself — a nonzero phase never appears in resident state).

* :class:`InflightStep` records one dispatched-but-not-retired decode
  step: the device-side sampled tokens (which feed straight back as the
  next dispatch's ``tokens`` input — the host never sees them until one
  step later), the donated next-positions array, and the logprob outputs.
  Retiring the record is the ONLY place the host blocks on the device.

Layering: pure data + marshaling helpers; the scheduling policy (when to
flush, when to reuse) lives in ``engine.engine``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax.numpy as jnp
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


class ResidentBatch:
    """Composition-keyed device mirror of the decode batch arrays."""

    def __init__(self) -> None:
        self.sig: Optional[Tuple] = None
        self.arrays: Dict[str, Any] = {}
        self.blocks: Tuple[Tuple[int, ...], ...] = ()

    def invalidate(self) -> None:
        self.sig = None
        self.arrays = {}
        self.blocks = ()

    def device_bytes(self) -> int:
        """Bytes the resident mirror holds on device (HBM ledger feed)."""
        # shai-lint: allow(host-sync) .nbytes is shape metadata (host int)
        return sum(int(getattr(a, "nbytes", 0)) for a in self.arrays.values())

    def refresh(self, engine, running, Bb: int) -> Dict[str, Any]:
        """Device arrays for ``running`` compacted into ``Bb`` rows.

        Composition unchanged: reuse every resident array, re-uploading
        only ``tables`` when some row's block LIST changed since the last
        marshal. Staleness is keyed on the block IDENTITIES, not counts:
        the allocator's free list is LIFO, so a shrink-then-regrow cycle
        (speculative rollback) can hand two slots each other's freed
        blocks with every per-row count unchanged — a count key would
        reuse tables that now point rows at the wrong physical blocks.
        Composition changed: one full host marshal (the engine's
        lock-step ``_marshal_running``) uploaded wholesale.
        """
        sig = composition_sig(running, Bb)
        blocks = tuple(tuple(engine.cache.seq(s.req.req_id).blocks)
                       for s in running)
        if sig == self.sig:
            if blocks != self.blocks:
                M = engine.ecfg.blocks_per_seq
                tables = np.zeros((Bb, M), np.int32)
                for i, s in enumerate(running):
                    tables[i] = engine.cache.seq(s.req.req_id).table(M)
                self.arrays["tables"] = jnp.asarray(tables)
                self.blocks = blocks
            return self.arrays
        host = engine._marshal_running(running, Bb)
        self.arrays = {k: jnp.asarray(v) for k, v in host.items()}
        self.sig = sig
        self.blocks = blocks
        return self.arrays
