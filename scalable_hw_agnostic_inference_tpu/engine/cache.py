"""Paged KV cache: device block pool + host-side block allocator.

The reference gets paged KV from vLLM's neuron fork (``block_size: 4096``,
reference ``cova/mllama-32-11b-vllm-trn1-config.yaml:16``). TPU-natively the
pool is one device array per layer and leaf ``[num_blocks, block_size,
<what the attention kind keeps of a token>]`` (per-head keys and values
``n_kv, head_dim`` twice, or ONE latent row: ``models.llama.cache_leaves``)
— block tables are *data* (int32 arrays), so one compiled executable serves
any allocation pattern; only bucket shapes trigger compiles.

Allocation is host-side and O(1) per block (free list). The device never
sees fragmentation: gathers go through block tables.
"""

from __future__ import annotations

import dataclasses
import itertools
import logging
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

log = logging.getLogger(__name__)

#: the CLOSED set of pad sizes the tier movers compile for: demotion
#: gathers and restore scatters pad their index arrays to one of these
#: (padding rows target reserved block 0), so attach-time priming covers
#: every shape the post-ready path can dispatch
_PAD_SIZES = (1, 2, 4, 8)
_PAD_MAX = _PAD_SIZES[-1]


def _pad_size(n: int) -> int:
    """Smallest registered pad covering ``n`` (callers chunk at _PAD_MAX)."""
    return 1 << max(0, n - 1).bit_length()


class BlockAllocator:
    """Refcounted free-list allocator over ``total_blocks`` physical blocks.

    Block 0 is reserved as the null block (block tables are padded with 0;
    its contents are garbage but always masked out by sequence lengths).
    Refcounts exist for prefix caching: a block shared by k sequences (plus
    possibly the prefix cache itself) is freed only when every holder lets
    go.
    """

    def __init__(self, total_blocks: int):
        if total_blocks < 2:
            raise ValueError("need at least 2 blocks (0 is reserved)")
        self.total = total_blocks
        self._free: List[int] = list(range(total_blocks - 1, 0, -1))
        self._ref: Dict[int, int] = {}

    @property
    def n_free(self) -> int:
        return len(self._free)

    def refcount(self, block: int) -> int:
        return self._ref.get(block, 0)

    def alloc(self, n: int) -> List[int]:
        if n > len(self._free):
            raise MemoryError(f"wanted {n} blocks, {len(self._free)} free")
        out = [self._free.pop() for _ in range(n)]
        for b in out:
            self._ref[b] = 1
        return out

    def incref(self, block: int) -> None:
        if block not in self._ref:
            raise ValueError(f"incref of unallocated block {block}")
        self._ref[block] += 1

    def free(self, blocks: List[int]) -> None:
        """Drop one reference per block; a block returns to the free list
        when its last reference goes."""
        for b in blocks:
            if b == 0:
                raise ValueError("block 0 is reserved")
            if b not in self._ref:
                raise ValueError(f"double free of block {b}")
            self._ref[b] -= 1
            if self._ref[b] == 0:
                del self._ref[b]
                self._free.append(b)


#: stamps for :attr:`SeqAllocation.version`: one counter for every
#: allocation, so no two block lists ever carry the same stamp — a request
#: preempted and re-admitted under its id gets a NEW allocation, and a new
#: stamp with it
_VERSIONS = itertools.count(1)


@dataclasses.dataclass
class SeqAllocation:
    """Host bookkeeping for one running sequence.

    ``version`` names the block LIST as it stands: every mutation of
    ``blocks`` (a grow that allocates, a shrink that frees, a copy-on-write
    swap) goes through :meth:`touch` and takes a fresh stamp, so a reader
    that kept ``(seq_id, version)`` knows in O(1) whether the table it
    built from this allocation is still true — block IDENTITY, not count:
    a shrink-then-regrow that swaps two rows' blocks moves both stamps."""

    seq_id: int
    blocks: List[int]
    n_tokens: int = 0
    version: int = dataclasses.field(
        default_factory=lambda: next(_VERSIONS))

    def touch(self) -> None:
        self.version = next(_VERSIONS)

    def table(self, blocks_per_seq: int) -> np.ndarray:
        t = np.zeros((blocks_per_seq,), np.int32)
        t[: len(self.blocks)] = self.blocks
        return t


@dataclasses.dataclass(frozen=True)
class RecurrentSpec:
    """The second kind of per-sequence state: ``layers``, the places of the
    recurrent layers in the model's (non-cross) layer order; ``leaves``,
    what ONE slot costs in one of them, ``name -> (shape, dtype)`` (dtype
    ``None``: the pool's own; ``models.llama.state_leaves``); ``n_slots``,
    the engine's ``max_num_seqs``."""

    layers: Tuple[int, ...]
    leaves: Dict[str, Tuple[Tuple[int, ...], Optional[str]]]
    n_slots: int


class PagedKVCache:
    """Device block pool + per-sequence block accounting.

    ``kv`` is a pytree: per layer one array ``[N, Bs, *shape]`` for each
    entry of ``leaves``, which says what a token costs the pool
    (``models.llama.cache_leaves``: ``{"k": (Hkv, Dh), "v": (Hkv, Dh)}``, or
    latent attention's one row ``{"c": (width,)}``). The jitted model paths
    update it functionally (donated) in ``engine.runner``. Block accounting,
    preemption and copy-on-write never look inside a block; the int8 pool
    and the host tier do, and refuse other leaves than ``k``/``v`` by name.

    ``n_layers`` counts the layers that HAVE leaves: the pool is sized by
    them. A model with recurrent layers (``recurrent``) keeps a second kind
    of per-sequence state in the same manager: a slot-indexed ARENA a
    recurrent layer, ``[n_slots + 1, *shape]`` for each of its
    ``state_leaves`` (the last slot is the null slot: padded rows step
    it, as padded tokens write block 0). ``kv`` then lists every layer in
    model order, a paged layer's blocks or a recurrent layer's arena, and
    the step programs carry it as the one donated pytree. A sequence of
    such a model is admitted WITH its slot; release gives both kinds back,
    and the ledger's feeds (``state_*``, ``leaked_bytes``) know both.
    """

    def __init__(self, n_layers: int, leaves: Dict[str, Tuple[int, ...]],
                 total_blocks: int, block_size: int, blocks_per_seq: int,
                 dtype=jnp.bfloat16, sharding=None,
                 enable_prefix_caching: bool = False, tier=None,
                 quant: bool = False,
                 recurrent: Optional["RecurrentSpec"] = None):
        self.n_layers = n_layers
        #: leaf name -> a token's shape in it (behind [N, block_size])
        self.leaves = {name: tuple(per) for name, per in leaves.items()}
        self._plain_kv = set(self.leaves) == {"k", "v"}
        if quant and not self._plain_kv:
            raise ValueError(
                f"an int8 pool (SHAI_KV_QUANT=int8) scales per block and kv "
                f"head: it has no form for the leaves {sorted(self.leaves)} "
                f"(a latent cache)")
        self.block_size = block_size
        self.blocks_per_seq = blocks_per_seq
        #: int8 KV pool (SHAI_KV_QUANT): blocks live as int8 with ONE f32
        #: scale per (block, kv head) riding alongside ("ks"/"vs") —
        #: ~2x blocks per HBM byte, priced through the SAME pool_bytes
        #: seam the HBM ledger and admission gate already read
        self.quant = quant
        self.allocator = BlockAllocator(total_blocks)
        # automatic prefix caching (the vLLM knob): full blocks are
        # content-addressed by a chain hash over their tokens; the cache
        # holds one reference per cached block and evicts LRU when the
        # allocator runs dry. Registered blocks are never written again
        # (prefill writes only a sequence's OWN fresh blocks; decode writes
        # past the prompt), so sharing is read-only by construction.
        self.prefix_caching = enable_prefix_caching
        self._hash2block: Dict[int, int] = {}
        self._block2hash: Dict[int, int] = {}
        self._lru: Dict[int, None] = {}     # insertion-ordered hash -> None
        # chain links for leaf-first eviction: evicting a chain HEAD first
        # would strand its cached descendants (lookups break at the missing
        # head while the tail still pins blocks)
        self._parent: Dict[int, int] = {}
        self._nchild: Dict[int, int] = {}

        def zeros(name: str, shp, dt) -> jax.Array:
            z = jnp.zeros(shp, dt)
            if sharding is not None:
                # tensor-parallel pool: split on the kv-head axis so each tp
                # rank owns its heads' blocks (sharding: {"k": NS, "v": NS})
                z = jax.device_put(z, sharding[name])
            return z

        block_dt = jnp.int8 if quant else dtype
        self.kv = [{name: zeros(name, (total_blocks, block_size) + per,
                                block_dt)
                    for name, per in self.leaves.items()}
                   for _ in range(n_layers)]
        if quant:
            sc_shape = (total_blocks, self.leaves["k"][0])
            for lay in self.kv:
                lay["ks"] = zeros("ks", sc_shape, jnp.float32)
                lay["vs"] = zeros("vs", sc_shape, jnp.float32)
        #: the recurrent layers' arenas, spliced into ``kv`` at their places
        self.recurrent = recurrent
        #: slot -> the sequence that holds it (recurrent models only)
        self._slot_seq: Dict[int, int] = {}
        self._state_bytes = self._slot_bytes = 0
        if recurrent is not None:
            assert not quant and sharding is None and tier is None, \
                "recurrent state: no int8 pool, no mesh, no host tier"
            paged = iter(self.kv)
            self.kv = []
            for i in range(n_layers + len(recurrent.layers)):
                if i not in recurrent.layers:
                    self.kv.append(next(paged))
                    continue
                self.kv.append({
                    name: jnp.zeros((recurrent.n_slots + 1,) + tuple(shp),
                                    dt or dtype)
                    for name, (shp, dt) in recurrent.leaves.items()})
            self._state_bytes = sum(
                int(a.nbytes) for i in recurrent.layers
                for a in self.kv[i].values())
            self._slot_bytes = self._state_bytes // (recurrent.n_slots + 1)
        self._seqs: Dict[int, SeqAllocation] = {}
        self.total_blocks = total_blocks
        # fixed device allocation: price it ONCE (the HBM ledger reads it
        # every engine step — a per-step re-sum is hot-loop host work).
        # Every leaf counts, scale arrays included: shai_hbm_kv_pool_bytes
        # must show the REAL int8 pool cost, not the bf16 one
        self._pool_bytes = sum(int(a.nbytes)
                               for lay in self.kv for a in lay.values()
                               ) - self._state_bytes
        # telemetry counters (obs.steploop reads them through the engine):
        # speculative rollbacks give reserved tokens/blocks back via shrink —
        # a high rollback rate is the "drafter wasting pool headroom" signal
        self.rollback_tokens = 0
        self.rollback_calls = 0
        self.rollback_blocks = 0
        # copy-on-write fan-out (SHAI_KV_COW): forks share blocks via the
        # same refcounts prefix caching uses; the first divergent write
        # into a shared partial tail block pays ONE device block copy
        self.cow_forks = 0
        self.cow_copies = 0
        # one jitted whole-block copy per (shape, dtype) leaf; src/dst ride
        # as DATA so every fork reuses the same compiled copy
        self._cow_copy = jax.jit(
            lambda arr, s, d: arr.at[d].set(arr[s]), donate_argnums=(0,))
        # host KV tier (kvtier/): eviction demotes cached blocks to a
        # bounded host-RAM pool instead of destroying them; admission
        # misses fall through to it and restore via a scatter-write
        self.tier = None
        self._tier_gather = None
        self._tier_restore = None
        if tier is not None:
            self.attach_tier(tier)

    # -- prefix cache -------------------------------------------------------

    @staticmethod
    def _chain_hashes(tokens, block_size: int):
        """Chain hash per FULL block: h_i commits to every token up to and
        including block i, so equal hashes mean equal prefixes.

        Blake2b-based, NOT Python's builtin hash: since the kvnet
        transport (``GET /kv/blocks``) keys blocks by these hashes ACROSS
        pods, the value must be a stable function of the tokens alone —
        the builtin tuple hash is CPython-build/version-dependent, and a
        staggered image rollout across interpreter versions would make
        every cross-pod handoff silently miss. 64-bit signed (fits the
        frame codec's ``<q`` and the int keys everywhere else)."""
        import hashlib

        out = []
        h = 0x5351  # fixed chain seed
        n_full = len(tokens) // block_size
        for i in range(n_full):
            m = hashlib.blake2b(digest_size=8)
            m.update(h.to_bytes(8, "little", signed=True))
            m.update(np.asarray(tokens[i * block_size:(i + 1) * block_size],
                                dtype="<i8").tobytes())
            h = int.from_bytes(m.digest(), "little", signed=True)
            out.append(h)
        return out

    def prefix_hashes(self, tokens) -> List[int]:
        """The prompt's full-block chain hashes — computed ONCE per
        admission attempt and shared by :meth:`cached_prefix`,
        :meth:`tier_prefix_len`, and :meth:`restore_prefix` (hashing every
        token is pure-Python work on the per-step admission path)."""
        if not self.prefix_caching:
            return []
        return self._chain_hashes(tokens, self.block_size)

    def cached_prefix(self, tokens, hashes: Optional[List[int]] = None
                      ) -> List[int]:
        """Longest run of cached blocks matching the prompt's full blocks."""
        if not self.prefix_caching:
            return []
        blocks = []
        for h in (hashes if hashes is not None
                  else self._chain_hashes(tokens, self.block_size)):
            b = self._hash2block.get(h)
            if b is None:
                break
            blocks.append(b)
            self._lru.pop(h, None)      # touch: most-recently-used
            self._lru[h] = None
        return blocks

    def register_prefix(self, tokens, blocks: List[int]) -> None:
        """Publish a prefilled prompt's full blocks for future reuse; the
        cache takes one reference per newly-registered block."""
        if not self.prefix_caching:
            return
        prev = None
        for h, b in zip(self._chain_hashes(tokens, self.block_size), blocks):
            if h in self._hash2block:
                prev = h
                continue  # an identical block is already published
            if b in self._block2hash:
                prev = h
                continue  # this physical block already backs another hash
            self._hash2block[h] = b
            self._block2hash[b] = h
            self.allocator.incref(b)
            self._lru[h] = None
            if prev is not None and prev in self._hash2block:
                self._parent[h] = prev
                self._nchild[prev] = self._nchild.get(prev, 0) + 1
            prev = h

    @property
    def n_evictable(self) -> int:
        """Cached blocks held ONLY by the cache (refcount 1) — reclaimable."""
        return sum(1 for h, b in self._hash2block.items()
                   if self.allocator.refcount(b) == 1)

    @property
    def n_available(self) -> int:
        """Free blocks plus what eviction could reclaim — the admission
        gate's denominator. Tier-aware by construction: with a host tier
        attached, evicting a cached block demotes its contents instead of
        destroying them, so counting evictable blocks as available no
        longer prices reclaimed cache hits as lost prefill work (the
        admission gate still sheds earlier when the HOST pool itself
        saturates — ``resilience.admission``)."""
        return self.allocator.n_free + self.n_evictable

    def _evict(self, n: int) -> int:
        """Drop up to ``n`` LRU cache-only blocks, LEAVES first — a chain
        must shed from the tail or its survivors become unreachable.

        With a host tier attached, eviction is a DEMOTION: the dropped
        blocks' KV is gathered (one dispatch, before any re-allocation can
        overwrite them) and handed to the tier, where a later admission
        miss can restore it instead of re-running prefill."""
        dropped = 0
        demoted: List[Tuple[int, int]] = []
        progress = True
        while dropped < n and progress:
            progress = False
            for h in list(self._lru):
                if dropped >= n:
                    break
                b = self._hash2block[h]
                if self.allocator.refcount(b) != 1:
                    continue  # still shared by a live sequence
                if self._nchild.get(h, 0):
                    continue  # cached descendants would be stranded
                del self._hash2block[h]
                del self._block2hash[b]
                del self._lru[h]
                parent = self._parent.pop(h, None)
                if parent is not None:
                    self._nchild[parent] -= 1
                    if not self._nchild[parent]:
                        del self._nchild[parent]
                if self.tier is not None and self.tier.accepts(h):
                    demoted.append((h, b))
                self.allocator.free([b])
                dropped += 1
                progress = True
        if demoted:
            # the gather dispatches BEFORE the caller's allocation can
            # write the freed blocks (dispatch order is data order); its
            # outputs are fresh buffers, safe to materialize later
            self._demote(demoted)
        return dropped

    def _alloc(self, n: int) -> List[int]:
        short = n - self.allocator.n_free
        if short > 0:
            self._evict(short)
        return self.allocator.alloc(n)

    # -- host KV tier (kvtier/) --------------------------------------------

    def attach_tier(self, tier) -> None:
        """Wire a :class:`~..kvtier.pool.HostKVTier` behind the prefix
        cache and prime the jitted movers against the live pool — the
        closed pad-size set compiles HERE, never on a post-ready request
        (the cold-graph-behind-the-LB discipline)."""
        from ..kvtier.restore import make_tier_gather, make_tier_restore

        if not self._plain_kv:
            raise ValueError(
                f"the host KV tier (SHAI_KVTIER) moves k and v blocks: it "
                f"has no form for the leaves {sorted(self.leaves)} (a "
                f"latent cache)")
        self.tier = tier
        self._tier_gather = make_tier_gather(quant=self.quant)
        self._tier_restore = make_tier_restore(quant=self.quant)
        lay0 = self.kv[0]
        shape = lay0["k"].shape[1:]
        dt = lay0["k"].dtype
        for pad in _PAD_SIZES:
            idx = jnp.zeros((pad,), jnp.int32)
            self._tier_gather(self.kv, idx)
            zeros = jnp.zeros((pad,) + shape, dt)
            # priming writes zeros into reserved block 0 — garbage there
            # is allowed by contract (tables mask it out)
            if self.quant:
                sc0 = jnp.zeros((pad,) + lay0["ks"].shape[1:], jnp.float32)
                (lay0["k"], lay0["v"], lay0["ks"],
                 lay0["vs"]) = self._tier_restore(
                    lay0["k"], lay0["v"], lay0["ks"], lay0["vs"], idx,
                    zeros, zeros, sc0, sc0)
            else:
                lay0["k"], lay0["v"] = self._tier_restore(
                    lay0["k"], lay0["v"], idx, zeros, zeros)

    def _demote(self, pairs: Sequence[Tuple[int, int]]) -> None:
        """Copy evicted blocks' KV out to the host tier: one batched
        gather per <=``_PAD_MAX`` chunk, handed to the tier (async mode
        enqueues the device buffers; the copy-out worker pays the
        transfer). Failures degrade to plain eviction, never raise."""
        tier = self.tier
        try:
            i = 0
            while i < len(pairs):
                grp = list(pairs[i:i + _PAD_MAX])
                n = len(grp)
                idx = np.zeros((_pad_size(n),), np.int32)
                idx[:n] = [b for _, b in grp]
                # quantized pools gather (k, v, ks, vs) in ONE dispatch —
                # the scales ride to the host next to their int8 blocks
                arrays = self._tier_gather(self.kv, jnp.asarray(idx))
                tier.store_batch([h for h, _ in grp], *arrays, n)
                i += n
        except Exception:
            log.warning("kv tier demotion failed; blocks evicted without "
                        "copy", exc_info=True)
            tier.count_error()

    def tier_prefix_len(self, hashes: List[int], from_block: int) -> int:
        """How many full blocks past ``from_block`` the host tier could
        restore for this prompt — the admission ladder's fall-through
        probe when :meth:`cached_prefix` stops short. ``hashes`` is the
        caller's :meth:`prefix_hashes` result (hashed once, shared)."""
        if self.tier is None or from_block >= len(hashes):
            return 0
        return self.tier.probe_run(hashes[from_block:])

    def restore_prefix(self, hashes: List[int], from_block: int, take: int,
                       pin: Sequence[int] = ()) -> List[int]:
        """Swap up to ``take`` host-tier blocks back into the device pool
        and register them as prefix-cache entries (refcount 1, the
        cache's own reference — exactly the state :meth:`register_prefix`
        leaves). Returns the restored device block ids; any shortfall
        (raced host eviction, transfer failure, dry pool) degrades to
        recompute for the uncovered remainder, never to an error.

        ``pin``: the device-cached run the caller is about to share —
        increfed around the allocation so the restore can never evict the
        very blocks it is extending."""
        if self.tier is None or take <= 0:
            return []
        run = self.tier.get_run(hashes[from_block:from_block + take])
        if not run:
            return []
        for b in pin:
            self.allocator.incref(b)
        try:
            try:
                blocks = self._alloc(len(run))
            except MemoryError:
                return []
            try:
                self._tier_write(blocks, run)
            except Exception:
                log.warning("kv tier restore failed; falling back to "
                            "recompute", exc_info=True)
                self.allocator.free(blocks)
                self.tier.count_error()
                return []
        finally:
            if pin:
                # pinned blocks are cache-registered (refcount >= 2 while
                # pinned), so this decref can never free them
                self.allocator.free(list(pin))
        prev = hashes[from_block - 1] if from_block > 0 else None
        if prev is not None and prev not in self._hash2block:
            prev = None
        for ent, b in zip(run, blocks):
            h = ent[0]
            self._hash2block[h] = b
            self._block2hash[b] = h
            self._lru[h] = None
            if prev is not None:
                self._parent[h] = prev
                self._nchild[prev] = self._nchild.get(prev, 0) + 1
            prev = h
        self.tier.count_restored(len(blocks))
        return blocks

    def _tier_write(self, blocks: List[int], run: List[Tuple]) -> None:
        """ONE jitted scatter-write per layer per <=``_PAD_MAX`` chunk:
        the restored blocks' host k/v (and the scale rows of a quantized
        pool) goes back into the pool rows ``blocks`` (padding rows target
        reserved block 0). Pure copies — a restored block is byte-exact."""
        i = 0
        while i < len(blocks):
            grp = blocks[i:i + _PAD_MAX]
            ent = run[i:i + _PAD_MAX]
            n = len(grp)
            pad = _pad_size(n)
            idx = np.zeros((pad,), np.int32)
            idx[:n] = grp
            # entry arrays are [n_layers, <block dims>]; stack per layer —
            # slot 0/1 = k/v blocks, slots 2/3 = the quantized scales
            n_arr = len(ent[0]) - 1
            bufs = []
            for ai in range(n_arr):
                per = ent[0][1 + ai].shape[1:]
                buf = np.zeros((self.n_layers, pad) + per,
                               ent[0][1 + ai].dtype)
                for j, e in enumerate(ent):
                    buf[:, j] = e[1 + ai]
                bufs.append(buf)
            idx_dev = jnp.asarray(idx)
            for li, lay in enumerate(self.kv):
                host = [jnp.asarray(b[li]) for b in bufs]
                if self.quant:
                    (lay["k"], lay["v"], lay["ks"],
                     lay["vs"]) = self._tier_restore(
                        lay["k"], lay["v"], lay["ks"], lay["vs"],
                        idx_dev, *host)
                else:
                    lay["k"], lay["v"] = self._tier_restore(
                        lay["k"], lay["v"], idx_dev, *host)
            i += n

    def demote_prompt_run(self, seq_id: int, prompt_ids) -> int:
        """Prefill-role handoff (kvnet): copy the sequence's full prompt
        blocks into the host tier WITHOUT evicting them from the device —
        the block data is gathered positionally from the sequence's own
        allocation (``admit`` lays blocks out in prompt order), so this
        works whatever admission path built it. Called by the engine at
        request finish, BEFORE release, so a peer decode pod can pull the
        run over ``GET /kv/blocks`` the moment the handoff returns.
        Returns the prompt's full-block count (the handoff's
        ``hashes_len``); failures degrade to recompute-on-the-peer via the
        ``_demote`` contract, never raise."""
        if self.tier is None or not self.prefix_caching:
            return 0
        alloc = self._seqs.get(seq_id)
        if alloc is None:
            return 0
        # NO re-hash here — this runs inside the step loop at every
        # finish on a prefill pod. Every prefill-role admission path has
        # register_prefix'ed the prompt's full blocks, so each block's
        # hash is one _block2hash lookup; an unregistered block (a
        # duplicate prompt whose identical blocks were published under
        # the FIRST copy's physical blocks) ends the walk — harmless,
        # the content-addressed tier already holds that run via the
        # first copy's demotions.
        n_full = len(prompt_ids) // self.block_size
        pairs: List[Tuple[int, int]] = []
        n_run = 0
        for b in alloc.blocks[:n_full]:
            h = self._block2hash.get(b)
            if h is None:
                break
            n_run += 1
            if self.tier.accepts(h):
                pairs.append((h, b))
        if pairs:
            self._demote(pairs)
        return n_run

    def demote_token_run(self, seq_id: int,
                         tokens) -> Tuple[int, List[int]]:
        """Live-migration bank (kvnet.migrate): copy the sequence's full
        blocks over ``tokens`` — prompt AND generated alike — into the
        host tier without evicting them from the device. Unlike
        :meth:`demote_prompt_run` (the per-finish prefill-handoff hot
        path, which walks only already-registered blocks), this PUBLISHES
        the run first: a mid-decode sequence's generated full blocks have
        never been content-addressed, and the migration manifest needs
        their chain hashes on the wire. Migration is a drain-time event,
        so the extra hash pass is off every hot path. Returns
        ``(n_run, hashes[:n_run])`` — the leading run actually banked;
        failures degrade through the ``_demote`` contract (the peer
        recomputes the shortfall), never raise."""
        if self.tier is None or not self.prefix_caching:
            return 0, []
        alloc = self._seqs.get(seq_id)
        if alloc is None:
            return 0, []
        hashes = self.prefix_hashes(tokens)
        if not hashes:
            return 0, []
        # publish prompt+generated full blocks (register_prefix no-ops
        # per-block where an identical block is already cached)
        self.register_prefix(tokens, alloc.blocks)
        pairs: List[Tuple[int, int]] = []
        n_run = 0
        for h, b in zip(hashes, alloc.blocks):
            # a duplicate prompt's blocks may be registered under ANOTHER
            # physical block — content-addressing means the tier run is
            # still intact through that first copy, keep walking by hash
            if self._hash2block.get(h) is None:
                break
            n_run += 1
            src = self._hash2block[h]
            if self.tier.accepts(h):
                pairs.append((h, src))
        if pairs:
            self._demote(pairs)
        return n_run, hashes[:n_run]

    def offload_preempt(self, tokens, seq_id: int) -> None:
        """Preemption offload: publish the victim's full blocks to the
        prefix cache (free — one incref per block) so re-admission reuses
        them directly while they survive, and pool pressure demotes them
        to the host tier through the eviction hook instead of destroying
        prefill+decode work. Only meaningful with a tier attached — the
        pre-tier engine keeps its exact preemption accounting."""
        if self.tier is None or not self.prefix_caching:
            return
        alloc = self._seqs.get(seq_id)
        if alloc is None:
            return
        self.register_prefix(tokens, alloc.blocks)

    # -- host-side sequence lifecycle --------------------------------------

    def can_admit(self, n_tokens: int) -> bool:
        return self._blocks_needed(n_tokens) <= self.n_available

    def admit(self, seq_id: int, n_tokens: int,
              reuse_blocks: Optional[List[int]] = None,
              slot: Optional[int] = None) -> SeqAllocation:
        """Allocate blocks to cover ``n_tokens`` prompt tokens.

        ``reuse_blocks``: cached prefix blocks to share (prefix caching) —
        they are increfed, and only the remainder is freshly allocated.
        ``slot``: the arena slot the sequence's recurrent state lives in
        (a model with recurrent layers admits with one, and only a free
        one; nothing is cleared: a prefill from position 0 overwrites it).
        """
        if seq_id in self._seqs:
            raise ValueError(f"seq {seq_id} already admitted")
        if self.recurrent is not None:
            if slot is None or not 0 <= slot < self.recurrent.n_slots:
                raise ValueError(
                    f"seq {seq_id}: a model with recurrent layers admits "
                    f"with a slot in [0, {self.recurrent.n_slots})")
            if slot in self._slot_seq:
                raise ValueError(f"slot {slot} is held by seq "
                                 f"{self._slot_seq[slot]}")
        reuse = list(reuse_blocks or [])
        need = self._blocks_needed(n_tokens) - len(reuse)
        assert need >= 0, "reuse longer than the prompt"
        # pin the reused blocks FIRST: at refcount 2 they are not evictable,
        # so the allocation below can never evict what we are about to share
        for b in reuse:
            self.allocator.incref(b)
        try:
            fresh = self._alloc(need)
        except MemoryError:
            self.allocator.free(reuse)
            raise
        alloc = SeqAllocation(seq_id, reuse + fresh, n_tokens)
        self._seqs[seq_id] = alloc
        if self.recurrent is not None:
            self._slot_seq[slot] = seq_id
        return alloc

    def fork_sequence(self, parent_id: int, child_id: int) -> SeqAllocation:
        """Copy-on-write fan-out seam (SHAI_KV_COW): admit ``child_id``
        sharing every block of ``parent_id`` (one incref each — the same
        refcounts prefix caching stacks on). Divergence is lazy: the first
        write into a shared partial tail block forks a private copy inside
        :meth:`extend`. Full shared blocks are never written again (prefill
        writes only fresh blocks, decode writes past ``n_tokens`` — the
        read-only-sharing contract above), so only the tail can ever need
        the copy; release/eviction need no special casing because a forked
        block simply carries refcount >= 2 until each holder lets go."""
        if child_id in self._seqs:
            raise ValueError(f"seq {child_id} already admitted")
        parent = self._seqs[parent_id]
        for b in parent.blocks:
            self.allocator.incref(b)
        alloc = SeqAllocation(child_id, list(parent.blocks), parent.n_tokens)
        self._seqs[child_id] = alloc
        self.cow_forks += 1
        return alloc

    def _cow_block(self, alloc: SeqAllocation, idx: int) -> None:
        """Fork a private copy of shared block ``alloc.blocks[idx]`` before
        the first divergent write lands in it. Allocates BEFORE dropping
        the shared reference (a MemoryError here leaves the fork intact for
        the caller's preempt-and-retry ladder), copies every pool leaf —
        int8 blocks and their scale rows byte-exactly — then swaps the
        sequence's table entry. The LAST holder never copies: its refcount
        is 1 by then, so n writers pay exactly n - 1 copies."""
        src = alloc.blocks[idx]
        [dst] = self._alloc(1)
        s = jnp.asarray(src, jnp.int32)
        d = jnp.asarray(dst, jnp.int32)
        for lay in self.kv:
            for name in list(lay):
                lay[name] = self._cow_copy(lay[name], s, d)
        self.allocator.free([src])
        alloc.blocks[idx] = dst
        alloc.touch()
        self.cow_copies += 1

    def _cow_pending(self, alloc: SeqAllocation) -> bool:
        """True when growing ``alloc`` would write into a partial tail
        block some OTHER holder still references — the one block layout
        where extend must fork first."""
        idx = alloc.n_tokens // self.block_size
        return (alloc.n_tokens % self.block_size != 0
                and idx < len(alloc.blocks)
                and self.allocator.refcount(alloc.blocks[idx]) > 1)

    def blocks_to_extend(self, seq_id: int, n_new: int = 1) -> int:
        """Fresh blocks :meth:`extend` would need to grow ``seq_id`` by
        ``n_new`` tokens (0 when the current tail block still has room).

        The async decode pipeline prices a whole step's growth through this
        BEFORE touching the allocator: the steady (lookahead) path must
        never trigger a recompute-preemption mid-dispatch — when the summed
        need exceeds ``n_available`` it flushes and lets the lock-step
        grow-with-preemption path handle the pressure instead. A pending
        copy-on-write fork (shared partial tail about to be written) prices
        its +1 copy block HERE so every caller stays consistent with what
        extend will actually allocate.
        """
        alloc = self._seqs[seq_id]
        need = max(0, self._blocks_needed(alloc.n_tokens + n_new)
                   - len(alloc.blocks))
        if n_new > 0 and self._cow_pending(alloc):
            need += 1
        return need

    def extend(self, seq_id: int, n_new: int = 1) -> SeqAllocation:
        """Grow a sequence by ``n_new`` tokens, allocating blocks as needed.

        When the write range opens inside a shared partial tail block (a
        :meth:`fork_sequence` sibling that is about to diverge), the block
        is copy-on-write forked first — only that one block can ever be
        both shared and written (see the read-only-sharing contract)."""
        alloc = self._seqs[seq_id]
        if n_new > 0 and self._cow_pending(alloc):
            self._cow_block(alloc, alloc.n_tokens // self.block_size)
        need = self._blocks_needed(alloc.n_tokens + n_new) - len(alloc.blocks)
        if need > 0:
            if len(alloc.blocks) + need > self.blocks_per_seq:
                raise MemoryError(f"seq {seq_id} exceeds max_model_len")
            alloc.blocks.extend(self._alloc(need))
            alloc.touch()
        alloc.n_tokens += n_new
        return alloc

    def shrink(self, seq_id: int, n_remove: int) -> SeqAllocation:
        """Roll back the last ``n_remove`` reserved tokens, freeing trailing
        blocks the shorter sequence no longer needs.

        Speculative decoding reserves ``1 + k`` tokens optimistically before
        verification; rejected drafts give their reservation back here so a
        partially-accepted step can't leak pool blocks. Only freshly
        allocated decode-tail blocks are ever in the rollback range —
        prefix-cache-shared blocks live at the FRONT of the allocation
        (``admit`` places ``reuse + fresh``) and a sequence never shrinks
        below its already-committed token count, so a shared block's
        refcount is never touched from here.
        """
        alloc = self._seqs[seq_id]
        if n_remove <= 0:
            return alloc
        assert n_remove <= alloc.n_tokens, "shrink below zero tokens"
        alloc.n_tokens -= n_remove
        self.rollback_tokens += n_remove
        self.rollback_calls += 1
        keep = self._blocks_needed(alloc.n_tokens)
        if keep < len(alloc.blocks):
            tail = alloc.blocks[keep:]
            del alloc.blocks[keep:]
            alloc.touch()
            self.allocator.free(tail)
            self.rollback_blocks += len(tail)
        return alloc

    def release(self, seq_id: int) -> None:
        alloc = self._seqs.pop(seq_id)
        self.allocator.free(alloc.blocks)  # cached blocks survive (cache ref)
        for slot in [s for s, q in self._slot_seq.items() if q == seq_id]:
            del self._slot_seq[slot]       # the state stays: nobody's now

    def seq(self, seq_id: int) -> SeqAllocation:
        return self._seqs[seq_id]

    # -- HBM ledger feed (obs.hbm) -----------------------------------------

    @property
    def pool_bytes(self) -> int:
        """Total device bytes of the preallocated KV pool (all layers;
        priced once at construction — the pool never resizes)."""
        return self._pool_bytes

    @property
    def used_bytes(self) -> float:
        """Logical bytes of allocated (non-free) blocks — the pool is a
        fixed device allocation, so block-level pressure shows up here,
        not in ``pool_bytes``. The reserved null block 0 is excluded: an
        empty pool reads 0, matching :meth:`leaked_blocks`' accounting."""
        if self.total_blocks <= 0:
            return 0.0
        used = (self.total_blocks - 1) - self.allocator.n_free
        return self.pool_bytes * (used / self.total_blocks)

    @property
    def leaked_blocks(self) -> int:
        """Allocated blocks no live holder explains: not referenced by any
        admitted sequence nor by the prefix cache. Always 0 in a correct
        engine — a sequence's natural KV growth is *held* growth — so this
        is the exact KV-leak signal the HBM ledger's drift detector
        tracks (a raw used-block count would read every decoding sequence
        as a leak)."""
        held = set()
        for a in self._seqs.values():
            held.update(a.blocks)
        held.update(self._block2hash.keys())
        used = (self.total_blocks - 1) - self.allocator.n_free  # 0 reserved
        return max(0, used - len(held))

    @property
    def leaked_bytes(self) -> float:
        """Both kinds of per-sequence state no live holder explains."""
        if self.total_blocks <= 0:
            return float(self.state_leaked_bytes)
        return (self.pool_bytes * (self.leaked_blocks / self.total_blocks)
                + self.state_leaked_bytes)

    # -- the recurrent arena's feeds ---------------------------------------

    @property
    def state_bytes(self) -> int:
        """Device bytes of the recurrent layers' arenas (all slots and the
        null slot; 0 for a model without recurrent layers)."""
        return self._state_bytes

    @property
    def slots_live(self) -> int:
        return len(self._slot_seq)

    @property
    def state_used_bytes(self) -> int:
        """Arena bytes of the slots admitted sequences hold."""
        return self._slot_bytes * len(self._slot_seq)

    @property
    def state_leaked_bytes(self) -> int:
        """Arena bytes of slots marked held whose sequence is gone: always
        0 in a correct engine (release gives the slot back)."""
        return self._slot_bytes * sum(
            q not in self._seqs for q in self._slot_seq.values())

    @property
    def active(self) -> List[int]:
        return sorted(self._seqs)

    def _blocks_needed(self, n_tokens: int) -> int:
        return max(1, -(-n_tokens // self.block_size))
