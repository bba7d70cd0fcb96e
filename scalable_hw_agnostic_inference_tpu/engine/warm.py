"""Engine warmup: compile the CLOSED executable set before readiness.

Split from engine.py (VERDICT r3 weak #5): the admission ladder stays in
engine.py; this module owns executable-set warmup. Functions take the engine instance
explicitly — they are the same code paths, re-homed.
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

def warm_executables(eng, prefix_lens: Sequence[int] = (0,)) -> int:
    """Compile the engine's CLOSED executable set up front.

    Every (prefill bucket, prefix_len) pair plus every batch-bucket
    decode step is built here, so no post-ready request can trigger an
    XLA compile — the reference's warmup-gates-readiness idiom
    (``app/run-sd.py:144-146``) applied to the engine. Returns the number
    of executables compiled.
    """
    n = 0
    kmax = min(max(1, eng.ecfg.max_prefill_batch),
               eng.ecfg.max_num_seqs)
    batch_sizes = []
    k = 1
    while k <= kmax:
        batch_sizes.append(k)
        k *= 2
    for b in eng.buckets.buckets:
        for p in sorted(set(prefix_lens)):
            if p == 0:
                for kb in batch_sizes:
                    eng._prefill_for(b, 0, kb)
                    n += 1
            elif 0 < p < b and eng._cross_kv is None:
                eng._prefill_for(b, p)  # prefix path stays single-seq
                n += 1
    if eng.ecfg.max_model_len > eng.buckets.max:
        # chunked-prefill ladder: one continuation executable per chunk
        # start past the largest bucket (cross engines included — their
        # cont executables carry the cross-args tail)
        C = eng.buckets.max
        start = C
        while start + C <= eng.ecfg.max_model_len:
            eng._cont_for(start // eng.ecfg.block_size)
            n += 1
            start += C
    if eng.cache.prefix_caching:
        # cached-admission ladder: (warm start, chunk bucket) pairs so
        # a cache hit never compiles post-ready (closed set — the SAME
        # _cached_starts list admission picks from)
        for s in eng._cached_starts():
            for cb in eng.buckets.buckets:
                if s + cb <= eng.ecfg.max_model_len:
                    key = ("cont", s // eng.ecfg.block_size, cb)
                    if key not in eng._prefill:
                        eng._cont_for(s // eng.ecfg.block_size, cb)
                        n += 1
    bb = 1
    batch_buckets = []
    while bb < eng.ecfg.max_num_seqs:
        batch_buckets.append(bb)
        bb *= 2
    batch_buckets.append(eng.ecfg.max_num_seqs)
    for bb in batch_buckets:
        eng._decode_for(bb)
        n += 1
        if eng._drafter is not None:
            # the speculative verify ladder mirrors decode's batch
            # buckets: a post-ready verify dispatch must never compile
            # (vanilla decode stays in the set too — the engine falls
            # back to it whenever drafting comes up empty)
            eng._verify_for(bb)
            n += 1
    # force compilation (jit is lazy until first call) with null args
    eng._run_warm_calls()
    eng._warmed = True  # cached admission now refuses cold compiles
    # telemetry baseline: every executable built from here on is a
    # bucket-miss recompile (obs counts them; /metrics exposes the total)
    eng.obs.warmed_executables = eng.n_executables
    return n

def _run_warm_calls(eng) -> None:
    ecfg = eng.ecfg
    B, M = ecfg.max_num_seqs, ecfg.blocks_per_seq
    # every warm argument takes the road the engine's own take
    # (``LLMEngine._put``): a program is warmed the way it will be called
    put = eng._put

    def zeros(shape, dtype=np.int32):
        return put(np.zeros(shape, dtype))

    def ones(shape, dtype=np.int32):
        return put(np.ones(shape, dtype))

    def cross_len(n):
        return put(np.full((n,), max(eng.cross_seq_len, 1), np.int32))

    def null_slots(n):
        """A recurrent model's warm rows write the arena's null slot."""
        return eng._slot_args([eng._null_slot] * n)

    #: sampler rows -> one sampler output of that many: what the feed
    #: program is warmed on, below
    sampled = {}

    def warm_sampler(logits, per_row: bool) -> None:
        """The admission-time sampler and the first token's logprob readout
        are part of the closed set too. They are plain jits, keyed on what
        the executable hands them — so they are warmed on its OWN logits:
        under tensor parallelism those are replicated over the mesh, and a
        warm-up on fresh single-device zeros compiled both a second time
        after ready (seconds each, inside the first requests)."""
        K = logits.shape[0]
        key = eng._admit_rng()  # the eager fold is warmed by being made
        if per_row:  # _admit_batch / _admit_fanout: per-row knob arrays
            sampled[K] = eng._sample1(
                logits, key, ones((K,), np.float32), zeros((K,)),
                ones((K,), np.float32))
        if K == 1:   # _admit_one, prefix and continuation: scalar knobs
            sampled[K] = eng._sample1(logits, key, 1.0, 0, 1.0)
        jax.block_until_ready(
            eng._lp1(logits, zeros((K,))))

    for key, fn in list(eng._prefill.items()):
        if key[0] == "cont":
            args = [eng.params, eng.cache.kv, zeros((1, key[2])),
                    ones((1,)), zeros((1, M))] + null_slots(1)
            if eng._cross_kv is not None:
                args += [eng._cross_zeros(1), zeros((1,), np.float32),
                         cross_len(1)]
            eng.cache.kv, logits = fn(*args)
            warm_sampler(logits, per_row=False)
            continue
        bucket, P_, K = key
        args = [eng.params, eng.cache.kv, zeros((K, bucket - P_)),
                ones((K,)), zeros((K, M))] + null_slots(K)
        if P_:
            args.append(zeros((K, P_, eng.cfg.dim), np.float32))
        if eng._cross_kv is not None:
            args += [eng._cross_zeros(K), zeros((K,), np.float32),
                     cross_len(K)]
        eng.cache.kv, logits = fn(*args)
        warm_sampler(logits, per_row=P_ == 0)

    def step_args(bb, tokens):
        """The argument list the decode family shares: null rows."""
        args = [eng.params, eng.cache.kv, tokens, zeros((bb,)),
                zeros((bb, M)), zeros((bb,), bool), eng._rng, zeros(()),
                ones((bb,), np.float32), zeros((bb,)),
                ones((bb,), np.float32)]
        if eng._cross_kv is not None:
            args += [eng._cross_kv, zeros((bb,), np.float32), zeros((bb,)),
                     cross_len(bb)]
        elif eng._state_layers:
            args.append(zeros((bb,)))   # inactive rows: the null slot's
        return args

    for bb, fn in list(eng._decode_fns.items()):
        # async engines warm the feedback variant through the same ladder
        # (pos + 1 and the next fold index ride in *rest; the donated
        # position buffer here is a warm-only throwaway)
        args = step_args(bb, zeros((bb,)))
        eng.cache.kv, nxt, *rest = fn(*args)
        if eng._async:
            # the steady path feeds a step's sampled tokens, pos + 1 and
            # fold index straight back as the next step's inputs. Under
            # tensor parallelism those outputs carry the mesh in their
            # type, which can make that call a second trace of the same
            # executable: warm it the way it will be called, or the first
            # steady step of every batch bucket compiles after ready
            args[1:4] = [eng.cache.kv, nxt, rest[0]]
            args[7] = rest[1]
            eng.cache.kv, nxt, *rest = fn(*args)
            # an event step writes an admission's sampled tokens into this
            # bucket's token input on the device (``_decode_dispatch``):
            # one tiny program a (decode bucket, sampler rows) pair, warmed
            # on the sampler's OWN output for the same reason
            for K, toks in sampled.items():
                eng._feed1(zeros((bb,)), zeros((K,)), toks)
        nxt.block_until_ready()
    K = eng.ecfg.num_speculative_tokens
    for bb, fn in list(eng._verify_fns.items()):
        eng.cache.kv, o, *_rest = fn(*step_args(bb, zeros((bb, K + 1))))
        o.block_until_ready()
    if eng._cross_embed is not None:  # the admission-time projector
        per_layer = eng._cross_embed(
            eng.params,
            jnp.zeros((eng.cross_seq_len, eng.cfg.dim), jnp.float32))
        jax.block_until_ready(per_layer)
        eng._cross_kv = eng._cross_write(
            eng._cross_kv, per_layer, jnp.int32(0))
        jax.block_until_ready(eng._cross_kv)
