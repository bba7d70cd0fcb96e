"""Jitted engine paths: bucketed prefill + one decode step for the batch.

The runner consumes the SAME parameter pytree as
``models.llama.LlamaForCausalLM`` (one weight story: HF convert → orbax →
either the plain server or this engine) but re-plumbs the forward around the
paged KV pool — prefill scatters whole blocks, decode writes one token per
slot and gathers per-slot context through block tables. The reference gets
all of this from the vLLM fork's neuron backend (SURVEY.md §2.6 row 5);
TPU-natively it is two compiled executables per bucket, shapes static.

Decode is ONE executable for the whole running batch: [B] tokens in,
[B] sampled tokens out, sampling on device (reference parity:
``on_device_sampling_config`` ``global_topk: 64``,
``cova/mllama-32-11b-vllm-trn1-config.yaml:19-22``).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..models.llama import LlamaConfig
from ..ops import kda, mla, shortconv, ssm
from ..ops.attention import dot_product_attention, on_tpu_platform
from ..ops.moe import expert_layer, gated_mlp
from ..ops.quant import quant_matmul
from ..ops.rope import apply_rope, apply_rope_interleaved
from ..ops.sampling import (
    sample_excluding,
    sample_logits,
    sampling_probs,
)


class EngineShardings:
    """Tensor-parallel placement plan for the engine's two executables.

    The reference's TP=32 serving tier comes from the vLLM/NxD fork
    (``compile-vllm-job.yaml:54-55``); here it is in_shardings on the jitted
    prefill/decode — params per ``models.llama.tp_rules``, the paged KV pool
    split on its kv-head axis (``cache_specs``) — and XLA inserts the
    collectives over the ``tp`` mesh axis.
    """

    def __init__(self, mesh, params, cfg: LlamaConfig):
        from ..models.llama import cache_specs, tp_rules

        tp = mesh.shape.get("tp", 1)
        # fail loudly at construction: a GQA config whose head counts don't
        # divide tp would otherwise surface as an opaque partitioning error
        # deep inside the first jitted call
        if cfg.n_kv_heads % tp or cfg.n_heads % tp:
            raise ValueError(
                f"tensor_parallel_size={tp} must divide both n_heads="
                f"{cfg.n_heads} and n_kv_heads={cfg.n_kv_heads}. For GQA "
                f"models with tp > n_kv_heads (the reference's 70B TP=32 "
                f"tier), widen the kv heads first with "
                f"models.llama.replicate_kv_heads(params, cfg, tp) — the "
                f"serve layer does this automatically (units/vllm.py)")
        self.mesh = mesh
        self.rep = NamedSharding(mesh, P())
        specs = tp_rules().tree_specs(params)
        self.params = jax.tree.map(
            lambda s: NamedSharding(mesh, s), specs,
            is_leaf=lambda x: isinstance(x, P))
        kvspec = cache_specs(cfg, axis_size=mesh.shape.get("tp", 1))
        self.kv_layer = {n: NamedSharding(mesh, s) for n, s in kvspec.items()}
        # int8 KV pools (SHAI_KV_QUANT): the per-(block, head) scale arrays
        # [N, Hkv] split on the same kv-head axis as the blocks they scale
        self.kv_scale = NamedSharding(mesh, P(None, "tp"))

    def kv_pool(self, n_layers: int, quant: bool = False):
        if quant:
            return [{**self.kv_layer,
                     "ks": self.kv_scale, "vs": self.kv_scale}
                    for _ in range(n_layers)]
        return [dict(self.kv_layer) for _ in range(n_layers)]

    def cross_pool(self, n_cross: int):
        # mllama cross-kv buffers [B, Lv, Hkv, Dh]: split on the kv-head
        # axis, same placement as the paged pool
        spec = NamedSharding(self.mesh, P(None, None, "tp", None))
        return [{"k": spec, "v": spec} for _ in range(n_cross)]


def _rmsnorm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    x32 = x.astype(jnp.float32)
    n = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (n * scale).astype(x.dtype)


def _proj(x: jax.Array, p: Dict[str, jax.Array]) -> jax.Array:
    # plain or int8 weight-only projections (ops.quant): decode re-reads all
    # weights per token, so int8 halves its HBM traffic
    return quant_matmul(x, p)


def _mlp(lp: Dict, x: jax.Array, act: str = "silu") -> jax.Array:
    return gated_mlp(lp["mlp"], x, act)


#: a recurrent KIND's two phases (``prefill``, ``decode``), by
#: ``LlamaConfig.state_kind``
_RECURRENT = {"kda": kda, "ssm": ssm, "conv": shortconv}


def _head_rmsnorm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    """RMSNorm over the head dim of ``[B, T, H, Dh]`` (q/k head norms)."""
    x32 = x.astype(jnp.float32)
    n = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (n * scale).astype(x.dtype)


def _embed(p: Dict, ids: jax.Array, cfg: LlamaConfig) -> jax.Array:
    x = p["embed"]["embedding"][ids]
    if cfg.embed_scale:
        x = x.astype(jnp.float32) * (cfg.dim ** 0.5)
    return x.astype(jnp.bfloat16)


def _latent_qk(at: Dict, h: jax.Array, q: jax.Array, pos: jax.Array,
               cfg: LlamaConfig, rope: bool = True):
    """Latent attention's half of a layer's projections: ``q``
    ``[B, T, H, head_dim]`` with its rotary lanes (the last
    ``qk_rope_head_dim``) turned, and the token's cache row ``[B, T,
    latent_width]``: the latent under its own norm, the ONE rotary key all
    heads share, zeros behind (``ops.mla.latent_rows``). ``rope`` False (a
    layer without positional embedding): those lanes are plain lanes."""
    N, R = cfg.qk_nope_head_dim, cfg.kv_lora_rank
    if not rope:
        turn = lambda x: x                                    # noqa: E731
    elif cfg.rope_interleave:
        turn = functools.partial(apply_rope_interleaved, positions=pos,
                                 theta=cfg.rope_theta)
    else:
        turn = functools.partial(apply_rope, positions=pos,
                                 theta=cfg.rope_theta,
                                 scaling=cfg.rope_scaling)
    q = jnp.concatenate([q[..., :N], turn(q[..., N:])], axis=-1)
    ckr = _proj(h, at["kv_a"])
    c = _rmsnorm(ckr[..., :R], at["kv_norm"]["scale"], cfg.rms_eps)
    k_rope = turn(ckr[:, :, None, R:])[:, :, 0]
    return q, mla.latent_rows(c, k_rope, cfg.latent_width)


@dataclasses.dataclass(frozen=True)
class LayerKind:
    """What one layer is, read off the model config (never a model's
    name): gated cross-attention over vision states or self-attention;
    the keys a query sees behind it (0 = all); rotary embedding or none;
    a routed FFN or the dense MLP; ``state``: a recurrent mixer (linear
    attention, ``ops.kda``, a state-space one, ``ops.ssm``, or a gated
    short convolution, ``ops.shortconv``), whose state is a slot's and not
    the pool's; ``part``: ``"mixer"`` or ``"ffn"``
    where the block is that part ALONE behind one norm, ``""`` where it is
    a mixer then a feed-forward part."""
    cross: bool = False
    window: int = 0
    rope: bool = True
    moe: bool = False
    state: bool = False
    part: str = ""


def layer_kinds(cfg: LlamaConfig) -> List[LayerKind]:
    cross = set(cfg.cross_attention_layers)
    return [LayerKind(cross=li in cross, window=cfg.window_of(li),
                      rope=cfg.rope_of(li), moe=cfg.moe_of(li),
                      state=cfg.state_of(li),
                      part=cfg.part_of(li))
            for li in range(cfg.n_layers)]


def _layer(lp: Dict, kind: LayerKind, x, positions, attend,
           cfg: LlamaConfig, *, cross=None, active=None, shardings=None):
    """THE decoder layer: every program of this module calls it, with its
    own attention closure. Mistral, mllama and the routed/windowed models
    are its cases, chosen by ``kind`` and the config's flags.

    ``x``: the token stream ``[B, T, dim]``, ``positions`` its ``[B, T]``
    cache positions. ``attend(q, k, v, window) -> o``: the program's
    attention — where this layer's new keys and values go in the pool, and
    what each query sees; arrays in, ``[B, T, H, Dh]`` out. With latent
    attention (``cfg.latent``, the attention KIND) ``k`` is the tokens'
    cache rows ``[B, T, latent_width]`` and ``v`` is the layer's ``kv_b``
    leaf, which the program expands or absorbs as its phase wants. In a
    recurrent layer (``kind.state``) ``q`` is the NORMED stream ``[B, T,
    dim]`` and ``k`` the layer's mixer leaves: the program's closure runs
    its phase of the model's recurrent kind over its slots (``prefill`` or
    ``decode`` of ``_RECURRENT``'s module) and hands back the gated output
    ``[B, T, H * d]``. A block of ONE part (``kind.part``) has one
    norm (``lp["norm"]``) and one residual add: the mixer alone, or the
    feed-forward part alone (``attend`` is then not called). ``cross``:
    ``(k, v, has_image, cross_len)`` of a cross layer, which attends those
    and touches no pool. ``active``: the rows that hold a real token (bool,
    ``[B, T]``); padded rows route to no expert.

    Returns ``(x, stats)``: ``stats`` the routed FFN's int32 ``[2]``
    (experts touched, largest load), ``None`` for a dense layer."""
    if kind.cross:
        ck, cv, has_image, cross_len = cross
        return _cross_layer(lp, x, ck, cv, has_image, cfg,
                            cross_len=cross_len, shardings=shardings), None
    if kind.part == "ffn":
        f, stats = _ffn(lp, kind, _rmsnorm(x, lp["norm"]["scale"],
                                           cfg.rms_eps), cfg, active)
        return x + f, stats
    at, Dh = lp["attn"], cfg.head_dim
    B, T, _ = x.shape
    h = _rmsnorm(x, lp["norm" if kind.part else "attn_norm"]["scale"],
                 cfg.rms_eps)
    gate = None
    if kind.state:
        o = attend(h, at, None, 0)
    else:
        q = _proj(h, at["q"]).reshape(B, T, cfg.n_heads, Dh)
        if cfg.latent:
            q, row = _latent_qk(at, h, q, positions, cfg, kind.rope)
            o = attend(q, row, at["kv_b"], kind.window)
        else:
            k = _proj(h, at["k"]).reshape(B, T, cfg.n_kv_heads, Dh)
            v = _proj(h, at["v"]).reshape(B, T, cfg.n_kv_heads, Dh)
            if cfg.qk_norm:
                q = _head_rmsnorm(q, at["q_norm"]["scale"], cfg.rms_eps)
                k = _head_rmsnorm(k, at["k_norm"]["scale"], cfg.rms_eps)
            if kind.rope:
                q = apply_rope(q, positions, cfg.rope_theta,
                               cfg.rope_scaling)
                k = apply_rope(k, positions, cfg.rope_theta,
                               cfg.rope_scaling)
            if cfg.attn_gate:
                gate = _proj(h, at["gate"])
            if cfg.head_lanes:
                # narrow heads ride on ``kv_lanes``: zero lanes add nothing
                # to a score and return zeros, which are cut again
                q, k, v = (jnp.pad(a, ((0, 0),) * 3 + (
                    (0, cfg.kv_lanes - Dh),)) for a in (q, k, v))
                o = attend(q, k, v, kind.window)[..., :Dh]
            else:
                o = attend(q, k, v, kind.window)
    o = o.reshape(B, T, -1)
    if gate is not None:
        o = o * jax.nn.sigmoid(gate)
    h = _proj(o, at["o"])
    if cfg.sandwich_norms:
        h = _rmsnorm(h, lp["post_attn_norm"]["scale"], cfg.rms_eps)
    x = x + h
    if kind.part == "mixer":
        return x, None
    m = _rmsnorm(x, lp["mlp_norm"]["scale"], cfg.rms_eps)
    f, stats = _ffn(lp, kind, m, cfg, active)
    if cfg.sandwich_norms:
        f = _rmsnorm(f, lp["post_mlp_norm"]["scale"], cfg.rms_eps)
    return x + f, stats


def _ffn(lp: Dict, kind: LayerKind, m: jax.Array, cfg: LlamaConfig, active):
    """A layer's feed-forward part on the normed stream ``m``: the routed
    experts (``(f, stats)``) or the dense MLP (``(f, None)``)."""
    if kind.moe:
        return expert_layer(lp["moe"], m, cfg, active=active, held=cfg.held)
    return _mlp(lp, m, cfg.mlp_act), None


def _run_layers(p: Dict, cfg: LlamaConfig, x, positions, attend, *,
                cross=None, active=None, shardings=None, attend_state=None):
    """Walk the stack through :func:`_layer`. ``attend(pi, q, k, v,
    window)`` gets the layer's POOL index first (cross layers and blocks
    that are a feed-forward part alone own no pool entry; a recurrent
    layer's entry of the state list is its slot arena, and ``attend_state``
    is its closure); ``cross(ci) -> (k, v, has_image,
    cross_len)`` serves the ``ci``-th cross layer. Returns ``(x, stats)``,
    the routed layers' stats summed (``None`` with no routed layer)."""
    ci = pi = 0
    stats = None
    for li, kind in enumerate(layer_kinds(cfg)):
        lp = p[f"layer_{li}"]
        if kind.cross:
            x, _ = _layer(lp, kind, x, positions, None, cfg,
                          cross=cross(ci), shardings=shardings)
            ci += 1
            continue
        if kind.part == "ffn":
            x, st = _layer(lp, kind, x, positions, None, cfg,
                           active=active)
        else:
            x, st = _layer(
                lp, kind, x, positions,
                functools.partial(attend_state if kind.state else attend,
                                  pi), cfg, active=active)
            pi += 1
        if st is not None:
            stats = st if stats is None else stats + st
    return x, stats


# top-N alternatives reported per sampled token when a request asks for
# logprobs (the OpenAI `logprobs` field; 5 is the classic completions cap)
K_LOGPROBS = 5


def token_logprobs(logits: jax.Array, toks: jax.Array):
    """``[B, V]`` raw logits + ``[B]`` sampled ids → per-token logprob data:
    ``(top_ids [B, K], top_logprobs [B, K], sampled_logprob [B])``. Raw
    (pre-temperature) distribution — what the OpenAI field reports."""
    logp = logits - jax.scipy.special.logsumexp(logits, axis=-1,
                                                keepdims=True)
    top_lp, top_ids = jax.lax.top_k(logp, K_LOGPROBS)
    tok_lp = jnp.take_along_axis(logp, toks[:, None], axis=1)[:, 0]
    return top_ids.astype(jnp.int32), top_lp, tok_lp


def make_cross_kv(cfg: LlamaConfig):
    """Compile ``cross_kv(params, states [Lv, dim]) -> [n_cross] x {k, v}``.

    The per-request half of mllama cross-attention: project (and k-norm) the
    vision states ONCE at admission; prefill/decode then read the projected
    k/v from slot-indexed buffers every step (vLLM's encoder-cache idea).
    HF recomputes this lazily inside ``MllamaTextCrossAttention`` (
    reference capability: ``cova/mllama-32-11b-vllm-trn1-config.yaml``).
    """

    def cross_kv(params, states):
        p = params["params"]
        out = []
        x = states[None].astype(jnp.bfloat16)      # [1, Lv, dim]
        for li in cfg.cross_attention_layers:
            lp = p[f"layer_{li}"]["cross_attn"]
            Lv = x.shape[1]
            k = _proj(x, lp["k"]).reshape(1, Lv, cfg.n_kv_heads, cfg.head_dim)
            v = _proj(x, lp["v"]).reshape(1, Lv, cfg.n_kv_heads, cfg.head_dim)
            k = _head_rmsnorm(k, lp["k_norm"]["scale"], cfg.rms_eps)
            out.append({"k": k[0], "v": v[0]})
        return out

    return jax.jit(cross_kv)


def make_cross_slot_write(cfg: LlamaConfig):
    """Compile ``write(cross_kv, per_layer, slot) -> cross_kv`` — all cross
    layers' slot rows updated in ONE donated-buffer call (2*n_cross
    host-dispatched full-buffer copies otherwise; ~400MB per admission at
    11B scale)."""

    def write(cross_kv, per_layer, slot):
        out = []
        for buf, new in zip(cross_kv, per_layer):
            out.append({
                "k": buf["k"].at[slot].set(new["k"].astype(buf["k"].dtype)),
                "v": buf["v"].at[slot].set(new["v"].astype(buf["v"].dtype)),
            })
        return out

    return jax.jit(write, donate_argnums=(0,))


def _tp_attention(shardings: Optional["EngineShardings"], q, k, v, *,
                  kv_lengths=None, causal=False, window=0, scale=None):
    """Self/cross attention, head-split over ``tp`` via shard_map under TP.

    The flash kernel behind ``dot_product_attention`` (``ops.pallas``) is a
    raw Mosaic call — XLA's SPMD partitioner refuses to split it
    automatically ("Mosaic kernels cannot be automatically partitioned"), so
    a TP-sharded prefill would fail to COMPILE on the first multi-chip boot.
    Attention is head-local, so under TP the call is explicitly shard_map'd
    on the head axes; contiguous head splits keep every GQA group on its
    rank (``EngineShardings`` enforces tp | n_heads and tp | n_kv_heads,
    widening GQA kv heads by replication when tp is larger —
    ``models.llama.replicate_kv_heads``). Single-device engines call
    straight through. Caught by the tp=32 abstract lowering leg
    (``__graft_entry__.dryrun_lower_llama70b_tp32``).
    """
    if shardings is None:
        return dot_product_attention(q, k, v, kv_lengths=kv_lengths,
                                     causal=causal, window=window,
                                     scale=scale)
    heads = P(None, None, "tp", None)
    if kv_lengths is None:
        return jax.shard_map(
            lambda q_, k_, v_: dot_product_attention(
                q_, k_, v_, causal=causal, window=window, scale=scale),
            mesh=shardings.mesh, in_specs=(heads,) * 3, out_specs=heads,
            check_vma=False,
        )(q, k, v)
    return jax.shard_map(
        lambda q_, k_, v_, n_: dot_product_attention(
            q_, k_, v_, kv_lengths=n_, causal=causal, window=window,
            scale=scale),
        mesh=shardings.mesh,
        in_specs=(heads, heads, heads, P(None)),
        out_specs=heads,
        check_vma=False,
    )(q, k, v, kv_lengths)


def _cross_layer(lp: Dict, x: jax.Array, cross_k: jax.Array,
                 cross_v: jax.Array, has_image: jax.Array,
                 cfg: LlamaConfig, cross_len=None,
                 shardings: Optional["EngineShardings"] = None) -> jax.Array:
    """One mllama gated cross-attention layer.

    ``x`` [B, T, dim]; ``cross_k/v`` [B, Lv, Hkv, Dh] (already k-normed);
    ``has_image`` [B] float gate — rows without vision states contribute
    nothing, which is exactly HF's skip-the-layer semantics for text-only
    requests through an mllama checkpoint. ``cross_len`` [B] marks the valid
    vision-token count per row (multi-tile images use a tile-count-dependent
    prefix of the static Lv buffer; the rest is masked).
    """
    B, T, _ = x.shape
    ca = lp["cross_attn"]
    h = _rmsnorm(x, lp["attn_norm"]["scale"], cfg.rms_eps)
    q = _proj(h, ca["q"]).reshape(B, T, cfg.n_heads, cfg.head_dim)
    q = _head_rmsnorm(q, ca["q_norm"]["scale"], cfg.rms_eps)
    o = _tp_attention(shardings, q, cross_k.astype(q.dtype),
                      cross_v.astype(q.dtype), kv_lengths=cross_len)
    # gate in x's dtype: an f32 gate would promote the residual stream (and
    # every downstream layer) off bf16
    gate = has_image.astype(x.dtype)[:, None, None]
    g_attn = jnp.tanh(lp["gate_attn"]).astype(x.dtype)
    g_mlp = jnp.tanh(lp["gate_mlp"]).astype(x.dtype)
    x = x + g_attn * _proj(o.reshape(B, T, -1), ca["o"]) * gate
    m = _mlp(lp, _rmsnorm(x, lp["mlp_norm"]["scale"], cfg.rms_eps))
    return x + g_mlp * m * gate


def _set_blocks(leaf: jax.Array, tbl: jax.Array,
                blocks: jax.Array) -> jax.Array:
    """``leaf [N, ...]`` with the blocks ``tbl [B, m]`` names replaced by
    ``blocks [B, m, ...]``, scattered as rows of the leaf's flat view
    ``[N, Bs * Hkv, Dh]`` (:func:`_scatter_blocks` says why)."""
    flat = leaf.reshape(leaf.shape[0], -1, leaf.shape[-1])
    flat = flat.at[tbl].set(
        blocks.astype(leaf.dtype).reshape(tbl.shape + flat.shape[1:]))
    return flat.reshape(leaf.shape)


def _scatter_blocks(kv_layer: Dict, tbl: jax.Array, k: jax.Array,
                    v: jax.Array, quant: bool,
                    shardings: Optional["EngineShardings"] = None) -> Dict:
    """Scatter whole fresh KV blocks ``[B, m, Bs, Hkv, Dh]`` into one pool
    layer. int8 pools (``SHAI_KV_QUANT``) quantize per block x kv-head on
    the way in (``ops.quant.quantize_kv_blocks``) and scatter the f32
    scales alongside — THE quantized-write seam every prefill/continuation
    scatter goes through.

    Every leaf is written through its flat view ``[N, Bs * Hkv, Dh]``
    (:func:`_set_blocks`), so that the donated leaf is updated in place. A
    pool leaf ``[N, Bs, Hkv, Dh]`` lives with its heads on the sublanes
    (minor tile ``(Hkv, 128)`` below eight heads); given the 4-D scatter
    ``leaf.at[tbl].set(blocks)`` the TPU compiler wants a block's TOKENS
    there (``{3,1,2,0:T(8,128)}``) and, at 4 and 2 heads (a device's
    share under TP counts), re-lays the WHOLE leaf into that layout and back
    around the few blocks written: two pool-sized copies a leaf a program,
    10 ms of a 27 ms prefill program at four heads over 10,241 blocks. The
    view has the same bytes in the same order whatever ``Hkv`` is, its rows
    fill whole ``(8, 128)`` tiles, and the compiled program is bitcast,
    scatter, bitcast (``tests/test_pool_write_layout.py`` holds it to
    that). Under TP the view is taken of each device's own heads
    (``shard_map``): ``Bs * Hkv`` as one axis could not be split on heads,
    and the partitioner would gather the pool.
    """
    fresh = {"k": k, "v": v}
    if quant:
        from ..ops.quant import quantize_kv_blocks

        fresh["k"], fresh["ks"] = quantize_kv_blocks(k)
        fresh["v"], fresh["vs"] = quantize_kv_blocks(v)

    def write(layer, tbl, fresh):
        return {n: _set_blocks(layer[n], tbl, fresh[n]) for n in layer}

    if shardings is None:
        return write(kv_layer, tbl, fresh)
    specs = {n: s.spec for n, s in shardings.kv_pool(1, quant)[0].items()}
    return jax.shard_map(
        write, mesh=shardings.mesh,
        # a block of the update carries the leaf's axes behind [B, m]
        in_specs=(specs, P(), {n: P(None, None, *s[1:])
                               for n, s in specs.items()}),
        out_specs=specs, check_vma=False)(kv_layer, tbl, fresh)


def _pool_scales(kv_layer: Dict):
    """``(k_scale, v_scale)`` of an int8 pool layer, ``(None, None)`` for a
    float pool — the read-side twin of :func:`_scatter_blocks`."""
    return kv_layer.get("ks"), kv_layer.get("vs")


def _logits(p: Dict, x: jax.Array, cfg: LlamaConfig) -> jax.Array:
    x = _rmsnorm(x, p["final_norm"]["scale"], cfg.rms_eps)
    if cfg.tie_embeddings:
        return (x.astype(jnp.float32) @ p["embed"]["embedding"].T)
    return _proj(x, p["lm_head"]).astype(jnp.float32)


def make_prefill(cfg: LlamaConfig, block_size: int, blocks_per_seq: int,
                 bucket: int, prefix_len: int = 0, n_seqs: int = 1,
                 shardings: Optional[EngineShardings] = None,
                 kv_quant: bool = False):
    """Compile ``prefill(params, kv, ids, n, block_tables[, prefix])``.

    ``n_seqs`` sequences per call: ``ids`` ``[K, bucket - prefix_len]``
    right-padded text with true lengths ``n_text`` ``[K]``, block tables
    ``[K, blocks_per_seq]``. Batching prefills is what keeps K queued prompts
    from each stalling the decode batch serially (VERDICT r2 weak #4) — the
    scheduler admits a same-bucket group and pays ONE executable call. Rows
    beyond the admitted group carry a null block table (all zeros) and write
    harmlessly into reserved block 0. With ``prefix_len > 0`` a ``prefix``
    ``[K, prefix_len, dim]`` of soft embeddings (vision tokens — the
    multimodal path, reference ``vllm_model_api_m.py:42-66``) occupies the
    first positions. k/v for the whole bucket are scattered into the pool;
    pad positions land in the null block and stay masked forever by the
    sequence length. Returns next-token logits from the last valid position
    of each row.
    """
    assert bucket % block_size == 0
    assert 0 <= prefix_len < bucket
    m_used = bucket // block_size
    cross_set = set(cfg.cross_attention_layers)

    def _prefill_impl(params, kv, ids, n_text, block_tables, prefix=None,
                      cross_kv=None, has_image=None, cross_len=None,
                      slots=None):
        p = params["params"]
        B = ids.shape[0]  # == n_seqs
        x = _embed(p, ids, cfg)
        if prefix_len:
            x = jnp.concatenate([prefix.astype(jnp.bfloat16), x], axis=1)
        T = x.shape[1]  # == bucket
        n = n_text + prefix_len
        positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
        tbl = block_tables[:, :m_used]  # [B, m_used]

        def attend_latent(pi, q, r, kv_b, window):
            # the expanded path: every head's keys and values up-projected
            # from the prompt's own latents, then the flash kernel (keys of
            # head_dim beside values of v_head_dim); the pool gets the rows
            k, v = mla.expand(r, kv_b, cfg)
            o = dot_product_attention(q, k, v, kv_lengths=n, causal=True,
                                      scale=mla.softmax_scale(cfg))
            pool = kv[pi]["c"]
            kv[pi] = {"c": pool.at[tbl].set(r.reshape(
                B, m_used, block_size, -1).astype(pool.dtype))}
            return o

        def attend_state(pi, h, at, _v, _window):
            # from position 0 the scan starts from a ZERO state and a zero
            # tail, whatever the slot held: a reused slot needs no clearing.
            # The bucket's padded tail is identity tokens, so what is
            # written is the state and tail the last REAL token left
            o, kv[pi] = _RECURRENT[cfg.state_kind].prefill(
                at, h, kv[pi], slots, n, cfg, carry=False,
                kernel=on_tpu_platform())
            return o

        def attend(pi, q, k, v, window):
            # causal within the prompt; pad keys masked by the true length —
            # kv_lengths (not a mask) keeps the pallas flash kernel eligible
            # for bucketed prefill shapes (VERDICT r1 #3); head-split
            # shard_map under TP (the raw Mosaic kernel cannot be
            # auto-partitioned)
            o = _tp_attention(shardings, q, k, v, kv_lengths=n, causal=True,
                              window=window, scale=cfg.attn_scale)
            # scatter each row's k/v blocks into the pool ([B, m_used]
            # index); int8 pools quantize per block x head on the way in
            kv[pi] = _scatter_blocks(
                kv[pi], tbl,
                k.reshape(B, m_used, block_size, cfg.n_kv_heads,
                          cfg.kv_lanes),
                v.reshape(B, m_used, block_size, cfg.n_kv_heads,
                          cfg.kv_lanes), kv_quant, shardings)
            return o

        # gated cross-attention over vision states: no rope, no KV pool
        # traffic — its keys are static per request
        x, _ = _run_layers(
            p, cfg, x, positions,
            attend_latent if cfg.latent else attend,
            cross=lambda ci: (cross_kv[ci]["k"], cross_kv[ci]["v"],
                              has_image, cross_len),
            active=positions < n[:, None], shardings=shardings,
            attend_state=attend_state)
        last = jnp.take_along_axis(x, (n - 1).reshape(B, 1, 1), axis=1)
        return kv, _logits(p, last, cfg)[:, 0]  # [B, V]

    # positional signature per variant (in_shardings needs positional args)
    if cfg.recurrent:
        # recurrent slot state beside the pool: the rows' SLOTS ride as data
        # (a dummy row carries the null slot). The boot refuses a soft
        # prefix, cross layers and a mesh with it
        assert not prefix_len and not cross_set and shardings is None

        def prefill(params, kv, ids, n_text, block_tables, slots):
            return _prefill_impl(params, kv, ids, n_text, block_tables,
                                 slots=slots)
    elif cross_set:
        assert not prefix_len, "mllama prefill: cross states, not soft prefix"

        def prefill(params, kv, ids, n_text, block_tables, cross_kv,
                    has_image, cross_len):
            return _prefill_impl(params, kv, ids, n_text, block_tables,
                                 cross_kv=cross_kv, has_image=has_image,
                                 cross_len=cross_len)
    elif prefix_len:
        def prefill(params, kv, ids, n_text, block_tables, prefix):
            return _prefill_impl(params, kv, ids, n_text, block_tables,
                                 prefix=prefix)
    else:
        def prefill(params, kv, ids, n_text, block_tables):
            return _prefill_impl(params, kv, ids, n_text, block_tables)

    if shardings is None:
        return jax.jit(prefill, donate_argnums=(1,))
    sh, rep = shardings, shardings.rep
    kvsh = sh.kv_pool(cfg.n_layers - len(cross_set), quant=kv_quant)
    in_sh = [sh.params, kvsh, rep, rep, rep]
    if cross_set:
        in_sh += [sh.cross_pool(len(cross_set)), rep, rep]
    elif prefix_len:
        in_sh += [rep]
    return jax.jit(prefill, donate_argnums=(1,),
                   in_shardings=tuple(in_sh), out_shardings=(kvsh, rep))


def _pool_kernel_call(shardings: Optional["EngineShardings"],
                      qf, kpool, vpool, tf, lf, ks=None, vs=None,
                      window: int = 0, scale: Optional[float] = None):
    """THE dispatch seam for the paged pool kernel on flattened rows:
    direct call on one device, head-split shard_map under TP (the raw
    Mosaic kernel cannot be auto-partitioned; attention is head-local so
    the split needs no collectives). int8 scale arrays ride along when
    present, split on the same kv-head axis as the blocks they scale.
    Decode and verify call it (``_make_token_forward``). ``window``: a window
    layer's bound, handed to the kernel as a static argument (0 hands it
    nothing), and so is ``scale`` (``LlamaConfig.attn_scale``)."""
    from ..ops.pallas.paged_attention import paged_decode_attention as kernel

    if window:
        kernel = functools.partial(kernel, window=window)
    if scale is not None:
        kernel = functools.partial(kernel, scale=scale)
    if shardings is None:
        return kernel(qf, kpool, vpool, tf, lf, ks, vs)
    heads_q = P(None, "tp", None)
    heads_kv = P(None, None, "tp", None)
    if ks is None:
        return jax.shard_map(
            lambda q_, k_, v_, t_, l_: kernel(q_, k_, v_, t_, l_),
            mesh=shardings.mesh,
            in_specs=(heads_q, heads_kv, heads_kv, P(None, None), P(None)),
            out_specs=heads_q, check_vma=False,
        )(qf, kpool, vpool, tf, lf)
    return jax.shard_map(
        lambda q_, k_, v_, t_, l_, ks_, vs_: kernel(
            q_, k_, v_, t_, l_, ks_, vs_),
        mesh=shardings.mesh,
        in_specs=(heads_q, heads_kv, heads_kv, P(None, None), P(None),
                  P(None, "tp"), P(None, "tp")),
        out_specs=heads_q, check_vma=False,
    )(qf, kpool, vpool, tf, lf, ks, vs)


def make_prefill_cont(cfg: LlamaConfig, block_size: int, blocks_per_seq: int,
                      bucket: int, start_blocks: int,
                      shardings: Optional[EngineShardings] = None,
                      kv_quant: bool = False):
    """Compile a CONTINUATION prefill chunk: ``cont(params, kv, ids, n_text,
    block_tables) -> (kv, next_logits)``.

    Prompts longer than the largest prefill bucket process in bucket-sized
    chunks, one per engine step — this executable handles the chunk whose
    first token sits at the STATIC position ``start_blocks * block_size``.
    The chunk's queries attend (a) the ``start`` tokens already written to
    the pool (gathered densely through the block table — amortized over the
    whole chunk, unlike decode's per-token gather) and (b) the chunk itself,
    causally. Keys are the exact concatenation [prior, chunk], so the causal
    offset ``S - T == start`` is exact and the flash kernel stays eligible
    (``kv_lengths = start + n_text`` masks chunk padding; a padded tail also
    writes into null block 0 like every other prefill).

    One executable per chunk start (``max_model_len / bucket - 1`` of them)
    — the static-shape ladder the reference bakes at compile time with its
    ``context_encoding_buckets`` (``cova/mllama-32-11b-vllm-trn1-config.yaml:10-16``),
    extended past the largest bucket. This is what makes a 128k
    ``max_model_len`` practical rather than a config key.

    Cross-attention (mllama) configs chunk too: the gated cross layers
    attend the request's static vision states each chunk (no pool traffic,
    same as ``make_prefill``); the signature gains the
    ``(cross_kv, has_image, cross_len)`` tail.

    ``kv_quant``: int8 pool — the prior-context gather dequantizes, the
    chunk scatter quantizes per block x head (``_scatter_blocks``).
    """
    assert bucket % block_size == 0
    assert start_blocks >= 1
    start = start_blocks * block_size
    c_blocks = bucket // block_size
    assert start_blocks + c_blocks <= blocks_per_seq
    cross_set = set(cfg.cross_attention_layers)

    def _cont_impl(params, kv, ids, n_text, block_tables, cross_kv=None,
                   has_image=None, cross_len=None, slots=None):
        p = params["params"]
        B = ids.shape[0]  # == 1
        x = _embed(p, ids, cfg)
        T = x.shape[1]  # == bucket
        n = n_text + start  # total valid tokens after this chunk
        offs = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
        positions = start + offs
        tbl_prior = block_tables[:, :start_blocks]        # [B, start_blocks]
        goff = (tbl_prior[:, :, None] * block_size
                + jnp.arange(block_size)[None, None, :]).reshape(B, start)
        tbl_chunk = block_tables[:, start_blocks:start_blocks + c_blocks]

        def attend_latent(pi, q, r, kv_b, window):
            # the chunk's prefix is LATENT in the pool: its rows are
            # gathered through the block table and expanded beside the
            # chunk's own (one up-projection of start + T rows a layer,
            # a third of the absorbed form's operations at these widths)
            pool = kv[pi]["c"]
            prior = pool.reshape(-1, pool.shape[-1])[goff].astype(r.dtype)
            k, v = mla.expand(jnp.concatenate([prior, r], axis=1), kv_b, cfg)
            o = dot_product_attention(q, k, v, kv_lengths=n, causal=True,
                                      scale=mla.softmax_scale(cfg))
            kv[pi] = {"c": pool.at[tbl_chunk].set(r.reshape(
                B, c_blocks, block_size, -1).astype(pool.dtype))}
            return o

        def attend_state(pi, h, at, _v, _window):
            # a continuation chunk's prefix is the SLOT's state and tail,
            # not the pool: read, scanned over the chunk, written back
            o, kv[pi] = _RECURRENT[cfg.state_kind].prefill(
                at, h, kv[pi], slots, n_text, cfg, carry=True,
                kernel=on_tpu_platform())
            return o

        def attend(pi, q, k, v, window):
            if kv_quant:
                # int8 prior context: block-shaped gather so the
                # per-(block, head) scales broadcast on the dequant
                from ..ops.quant import dequantize_kv_blocks

                kprior = dequantize_kv_blocks(
                    kv[pi]["k"][tbl_prior], kv[pi]["ks"][tbl_prior],
                    q.dtype).reshape(B, start, cfg.n_kv_heads, cfg.kv_lanes)
                vprior = dequantize_kv_blocks(
                    kv[pi]["v"][tbl_prior], kv[pi]["vs"][tbl_prior],
                    q.dtype).reshape(B, start, cfg.n_kv_heads, cfg.kv_lanes)
            else:
                kflat = kv[pi]["k"].reshape(-1, cfg.n_kv_heads, cfg.kv_lanes)
                vflat = kv[pi]["v"].reshape(-1, cfg.n_kv_heads, cfg.kv_lanes)
                kprior = kflat[goff].astype(q.dtype)
                vprior = vflat[goff].astype(q.dtype)
            kcat = jnp.concatenate([kprior, k], axis=1)  # [B, start+T, ...]
            vcat = jnp.concatenate([vprior, v], axis=1)
            o = _tp_attention(shardings, q, kcat, vcat, kv_lengths=n,
                              causal=True, window=window,
                              scale=cfg.attn_scale)
            kv[pi] = _scatter_blocks(
                kv[pi], tbl_chunk,
                k.reshape(B, c_blocks, block_size, cfg.n_kv_heads,
                          cfg.kv_lanes),
                v.reshape(B, c_blocks, block_size, cfg.n_kv_heads,
                          cfg.kv_lanes), kv_quant, shardings)
            return o

        x, _ = _run_layers(
            p, cfg, x, positions,
            attend_latent if cfg.latent else attend,
            cross=lambda ci: (cross_kv[ci]["k"], cross_kv[ci]["v"],
                              has_image, cross_len),
            active=offs < n_text[:, None], shardings=shardings,
            attend_state=attend_state)
        last = jnp.take_along_axis(x, (n_text - 1).reshape(B, 1, 1), axis=1)
        return kv, _logits(p, last, cfg)[:, 0]  # [B, V]

    if cfg.recurrent:
        assert not cross_set and shardings is None  # as make_prefill

        def cont(params, kv, ids, n_text, block_tables, slots):
            return _cont_impl(params, kv, ids, n_text, block_tables,
                              slots=slots)
    elif cross_set:
        def cont(params, kv, ids, n_text, block_tables, cross_kv, has_image,
                 cross_len):
            return _cont_impl(params, kv, ids, n_text, block_tables,
                              cross_kv=cross_kv, has_image=has_image,
                              cross_len=cross_len)
    else:
        def cont(params, kv, ids, n_text, block_tables):
            return _cont_impl(params, kv, ids, n_text, block_tables)

    if shardings is None:
        return jax.jit(cont, donate_argnums=(1,))
    sh, rep = shardings, shardings.rep
    kvsh = sh.kv_pool(cfg.n_layers - len(cross_set), quant=kv_quant)
    in_sh = [sh.params, kvsh, rep, rep, rep]
    if cross_set:
        in_sh += [sh.cross_pool(len(cross_set)), rep, rep]
    return jax.jit(cont, donate_argnums=(1,),
                   in_shardings=tuple(in_sh), out_shardings=(kvsh, rep))


def _resolve_paged(paged):
    """Default the paged-kernel switch: on for TPU backends, off elsewhere
    (the interpreter is test-only); the ``SHAI_PAGED_DECODE`` env var (0/1)
    overrides."""
    from ..obs.util import env_flag

    if paged is not None:
        return paged
    env = env_flag("SHAI_PAGED_DECODE", None)
    if env is not None:
        return env
    from ..ops.attention import on_tpu_platform

    return on_tpu_platform()


def _make_token_forward(cfg: LlamaConfig, block_size: int,
                        blocks_per_seq: int, max_num_seqs: int, T: int,
                        shardings: Optional[EngineShardings], paged: bool,
                        kv_quant: bool = False):
    """THE paged-engine forward for ``T`` new tokens per sequence — decode
    is its ``T=1`` instantiation, speculative verify its ``T=k+1``, so the
    two dispatch paths share one layer stack and cannot drift apart (the
    greedy-equivalence invariant rests on this).

    ``fwd(params, kv, tokens [B, T], positions [B, T],
    tables [B, blocks_per_seq] [, cross tail]) -> (kv, logits [B, T, V])``:
    scatters all ``T`` tokens' kv into the pool — positions past the table
    or a slot's reservation route to the null block, the harmless-garbage
    padding convention — then every query attends its own causal window:
    through the Pallas paged kernel with the ``T`` queries flattened into
    the batch axis (a row walks its own live tiles, whatever the table's
    width: ``ops.pallas.paged_attention``), or the dense gather + mask path
    off-TPU.
    """
    L = block_size * blocks_per_seq
    cross_set = set(cfg.cross_attention_layers)

    def fwd(params, kv, tokens, positions, tables, cross_kv=None,
            has_image=None, slot_idx=None, cross_len=None, active=None):
        p = params["params"]
        B = max_num_seqs
        x = _embed(p, tokens, cfg)                                # [B,T,d]
        # flat write offsets for the T new tokens' kv: [B, T]
        pblk = positions // block_size
        blk = jnp.where(
            pblk < blocks_per_seq,
            jnp.take_along_axis(
                tables, jnp.clip(pblk, 0, blocks_per_seq - 1), axis=1),
            0)
        widx = blk * block_size + positions % block_size
        if not paged and not kv_quant and not cfg.latent:
            # flat gather offsets for the whole context window: [B, L]
            goff = (tables[:, :, None] * block_size
                    + jnp.arange(block_size)[None, None, :]).reshape(B, L)
            # query t attends exactly positions <= positions[b, t] (its own
            # just-written token included); padding rows see one dummy token
            behind = positions[:, :, None] - jnp.arange(L)[None, None, :]
            mask = (behind >= 0)[:, None]               # [B, 1, T, L]

        def attend_latent(pi, q, r, kv_b, window):
            # the absorbed path: the new tokens' rows go into the pool,
            # the queries into the rows' own coordinates, and every head
            # reads the row's tiles ONCE through the latent kernel (the
            # gather reference off the TPU); W^V comes after the softmax
            pool = kv[pi]["c"]
            kv[pi] = {"c": pool.reshape(-1, pool.shape[-1]).at[widx].set(
                r.astype(pool.dtype)).reshape(pool.shape)}
            u = mla.paged_latent_attention(
                mla.absorb_q(q, kv_b, cfg), kv[pi]["c"], tables, positions,
                rank=cfg.kv_lora_rank, scale=mla.softmax_scale(cfg),
                paged=paged)
            return mla.unabsorb(u, kv_b, cfg)

        def attend_state(pi, h, at, _v, _window):
            # one recurrent step a row, in place on the row's slot; a
            # padded or finished row steps the NULL slot (the arena's last),
            # so no sequence's state or tail is touched for it
            assert T == 1, "one token a step over recurrent state"
            n_slots = next(iter(kv[pi].values())).shape[0]
            slots = jnp.where(active > 0, slot_idx, n_slots - 1)
            o, kv[pi] = _RECURRENT[cfg.state_kind].decode(
                at, h, kv[pi], slots, cfg, kernel=paged)
            return o

        def attend(pi, q, kk, vv, window):
            if kv_quant:
                # int8 pool: one read-modify-write requantize per new token
                # (T is 1 for decode, k+1 for verify — a tiny unroll); the
                # block's scale only ever grows, so resident tokens stay
                # within half a step of the final scale
                from ..ops.quant import requantize_block_tokens

                kpool, vpool = kv[pi]["k"], kv[pi]["v"]
                ks_, vs_ = kv[pi]["ks"], kv[pi]["vs"]
                for t in range(T):
                    bt = blk[:, t]
                    pin = positions[:, t] % block_size
                    kq, ksn = requantize_block_tokens(
                        kpool[bt], ks_[bt], kk[:, t], pin)
                    vq, vsn = requantize_block_tokens(
                        vpool[bt], vs_[bt], vv[:, t], pin)
                    kpool = kpool.at[bt].set(kq)
                    vpool = vpool.at[bt].set(vq)
                    ks_ = ks_.at[bt].set(ksn)
                    vs_ = vs_.at[bt].set(vsn)
                kv[pi] = {"k": kpool, "v": vpool, "ks": ks_, "vs": vs_}
            else:
                # one token a row through the flat view [N * Bs, Hkv, Dh]:
                # the same device as _scatter_blocks' view (the leaf's own
                # byte order, so the donated leaf is written in place and
                # not re-laid around the write), with the heads kept an
                # axis, so that TP splits it without a shard_map
                pool_shape = kv[pi]["k"].shape
                kflat = kv[pi]["k"].reshape(-1, cfg.n_kv_heads, cfg.kv_lanes)
                vflat = kv[pi]["v"].reshape(-1, cfg.n_kv_heads, cfg.kv_lanes)
                kflat = kflat.at[widx].set(kk.astype(kflat.dtype))
                vflat = vflat.at[widx].set(vv.astype(vflat.dtype))
                kv[pi] = {"k": kflat.reshape(pool_shape),
                          "v": vflat.reshape(pool_shape)}
            ksc, vsc = _pool_scales(kv[pi])
            if paged:
                o = _pool_kernel_call(
                    shardings, q.reshape(B * T, cfg.n_heads, cfg.kv_lanes),
                    kv[pi]["k"], kv[pi]["v"],
                    jnp.repeat(tables, T, axis=0) if T > 1 else tables,
                    jnp.clip(positions + 1, 1, L).reshape(B * T),
                    ksc, vsc, window=window, scale=cfg.attn_scale)
                return o.reshape(B, T, cfg.n_heads, cfg.kv_lanes)
            if kv_quant:
                # deviceless int8 path: the gather reference dequantizes
                # right after the block gather (ops.attention)
                from ..ops.attention import ragged_gather_attention

                return ragged_gather_attention(
                    q, kv[pi]["k"], kv[pi]["v"], tables, positions, ksc,
                    vsc, window=window, scale=cfg.attn_scale)
            kflat = kv[pi]["k"].reshape(-1, cfg.n_kv_heads, cfg.kv_lanes)
            vflat = kv[pi]["v"].reshape(-1, cfg.n_kv_heads, cfg.kv_lanes)
            # a window layer's query also drops what lies a window behind
            m = mask & (behind < window)[:, None] if window else mask
            return dot_product_attention(q, kflat[goff], vflat[goff],
                                         mask=m, scale=cfg.attn_scale)

        # slot_idx maps the COMPACTED batch row back to its slot's rows in
        # the full cross-kv buffers (gather fuses into the attention read)
        x, stats = _run_layers(
            p, cfg, x, positions,
            attend_latent if cfg.latent else attend,
            cross=lambda ci: (cross_kv[ci]["k"][slot_idx],
                              cross_kv[ci]["v"][slot_idx], has_image,
                              cross_len),
            active=None if active is None or not cfg.n_experts else
            jnp.broadcast_to(active[:, None] > 0, (B, T)),
            shardings=shardings, attend_state=attend_state)
        return kv, _logits(p, x, cfg), stats  # [B, T, V] f32

    return fwd


#: a step's decode-family draw folds ``step * FOLD_STRIDE`` into the base
#: key; the odd indices between are the admission sampler's (``engine.py``)
FOLD_STRIDE = 2


def make_decode(cfg: LlamaConfig, block_size: int, blocks_per_seq: int,
                max_num_seqs: int,
                shardings: Optional[EngineShardings] = None,
                paged: Optional[bool] = None, feedback: bool = False,
                kv_quant: bool = False):
    """Compile one decode step for the whole slot batch.

    ``decode(params, kv, tokens [B], pos [B], tables [B, M], active [B],
    rng, fold, temperature [B], top_k [B], top_p [B]) ->
    (kv, next_tokens [B])``.

    ``rng`` is the engine's BASE key, resident on the device like the
    weights, and ``fold`` the int32 scalar this step folds into it: the
    program draws from ``fold_in(rng, fold)``, the same threefry the host
    used to launch as two eager programs before every dispatch, so the
    keys, and the sampled tokens, are bit for bit what they were. The
    ``feedback`` variant hands back ``fold + FOLD_STRIDE`` beside
    ``pos + 1``: the next step's index, already on the device (on a
    four-chip mesh a host scalar costs the call 0.5 ms, a resident one
    nothing: PERF.md, PR 30).

    ``feedback``: the async-pipeline variant (``SHAI_ASYNC_DECODE``). The
    executable additionally returns ``pos + 1`` (and the next ``fold``) so
    the engine can feed the sampled-token and position arrays of step N
    straight back as step N+1's inputs without a host round-trip, and
    ``pos`` is donated along
    with the KV pool (the position buffer ping-pongs in place; ``tokens``
    is NOT donated — the host still reads step N's sampled tokens back one
    step later for EOS/stop bookkeeping, and a donated buffer could not be
    fetched after being consumed by the next dispatch).

    ``pos[b]`` is the index the new token is written at (== tokens so far).
    Inactive slots carry ``tables`` of zeros and write harmlessly into the
    reserved null block 0.

    ``max_num_seqs`` here is the BATCH BUCKET of this executable, not
    necessarily the engine's slot count: the engine compacts active slots
    and dispatches the smallest power-of-two batch covering them, so decode
    cost scales with occupancy (VERDICT r2 weak #3: a lone sequence no
    longer pays for a full idle batch). The batch bucket is the ONLY thing
    that chooses a decode program: attention is handed the full
    ``blocks_per_seq`` table and each row pays for the tiles it holds.

    ``paged``: attention streams straight out of the block pool via the
    Pallas paged kernel (``ops.pallas.paged_attention``) instead of the
    dense ``[B, L, Hkv, Dh]`` gather (VERDICT r2 missing #3). Default: on
    for TPU backends, off elsewhere (the interpreter is test-only); the
    ``SHAI_PAGED_DECODE`` env var (0/1) overrides.

    ``kv_quant``: int8 KV pool (``SHAI_KV_QUANT=int8``) — writes quantize
    per block x kv-head, reads dequantize in-kernel; the kv pytree carries
    ``ks``/``vs`` scale arrays next to the block pools.

    The layer stack itself is ``_make_token_forward`` at ``T=1`` — shared
    verbatim with the speculative verify executable.
    """
    paged = _resolve_paged(paged)
    cross_set = set(cfg.cross_attention_layers)
    fwd = _make_token_forward(cfg, block_size, blocks_per_seq, max_num_seqs,
                              1, shardings, paged, kv_quant=kv_quant)

    def _decode_impl(params, kv, tokens, pos, tables, active, rng, fold,
                     temperature, top_k, top_p, cross_kv=None, has_image=None,
                     slot_idx=None, cross_len=None):
        rng = jax.random.fold_in(rng, fold)
        kv, logits, stats = fwd(
            params, kv, tokens[:, None], pos[:, None], tables,
            cross_kv=cross_kv, has_image=has_image, slot_idx=slot_idx,
            cross_len=cross_len, active=active)
        logits = logits[:, 0]  # [B, V]
        if cfg.latent:
            # what the latent kernel read: a live row's tokens so far, this
            # one included, in every layer (one more int32 behind the
            # routing counts, in the same read)
            seen = (jnp.sum(jnp.where(active > 0, pos + 1, 0))
                    * (cfg.n_layers - len(cross_set) - len(cfg.kda_layers))
                    ).astype(jnp.int32)[None]
            stats = seen if stats is None else jnp.concatenate([stats, seen])
        nxt = sample_logits(logits, rng, temperature, top_k, top_p)
        # logprob data rides along (tiny vs the matmuls); the engine only
        # transfers it to the host when a running request asked for it
        top_ids, top_lp, tok_lp = token_logprobs(logits, nxt)
        out = (kv, nxt) + (
            (pos + 1, fold + FOLD_STRIDE) if feedback else ()) + (
            top_ids, top_lp, tok_lp)
        # a routed model's step says what routing did (ROUTE_STATS int32
        # behind the sampled tokens, ONE array: the host's one read of the
        # step carries both), a latent one what its kernel read; a dense
        # model's outputs are what they were
        return out if stats is None else out + (
            jnp.concatenate([nxt.astype(jnp.int32), stats]),)

    if cfg.recurrent:
        # the rows' SLOTS ride as data, as the cross tail's do
        assert not cross_set and shardings is None  # as make_prefill

        def decode(params, kv, tokens, pos, tables, active, rng, fold,
                   temperature, top_k, top_p, slot_idx):
            return _decode_impl(params, kv, tokens, pos, tables, active, rng,
                                fold, temperature, top_k, top_p,
                                slot_idx=slot_idx)
    elif cross_set:
        def decode(params, kv, tokens, pos, tables, active, rng, fold,
                   temperature, top_k, top_p, cross_kv, has_image, slot_idx,
                   cross_len):
            return _decode_impl(params, kv, tokens, pos, tables, active, rng,
                                fold, temperature, top_k, top_p,
                                cross_kv=cross_kv, has_image=has_image,
                                slot_idx=slot_idx, cross_len=cross_len)
    else:
        def decode(params, kv, tokens, pos, tables, active, rng, fold,
                   temperature, top_k, top_p):
            return _decode_impl(params, kv, tokens, pos, tables, active, rng,
                                fold, temperature, top_k, top_p)

    donate = (1, 3) if feedback else (1,)
    if shardings is None:
        return jax.jit(decode, donate_argnums=donate)
    sh, rep = shardings, shardings.rep
    kvsh = sh.kv_pool(cfg.n_layers - len(cross_set), quant=kv_quant)
    in_sh = (sh.params, kvsh) + (rep,) * 9
    if cross_set:
        in_sh += (sh.cross_pool(len(cross_set)), rep, rep, rep)
    out_sh = (kvsh,) + (rep,) * ((6 if feedback else 4)
                                 + bool(cfg.n_experts or cfg.latent))
    return jax.jit(decode, donate_argnums=donate,
                   in_shardings=in_sh, out_shardings=out_sh)


def make_verify(cfg: LlamaConfig, block_size: int, blocks_per_seq: int,
                max_num_seqs: int, k: int,
                shardings: Optional[EngineShardings] = None,
                paged: Optional[bool] = None, kv_quant: bool = False):
    """Compile one speculative VERIFY step: score ``k + 1`` positions per
    sequence in ONE paged-attention dispatch.

    ``verify(params, kv, tokens [B, k+1], pos0 [B], tables [B, M],
    active [B], rng, fold, temperature [B], top_k [B], top_p [B]) ->
    (kv, o [B, k+1], oex [B, k], accept_p [B, k], o_lp [B, k+1],
    d_lp [B, k], oex_lp [B, k], top_ids [B, k+1, K], top_lp [B, k+1, K])``.

    ``tokens[:, 0]`` is each slot's pending token, ``tokens[:, 1:]`` the
    drafted continuation (zero-padded past the slot's true draft length —
    padded positions write into the null block / reserved tail and their
    outputs are never committed). ``pos0[b]`` is the cache index the
    pending token is written at; position ``i`` lands at ``pos0 + i``. The
    layer stack is ``_make_token_forward`` at ``T=k+1`` — shared verbatim
    with vanilla decode.

    Outputs, per position ``i`` (predicting the token at ``pos0 + i + 1``):
    ``o`` a sample from the full target distribution (argmax at temperature
    0), ``oex`` a sample with the draft token removed AFTER the top-k/top-p
    masks (the rejection-resample stays inside vanilla's support —
    ``ops.sampling.sample_excluding``), ``accept_p`` the draft token's
    probability under the ACTUAL sampling distribution
    (``ops.sampling.sampling_probs``), plus raw logprob data for every
    token the engine might commit (the OpenAI ``logprobs`` surface):
    ``o_lp``/``d_lp``/``oex_lp`` and the top-K alternatives. Acceptance
    itself is a host-side walk (``speculative.accept_drafts``) — per-slot
    draft lengths are dynamic, the executable stays static-shaped.
    """
    assert k >= 1
    T = k + 1
    paged = _resolve_paged(paged)
    cross_set = set(cfg.cross_attention_layers)
    fwd = _make_token_forward(cfg, block_size, blocks_per_seq, max_num_seqs,
                              T, shardings, paged, kv_quant=kv_quant)

    def _verify_impl(params, kv, tokens, pos0, tables, active, rng, fold,
                     temperature, top_k, top_p, cross_kv=None, has_image=None,
                     slot_idx=None, cross_len=None):
        B = max_num_seqs
        rng = jax.random.fold_in(rng, fold)  # as make_decode
        positions = pos0[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
        kv, logits, _ = fwd(params, kv, tokens, positions, tables,
                            cross_kv=cross_kv, has_image=has_image,
                            slot_idx=slot_idx, cross_len=cross_len,
                            active=active)
        draft = tokens[:, 1:]  # [B, k]
        bt = jnp.broadcast_to(temperature[:, None], (B, T))
        bk = jnp.broadcast_to(top_k[:, None], (B, T))
        bp = jnp.broadcast_to(top_p[:, None], (B, T))
        # independent per-position samples: one folded key each — categorical
        # over a [B, T, V] batch already draws per-row
        o_tok = sample_logits(logits, jax.random.fold_in(rng, 1),
                              bt, bk, bp)
        # rejection resample: the draft token is removed AFTER the
        # top-k/top-p masks, keeping the resample inside vanilla's support
        oex = sample_excluding(logits[:, :k], jax.random.fold_in(rng, 2),
                               draft, bt[:, :k], bk[:, :k], bp[:, :k])
        accept_p = jnp.take_along_axis(
            sampling_probs(logits[:, :k], bt[:, :k], bk[:, :k], bp[:, :k]),
            draft[..., None], axis=-1)[..., 0]
        # raw (pre-temperature) logprob surface for every committable token
        logp = logits - jax.scipy.special.logsumexp(logits, axis=-1,
                                                    keepdims=True)
        top_lp, top_ids = jax.lax.top_k(logp, K_LOGPROBS)
        o_lp = jnp.take_along_axis(logp, o_tok[..., None], axis=-1)[..., 0]
        d_lp = jnp.take_along_axis(logp[:, :k], draft[..., None],
                                   axis=-1)[..., 0]
        oex_lp = jnp.take_along_axis(logp[:, :k], oex[..., None],
                                     axis=-1)[..., 0]
        return (kv, o_tok, oex, accept_p, o_lp, d_lp, oex_lp,
                top_ids.astype(jnp.int32), top_lp)

    if cross_set:
        def verify(params, kv, tokens, pos0, tables, active, rng, fold,
                   temperature, top_k, top_p, cross_kv, has_image, slot_idx,
                   cross_len):
            return _verify_impl(params, kv, tokens, pos0, tables, active,
                                rng, fold, temperature, top_k, top_p,
                                cross_kv=cross_kv, has_image=has_image,
                                slot_idx=slot_idx, cross_len=cross_len)
    else:
        def verify(params, kv, tokens, pos0, tables, active, rng, fold,
                   temperature, top_k, top_p):
            return _verify_impl(params, kv, tokens, pos0, tables, active,
                                rng, fold, temperature, top_k, top_p)

    if shardings is None:
        return jax.jit(verify, donate_argnums=(1,))
    sh, rep = shardings, shardings.rep
    kvsh = sh.kv_pool(cfg.n_layers - len(cross_set), quant=kv_quant)
    in_sh = (sh.params, kvsh) + (rep,) * 9
    if cross_set:
        in_sh += (sh.cross_pool(len(cross_set)), rep, rep, rep)
    return jax.jit(verify, donate_argnums=(1,),
                   in_shardings=in_sh,
                   out_shardings=(kvsh,) + (rep,) * 8)
