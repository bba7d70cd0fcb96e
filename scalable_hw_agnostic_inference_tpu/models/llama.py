"""Llama-family causal LM (Llama-3 / Mistral / DeepSeek-distill / TinyLlama).

Parity targets: the reference's ``run-llama.py`` (Llama-3-8B / Mistral-7B
generation, reference ``app/run-llama.py:21-58``) and the causal-LM side of
``deepseek_model_api.py``. The reference compiles these via optimum-neuron /
vLLM-NxD with frozen ``sequence_length`` and ``num_cores`` (reference
``app/compile-llam3.py:14-28``); here the same model is one flax module whose
forward jits at bucketed shapes, with an explicit functional KV cache so the
identical code path serves:

- full-sequence scoring (no cache),
- prefill into a preallocated cache (bucketed prompt lengths),
- single-token decode steps driven by ``lax.scan`` (`generate` below), and
- the paged-attention engine (which manages its own cache layout).

Tensor parallelism is a declarative rules table (``tp_rules``) — Megatron
column/row sharding expressed as PartitionSpecs over the ICI mesh instead of
the reference's ColumnParallelLinear/RowParallelLinear class pair (reference
``app/src/transformer/model.py:162-252``).
"""

from __future__ import annotations

import dataclasses
import functools
import importlib
import itertools
from typing import Any, Dict, List, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..ops.attention import causal_mask, dot_product_attention
from ..ops.norms import RMSNorm
from ..ops.rope import apply_rope
from ..parallel.sharding import ShardingRules
from . import convert

# A per-layer KV cache entry: {"k": [B, S, Hkv, Dh], "v": [B, S, Hkv, Dh]}
LayerCache = Dict[str, jax.Array]
Cache = List[LayerCache]


#: AFMoE's attention pattern: three window layers, then one full layer
_AFMOE_PERIOD = ("sliding_attention",) * 3 + ("full_attention",)

#: a hybrid pattern's letters (HF ``hybrid_override_pattern``): what part a
#: block IS (``block_parts``) and what its mixer is (``layer_types``)
_HYBRID_LETTERS = {"M": ("mixer", "state_space"),
                   "*": ("mixer", "full_attention"),
                   "E": ("ffn", "none"), "-": ("ffn", "none")}

#: the layer types whose per-sequence state is a SLOT's, and the recurrent
#: KIND each is (``LlamaConfig.state_kind``); the kind's ``state_shapes``
#: and two phases live in the module ``_STATE_MODULES`` names under ``ops``
_STATE_KINDS = {"linear_attention": "kda", "state_space": "ssm",
                "conv": "conv"}
_STATE_MODULES = {"kda": "kda", "ssm": "ssm", "conv": "shortconv"}

#: Nemotron-3-Nano's 52 blocks, and the first nine of them
_NEMOTRON3_PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 128256
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    mlp_dim: int = 14336
    max_seq_len: int = 8192
    rope_theta: float = 500000.0
    # HF rope_type="llama3" tuple (factor, low_freq_factor, high_freq_factor,
    # original_max_position_embeddings); None = plain rope
    rope_scaling: Optional[Tuple[float, float, float, int]] = None
    rms_eps: float = 1e-5
    tie_embeddings: bool = False
    # mllama (Llama-3.2-Vision): indices of gated cross-attention layers that
    # attend precomputed vision states instead of the token KV cache
    # (reference serves this architecture via the vLLM fork,
    # ``cova/mllama-32-11b-vllm-trn1-config.yaml``). Empty = plain llama.
    cross_attention_layers: Tuple[int, ...] = ()
    # width of one attention head; None = ``dim // n_heads`` (filled in by
    # ``__post_init__``), a number where the published config says otherwise
    head_dim: Optional[int] = None
    # lanes a head's keys and values take in the paged pool and in every
    # attention call; 0 = ``head_dim``. A head narrower than the TPU's 128
    # lanes says 128 here: q, k and v are zero-padded to it behind the head
    # norms and the rotary embedding, the softmax scale stays ``head_dim **
    # -0.5`` (``attn_scale``) and the output's pad lanes are cut. The v5e
    # stores a pool leaf whose minor dimension is 64 TRANSPOSED (blocks on
    # the lanes) and re-lays it whole before a kernel call, and Mosaic
    # refuses the paged kernel's 64-lane slice of a 128-lane tile: a
    # declared pad (as ``latent_width``'s) keeps the leaf in its own order
    head_lanes: int = 0
    # -- what a layer IS, as data (``layer_kind``): the engine's one layer
    # function reads these and no model's name --------------------------
    # per-layer attention kind, HF's names: "sliding_attention" (a window
    # of ``sliding_window`` keys), "full_attention", "linear_attention"
    # (KDA, ``ops.kda``: recurrent slot state, no cache rows),
    # "state_space" (a Mamba-2 mixer, ``ops.ssm``: the same kind of state)
    # or "conv" (a double-gated short convolution, ``ops.shortconv``: a
    # slot that holds the convolution's tail alone);
    # () = all full; "none" where the block has no mixer (``block_parts``)
    layer_types: Tuple[str, ...] = ()
    sliding_window: int = 0
    # full-attention layers carry rotary embedding (False: none at all)
    rope_on_full_attention: bool = True
    qk_norm: bool = False          # RMSNorm over each q and k head
    attn_gate: bool = False        # o * sigmoid(Wg a) before the o matrix
    sandwich_norms: bool = False   # a norm after each half too (four a layer)
    embed_scale: bool = False      # x0 = Embed[ids] * sqrt(dim)
    # routed FFN: ``n_experts`` experts of width ``moe_mlp_dim`` scored by a
    # float32 sigmoid router, ``n_experts_per_tok`` chosen on score + bias,
    # weights renormalised (``route_norm``) and scaled; ``n_shared_experts``
    # always-on experts beside them; the first ``n_dense_layers`` layers
    # keep the dense MLP of width ``mlp_dim``. 0 experts = all dense.
    n_experts: int = 0
    n_experts_per_tok: int = 0
    n_shared_experts: int = 0
    moe_mlp_dim: int = 0
    n_dense_layers: int = 0
    route_norm: bool = True
    route_scale: float = 1.0
    # -- the attention KIND, as data: ``kv_lora_rank`` > 0 is multi-head
    # latent attention. A token's cache entry is then ONE normed latent of
    # ``kv_lora_rank`` and ONE rotary key of ``qk_rope_head_dim`` shared by
    # all heads (``cache_leaves``), queries are ``head_dim`` = nope + rope
    # wide, values ``v_head_dim``; 0 = per-head keys and values
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # rotary pairs are lanes ``(2i, 2i+1)`` (HF ``rope_interleave``), not
    # the half-rotation's ``(i, i + D/2)``
    rope_interleave: bool = False
    # -- "linear_attention" layers (Kimi Delta Attention): ``kda_heads``
    # heads of ``kda_head_dim`` behind a causal depthwise convolution of
    # ``kda_conv`` taps; the decay's and the output gate's low-rank width
    # is ``kda_head_dim``. Their per-sequence state is a SLOT's
    # (``state_leaves``), constant in the context's length
    kda_heads: int = 0
    kda_head_dim: int = 0
    kda_conv: int = 4
    # the routed experts HELD here, ``(first, count)`` of the ``n_experts``
    # the router scores: this chip's share of an expert-parallel layout
    # (the stacked expert leaves are that slice); () = all of them
    experts_held: Tuple[int, ...] = ()
    # -- what a BLOCK is, as data: () = every layer is a mixer THEN a
    # feed-forward part, each behind its own norm; else, per layer,
    # "mixer" (ONE norm, the mixer ``layer_types`` names, ONE residual add)
    # or "ffn" (one norm, the routed or dense feed-forward part, one add).
    # An "ffn" block costs neither the pool nor a slot anything
    block_parts: Tuple[str, ...] = ()
    # the feed-forward parts' form, experts, shared expert and dense MLP
    # alike: "silu" is ``Down(silu(Gate x) * Up x)``, three matrices;
    # "relu2" is ``Down(relu(Up x) ** 2)``, two (no gate leaf)
    mlp_act: str = "silu"
    # the shared expert's width; 0 = ``moe_mlp_dim * n_shared_experts``
    shared_mlp_dim: int = 0
    # -- "state_space" layers (Mamba-2): ``ssm_heads`` heads of
    # ``ssm_head_dim`` channels over ``ssm_state`` state lanes, ``B`` and
    # ``C`` shared by the heads of each of ``ssm_groups`` groups, behind ONE
    # causal depthwise convolution (with bias) of ``ssm_conv`` taps over x,
    # B and C together. Their per-sequence state is a SLOT's
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state: int = 0
    ssm_groups: int = 1
    ssm_conv: int = 4
    # -- "conv" layers (a short convolution between two gates): taps of the
    # causal depthwise convolution over ``dim`` channels (HF
    # ``conv_L_cache``). Their per-sequence state is a SLOT's: the last
    # ``conv_taps - 1`` inputs of the convolution and nothing else
    conv_taps: int = 3

    def __post_init__(self):
        # sequence fields normalize to tuples so configs hash and compare
        # stably across a JSON round-trip (the weight-store metadata path)
        if not isinstance(self.cross_attention_layers, tuple):
            object.__setattr__(self, "cross_attention_layers",
                               tuple(self.cross_attention_layers))
        if (self.rope_scaling is not None
                and not isinstance(self.rope_scaling, tuple)):
            object.__setattr__(self, "rope_scaling",
                               tuple(self.rope_scaling))
        if not isinstance(self.layer_types, tuple):
            object.__setattr__(self, "layer_types", tuple(self.layer_types))
        if not isinstance(self.experts_held, tuple):
            object.__setattr__(self, "experts_held",
                               tuple(self.experts_held))
        if not isinstance(self.block_parts, tuple):
            object.__setattr__(self, "block_parts", tuple(self.block_parts))
        if self.head_dim is None:
            object.__setattr__(self, "head_dim", self.dim // self.n_heads)
        if self.layer_types and len(self.layer_types) != self.n_layers:
            raise ValueError(
                f"layer_types names {len(self.layer_types)} layers, "
                f"n_layers is {self.n_layers}")
        if self.block_parts and (
                len(self.block_parts) != self.n_layers
                or set(self.block_parts) - {"mixer", "ffn"}):
            raise ValueError(
                f"block_parts names {len(self.block_parts)} blocks of "
                f"{sorted(set(self.block_parts))}, n_layers is "
                f"{self.n_layers} and a part is 'mixer' or 'ffn'")
        if self.head_lanes and (self.head_lanes < self.head_dim
                                or self.kv_lora_rank):
            raise ValueError(
                f"head_lanes {self.head_lanes} pads per-head keys and "
                f"values of head_dim {self.head_dim}: it is no less, and "
                f"latent attention pads its own row (latent_width)")
        if self.latent and (
                self.head_dim != self.qk_nope_head_dim + self.qk_rope_head_dim
                or self.n_kv_heads != self.n_heads or not self.v_head_dim):
            raise ValueError(
                "latent attention: head_dim is qk_nope_head_dim + "
                "qk_rope_head_dim, every head has its own keys "
                "(n_kv_heads == n_heads) and v_head_dim is given")

    @property
    def latent(self) -> bool:
        """Multi-head latent attention (the attention kind)."""
        return self.kv_lora_rank > 0

    @property
    def latent_width(self) -> int:
        """Lanes of one token's latent cache entry: the latent, the shared
        rotary key behind it, and zeros up to a whole number of the TPU's
        128 lanes (the device pads a last dim to that anyway; a declared
        pad keeps every copy and product aligned)."""
        return -(-(self.kv_lora_rank + self.qk_rope_head_dim) // 128) * 128

    @property
    def kv_lanes(self) -> int:
        """Lanes of one head's keys (and values) in the pool and in the
        attention calls: ``head_lanes``, or ``head_dim`` as it is."""
        return self.head_lanes or self.head_dim

    @property
    def attn_scale(self) -> Optional[float]:
        """The softmax scale where the calls cannot read it off the lanes
        (``head_lanes``); None: each call's own ``lanes ** -0.5``."""
        return self.head_dim ** -0.5 if self.head_lanes else None

    def window_of(self, li: int) -> int:
        """Keys layer ``li``'s queries see behind them, themselves
        included; 0 = every key (plain causal)."""
        if self.layer_types and self.layer_types[li] == "sliding_attention":
            return int(self.sliding_window)
        return 0

    def rope_of(self, li: int) -> bool:
        return bool(self.window_of(li) or self.rope_on_full_attention)

    def part_of(self, li: int) -> str:
        """``"mixer"`` or ``"ffn"`` where block ``li`` is that part ALONE,
        ``""`` where it is a mixer then a feed-forward part."""
        return self.block_parts[li] if self.block_parts else ""

    def moe_of(self, li: int) -> bool:
        return (bool(self.n_experts) and li >= self.n_dense_layers
                and self.part_of(li) != "mixer")

    def kda_of(self, li: int) -> bool:
        """Layer ``li`` is linear attention: slot state, no cache rows."""
        return bool(self.layer_types) and (
            self.layer_types[li] == "linear_attention")

    def ssm_of(self, li: int) -> bool:
        """Layer ``li`` is a state-space mixer: slot state, no cache rows."""
        return bool(self.layer_types) and (
            self.layer_types[li] == "state_space")

    def conv_of(self, li: int) -> bool:
        """Layer ``li`` is a gated short convolution: slot state (the
        convolution's tail), no cache rows."""
        return bool(self.layer_types) and self.layer_types[li] == "conv"

    def state_of(self, li: int) -> bool:
        """Layer ``li`` keeps recurrent slot state, whatever its kind."""
        return bool(self.layer_types) and (
            self.layer_types[li] in _STATE_KINDS)

    @property
    def _pool_order(self) -> List[int]:
        """The layers that own an entry of the engine's per-layer state
        list, in order: every block with a mixer but the cross layers."""
        return [li for li in range(self.n_layers)
                if li not in self.cross_attention_layers
                and self.part_of(li) != "ffn"]

    @property
    def kda_layers(self) -> Tuple[int, ...]:
        """Pool indices (cross layers and blocks without a mixer own none)
        of the KDA layers: where the engine's per-layer state list holds a
        slot arena and no blocks."""
        return tuple(pi for pi, li in enumerate(self._pool_order)
                     if self.kda_of(li))

    @property
    def state_layers(self) -> Tuple[int, ...]:
        """Pool indices of every layer that keeps recurrent slot state,
        whatever its kind."""
        return tuple(pi for pi, li in enumerate(self._pool_order)
                     if self.state_of(li))

    @property
    def state_kind(self) -> str:
        """``"kda"``, ``"ssm"``, ``"conv"`` or ``""``: the recurrent kind
        of this model's slot state (a model has one)."""
        return next((_STATE_KINDS[t] for t in self.layer_types
                     if t in _STATE_KINDS), "")

    @property
    def n_paged_layers(self) -> int:
        """Layers whose tokens cost the paged pool rows."""
        return len(self._pool_order) - len(self.state_layers)

    @property
    def recurrent(self) -> bool:
        """Some layer keeps recurrent slot state."""
        return bool(self.state_layers)

    @property
    def shared_width(self) -> int:
        return self.shared_mlp_dim or self.moe_mlp_dim * self.n_shared_experts

    @property
    def held(self) -> Optional[Tuple[int, int]]:
        """``(first, count)`` of the experts held here; None = all."""
        return tuple(self.experts_held) or None

    @property
    def n_experts_held(self) -> int:
        return self.experts_held[1] if self.experts_held else self.n_experts

    @property
    def window_layers(self) -> Tuple[int, ...]:
        """Pool indices (cross layers own none) of the window layers."""
        return tuple(pi for pi, li in enumerate(self._pool_order)
                     if self.window_of(li))

    @property
    def n_moe_layers(self) -> int:
        return sum(self.moe_of(li) for li in range(self.n_layers))

    @property
    def engine_only(self) -> bool:
        """Mechanisms only ``engine.runner``'s layer function implements
        (the contiguous-cache flax module does not)."""
        return bool(self.n_experts or self.layer_types or self.qk_norm
                    or self.attn_gate or self.sandwich_norms
                    or self.embed_scale or self.latent or self.recurrent
                    or self.block_parts or self.mlp_act != "silu")

    @classmethod
    def tiny(cls) -> "LlamaConfig":
        """Deterministic CI-tier config (byte-level vocab)."""
        return cls(
            vocab_size=512, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
            mlp_dim=128, max_seq_len=256, rope_theta=10000.0,
            tie_embeddings=True,
        )

    @classmethod
    def llama3_8b(cls) -> "LlamaConfig":
        return cls()  # defaults are Llama-3-8B

    @classmethod
    def llama32_1b(cls) -> "LlamaConfig":
        """Llama-3.2-1B geometry — the reference's vLLM default model
        (``vllm_model_api.py`` ConfigMap)."""
        return cls(vocab_size=128256, dim=2048, n_layers=16, n_heads=32,
                   n_kv_heads=8, mlp_dim=8192, max_seq_len=4096,
                   rope_theta=500000.0, tie_embeddings=True)

    @classmethod
    def llama32_3b(cls) -> "LlamaConfig":
        """Llama-3.2-3B geometry — the largest Llama fitting one v5e chip
        in bf16 with KV headroom."""
        return cls(vocab_size=128256, dim=3072, n_layers=28, n_heads=24,
                   n_kv_heads=8, mlp_dim=8192, max_seq_len=4096,
                   rope_theta=500000.0, tie_embeddings=True)

    @classmethod
    def mistral_7b(cls) -> "LlamaConfig":
        """Mistral-7B-v0.3 geometry (reference serves Mistral through the
        same causal-LM server, ``app/run-llama.py`` / ``mistral/``): llama
        arch with a 32k vocab; v0.3 dropped the sliding window, so no
        attention variant is needed."""
        return cls(vocab_size=32768, dim=4096, n_layers=32, n_heads=32,
                   n_kv_heads=8, mlp_dim=14336, max_seq_len=32768,
                   rope_theta=1000000.0)

    @classmethod
    def trinity_mini(cls, layer_types: Tuple[str, ...] = (),
                     n_dense_layers: int = 2) -> "LlamaConfig":
        """Trinity-Mini (``model_type: afmoe``) geometry: 128 sigmoid-routed
        experts of width 1024 beside a shared one, three window layers
        (2048, rotary) to one full layer (no positional embedding), gated
        QK-normed heads of 128 (32 x 128 = twice the hidden size), four
        norms a layer, a 200k vocabulary. Whole (32 layers, the default) it
        is 52 GB in bf16; ``layer_types`` cuts the depth."""
        layer_types = tuple(layer_types) or _AFMOE_PERIOD * 8
        return cls(
            vocab_size=200192, dim=2048, n_layers=len(layer_types),
            n_heads=32, n_kv_heads=4, head_dim=128, mlp_dim=6144,
            max_seq_len=131072, rope_theta=10000.0, rms_eps=1e-5,
            layer_types=layer_types, sliding_window=2048,
            rope_on_full_attention=False, qk_norm=True, attn_gate=True,
            sandwich_norms=True, embed_scale=True, n_experts=128,
            n_experts_per_tok=8, n_shared_experts=1, moe_mlp_dim=1024,
            n_dense_layers=n_dense_layers, route_norm=True,
            route_scale=2.826)

    @classmethod
    def trinity_mini_stage(cls) -> "LlamaConfig":
        """One chip's pipeline stage of Trinity-Mini: the embedding, the
        head, one dense layer and one whole period of four expert layers
        (sliding, sliding, sliding, full) of the 32. Not a servable whole
        model: 8.48 GB of the 52 GB."""
        return cls.trinity_mini(_AFMOE_PERIOD[:1] + _AFMOE_PERIOD, 1)

    @classmethod
    def tiny_afmoe(cls) -> "LlamaConfig":
        """CI-tier stand-in with Trinity-Mini's mechanisms: a dense layer,
        three window layers and a full one without rotary embedding, 32
        experts top-8 beside a shared one (a flipped choice then swaps an
        eighth of the routed output, as at full size, not half), gated
        QK-normed heads wider than ``dim // n_heads``, a window (32)
        shorter than the test prompts."""
        return cls(
            vocab_size=512, dim=64, n_layers=5, n_heads=4, n_kv_heads=2,
            head_dim=32, mlp_dim=128, max_seq_len=8192, rope_theta=10000.0,
            layer_types=("sliding_attention",) * 4 + ("full_attention",),
            sliding_window=32, rope_on_full_attention=False, qk_norm=True,
            attn_gate=True, sandwich_norms=True, embed_scale=True,
            n_experts=32, n_experts_per_tok=8, n_shared_experts=1,
            moe_mlp_dim=16, n_dense_layers=1, route_norm=True,
            route_scale=2.826)

    @classmethod
    def kanana2_30b(cls, n_layers: int = 48) -> "LlamaConfig":
        """Kanana-2-30B-A3B (``model_type: deepseek_v3``) geometry: latent
        attention (a latent of 512 and one shared rotary key of 64 a token;
        32 heads of 128 + 64 against values of 128, interleaved rotary
        pairs, no q latent, no rope scaling), one leading dense layer of
        6144, then 128 sigmoid-routed experts of 768, 6 a token, scores
        renormalised and scaled by 2.448, beside 2 shared experts; a 128k
        vocabulary, untied. Whole (48 layers) it is 61 GB in bf16;
        ``n_layers`` cuts the depth."""
        return cls(
            vocab_size=128256, dim=2048, n_layers=n_layers, n_heads=32,
            n_kv_heads=32, head_dim=192, mlp_dim=6144, max_seq_len=32768,
            rope_theta=1000000.0, rms_eps=1e-6, n_experts=128,
            n_experts_per_tok=6, n_shared_experts=2, moe_mlp_dim=768,
            n_dense_layers=1, route_norm=True, route_scale=2.448,
            kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
            v_head_dim=128, rope_interleave=True)

    @classmethod
    def kanana2_stage(cls) -> "LlamaConfig":
        """One chip's pipeline stage of Kanana-2-30B-A3B: the embedding,
        the head, the leading dense layer and six expert layers of the 48,
        every expert held. Not a servable whole model: 8.86 GB of 61 GB."""
        return cls.kanana2_30b(7)

    @classmethod
    def tiny_mla(cls) -> "LlamaConfig":
        """CI-tier stand-in with Kanana-2's mechanisms: latent attention
        (a latent of 32 and a shared rotary key of 8, interleaved pairs;
        heads of 16 + 8 against values of 16), a dense layer and three
        expert layers of 16 experts top-4 beside two shared ones."""
        return cls(
            vocab_size=512, dim=64, n_layers=4, n_heads=4, n_kv_heads=4,
            head_dim=24, mlp_dim=128, max_seq_len=8192, rope_theta=10000.0,
            rms_eps=1e-6, n_experts=16, n_experts_per_tok=4,
            n_shared_experts=2, moe_mlp_dim=16, n_dense_layers=1,
            route_norm=True, route_scale=2.448, kv_lora_rank=32,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            rope_interleave=True)

    @classmethod
    def kimi_linear_48b(cls, layer_types: Tuple[str, ...] = (),
                        experts_held: Tuple[int, ...] = ()
                        ) -> "LlamaConfig":
        """Kimi-Linear-48B-A3B (``model_type: kimi_linear``) geometry: three
        KDA layers (32 heads of 128 behind a convolution of 4; a float32
        state of 32 x 128 x 128 a sequence a layer) to one MLA layer (a
        latent of 512 and one shared key of 64 a token, 32 heads of 128 +
        64 against values of 128, NO positional embedding anywhere:
        ``mla_use_nope``), one leading dense layer of 9216, then 256
        sigmoid-routed experts of 1024, 8 a token, scores renormalised and
        scaled by 2.446, beside one shared expert; a 164k vocabulary,
        untied. Whole (27 layers, the default; published 1-based layers
        4, 8, 12, 16, 20, 24 and 27 are the MLA ones) it is 98 GB in bf16;
        ``layer_types`` cuts the depth, ``experts_held`` the experts to one
        chip's share."""
        layer_types = tuple(layer_types) or tuple(
            "full_attention" if li in (4, 8, 12, 16, 20, 24, 27)
            else "linear_attention" for li in range(1, 28))
        return cls(
            vocab_size=163840, dim=2304, n_layers=len(layer_types),
            n_heads=32, n_kv_heads=32, head_dim=192, mlp_dim=9216,
            max_seq_len=1048576, rope_theta=10000.0, rms_eps=1e-5,
            layer_types=layer_types, rope_on_full_attention=False,
            n_experts=256, n_experts_per_tok=8, n_shared_experts=1,
            moe_mlp_dim=1024, n_dense_layers=1, route_norm=True,
            route_scale=2.446, kv_lora_rank=512, qk_nope_head_dim=128,
            qk_rope_head_dim=64, v_head_dim=128, kda_heads=32,
            kda_head_dim=128, kda_conv=4, experts_held=experts_held)

    @classmethod
    def kimi_linear_stage(cls) -> "LlamaConfig":
        """One chip's share of a stage of Kimi-Linear-48B-A3B: the
        embedding, the head, the leading dense layer (published layer 1,
        KDA) and one whole period (published layers 5-8: KDA, KDA, KDA,
        MLA), the expert layers divided over TWO chips by experts: 128 of
        the 256 held here. Not a servable whole model: 9.32 GB of 98 GB."""
        return cls.kimi_linear_48b(
            ("linear_attention",) * 4 + ("full_attention",), (0, 128))

    @classmethod
    def tiny_kda(cls) -> "LlamaConfig":
        """CI-tier stand-in with Kimi-Linear's mechanisms in the cut's
        pattern: a dense KDA layer, three KDA expert layers and one MLA
        expert layer without positional embedding; KDA heads of 16 behind
        a convolution of 4; 16 experts top-8 beside a shared one, 8 of
        them held here (8 a token as published: at 4 a flipped choice
        carries a quarter of the routed output and the stand-in's rounding
        floor doubles)."""
        return cls(
            vocab_size=512, dim=64, n_layers=5, n_heads=4, n_kv_heads=4,
            head_dim=24, mlp_dim=128, max_seq_len=8192, rope_theta=10000.0,
            rms_eps=1e-5,
            layer_types=("linear_attention",) * 4 + ("full_attention",),
            rope_on_full_attention=False, n_experts=16, n_experts_per_tok=8,
            n_shared_experts=1, moe_mlp_dim=16, n_dense_layers=1,
            route_norm=True, route_scale=2.446, kv_lora_rank=32,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            kda_heads=4, kda_head_dim=16, kda_conv=4, experts_held=(0, 8))

    @classmethod
    def hybrid(cls, pattern: str, **kw) -> "LlamaConfig":
        """A model whose blocks are ONE part each, by a pattern's letters
        (HF ``hybrid_override_pattern``): ``M`` a state-space mixer, ``*``
        attention, ``E`` (or ``-``) a feed-forward part."""
        parts, kinds = zip(*(_HYBRID_LETTERS[c] for c in pattern))
        return cls(n_layers=len(pattern), block_parts=parts,
                   layer_types=kinds, **kw)

    @classmethod
    def nemotron3_nano(cls, pattern: str = _NEMOTRON3_PATTERN,
                       experts_held: Tuple[int, ...] = ()) -> "LlamaConfig":
        """NVIDIA-Nemotron-3-Nano-30B-A3B (``model_type: nemotron_h``)
        geometry: 52 blocks of ONE part each (one norm, one residual add):
        23 Mamba-2 mixers (64 heads of 64 over 128 state lanes, 8 groups of
        B and C, a convolution of 4 with bias: a float32 state of 64 x 64 x
        128 a sequence a block), 6 attention blocks (32 query heads over 2
        key/value heads of 128, NO positional embedding) and 23 routed
        blocks (128 sigmoid-routed experts of TWO matrices 2688 x 1856 with
        ``relu ** 2`` between, 6 a token, renormalised and scaled by 2.5,
        beside one shared expert of 3712); a 131k vocabulary, untied. Whole
        it is 63 GB in bf16; ``pattern`` cuts the depth, ``experts_held``
        the experts to one chip's share."""
        return cls.hybrid(
            pattern, vocab_size=131072, dim=2688, n_heads=32, n_kv_heads=2,
            head_dim=128, mlp_dim=1856, max_seq_len=262144,
            rope_theta=10000.0, rms_eps=1e-5, rope_on_full_attention=False,
            n_experts=128, n_experts_per_tok=6, n_shared_experts=1,
            moe_mlp_dim=1856, shared_mlp_dim=3712, route_norm=True,
            route_scale=2.5, mlp_act="relu2", ssm_heads=64, ssm_head_dim=64,
            ssm_state=128, ssm_groups=8, ssm_conv=4,
            experts_held=experts_held)

    @classmethod
    def nemotron3_nano_stage(cls) -> "LlamaConfig":
        """One chip's share of a stage of Nemotron-3-Nano-30B-A3B: the
        embedding, the head and the model's first nine blocks (``MEMEM*EME``:
        four mixers, four routed blocks, one attention block), the routed
        blocks divided over TWO chips by experts: 64 of the 128 held here.
        Not a servable whole model: 7.0 GB of 63 GB."""
        return cls.nemotron3_nano(_NEMOTRON3_PATTERN[:9], (0, 64))

    @classmethod
    def tiny_ssm(cls) -> "LlamaConfig":
        """CI-tier stand-in with Nemotron-3-Nano's mechanisms in the cut's
        pattern (``MEMEM*EME``): blocks of one part each, state-space
        mixers of 4 heads of 16 over 32 state lanes in 2 groups behind a
        convolution of 4, one attention block of 4 heads over 2 without
        positional embedding, 16 two-matrix ``relu ** 2`` experts top-6
        beside a shared one of twice their width, 8 of them held here."""
        return cls.hybrid(
            _NEMOTRON3_PATTERN[:9], vocab_size=512, dim=64, n_heads=4,
            n_kv_heads=2, head_dim=32, mlp_dim=16, max_seq_len=8192,
            rope_theta=10000.0, rms_eps=1e-5, rope_on_full_attention=False,
            n_experts=16, n_experts_per_tok=6, n_shared_experts=1,
            moe_mlp_dim=16, shared_mlp_dim=32, route_norm=True,
            route_scale=2.5, mlp_act="relu2", ssm_heads=4, ssm_head_dim=16,
            ssm_state=32, ssm_groups=2, ssm_conv=4, experts_held=(0, 8))

    @classmethod
    def lfm2_24b(cls, layer_types: Tuple[str, ...] = (),
                 n_dense_layers: int = 2) -> "LlamaConfig":
        """LFM2-24B-A2B (``model_type: lfm2_moe``) geometry: 40 layers, 30
        of them a double-gated short convolution (``out(C * conv3(B *
        x))``: no attention, no scan; a slot's state is the last two inputs
        of the convolution, 2 x 2048 values) and every fourth from the third
        on attention (32 query heads over 8 key/value heads of 64,
        QK-normed, rotary at theta 1e6, cached on 128 lanes:
        ``head_lanes``); two leading dense layers of 11,776, then 64
        sigmoid-routed experts of 1536, 4 a token, scores renormalised,
        scaled by 1, no shared expert; a 65k vocabulary, the head tied to
        the embedding. Whole it is 48 GB in bf16; ``layer_types`` and
        ``n_dense_layers`` cut the depth."""
        layer_types = tuple(layer_types) or tuple(
            "full_attention" if li % 4 == 2 else "conv" for li in range(40))
        return cls(
            vocab_size=65536, dim=2048, n_layers=len(layer_types),
            n_heads=32, n_kv_heads=8, head_dim=64, head_lanes=128,
            mlp_dim=11776, max_seq_len=128000, rope_theta=1000000.0,
            rms_eps=1e-5, tie_embeddings=True, layer_types=layer_types,
            qk_norm=True, n_experts=64, n_experts_per_tok=4, moe_mlp_dim=1536,
            n_dense_layers=n_dense_layers, route_norm=True, route_scale=1.0,
            conv_taps=3)

    @classmethod
    def lfm2_24b_stage(cls) -> "LlamaConfig":
        """One chip's pipeline stage of LFM2-24B-A2B: the embedding (the
        tied head), ONE leading dense layer and the model's layers 1-9
        (conv, attention, conv, conv, conv, attention, conv, conv, conv:
        two whole periods of four behind the dense layer), every expert of
        a layer held. Not a servable whole model: 10.4 GB of 48 GB."""
        return cls.lfm2_24b(cls.lfm2_24b().layer_types[1:10], 1)

    @classmethod
    def tiny_lfm2(cls) -> "LlamaConfig":
        """CI-tier stand-in with LFM2-24B-A2B's mechanisms in the cut's
        pattern: a dense conv layer, then two periods of attention (4
        QK-normed rotary heads of 16 over 2, cached on 32 lanes) and three
        conv layers of 3 taps, 16 sigmoid-routed experts of 16 top-4 with
        no shared expert, every one held, the head tied to the embedding."""
        return cls(
            vocab_size=512, dim=64, n_layers=9, n_heads=4, n_kv_heads=2,
            head_dim=16, head_lanes=32, mlp_dim=128, max_seq_len=8192,
            rope_theta=1000000.0, rms_eps=1e-5, tie_embeddings=True,
            layer_types=cls.lfm2_24b().layer_types[1:10], qk_norm=True,
            n_experts=16, n_experts_per_tok=4, moe_mlp_dim=16,
            n_dense_layers=1, route_norm=True, route_scale=1.0, conv_taps=3)

    @classmethod
    def llama3_70b(cls) -> "LlamaConfig":
        """Llama-3-70B / DeepSeek-R1-Distill-Llama-70B geometry — the
        reference's biggest deployment (TP=32,
        ``compile-vllm-job.yaml:49-55``)."""
        return cls(dim=8192, n_layers=80, n_heads=64, n_kv_heads=8,
                   mlp_dim=28672)

    @classmethod
    def mllama_11b_text(cls) -> "LlamaConfig":
        """Llama-3.2-11B-Vision text tower: 40 layers, 8 of them gated
        cross-attention (``cova/mllama-32-11b-vllm-trn1-config.yaml``)."""
        return cls(dim=4096, n_layers=40, n_heads=32, n_kv_heads=8,
                   mlp_dim=14336, max_seq_len=131072,
                   cross_attention_layers=(3, 8, 13, 18, 23, 28, 33, 38))

    @classmethod
    def from_hf(cls, hf) -> "LlamaConfig":
        if "conv" in (getattr(hf, "layer_types", None) or ()):
            # the ``lfm2_moe`` keys: gated short convolutions beside
            # QK-normed attention, routed experts behind the dense layers
            return cls(
                vocab_size=hf.vocab_size, dim=hf.hidden_size,
                n_layers=hf.num_hidden_layers,
                n_heads=hf.num_attention_heads,
                n_kv_heads=hf.num_key_value_heads,
                mlp_dim=hf.intermediate_size,
                max_seq_len=hf.max_position_embeddings,
                rope_theta=float(hf.rope_parameters["rope_theta"]),
                rms_eps=hf.norm_eps,
                tie_embeddings=getattr(hf, "tie_word_embeddings", True),
                layer_types=tuple(hf.layer_types), qk_norm=True,
                head_lanes=-(-(hf.hidden_size // hf.num_attention_heads)
                             // 128) * 128,
                n_experts=hf.num_experts,
                n_experts_per_tok=hf.num_experts_per_tok,
                moe_mlp_dim=hf.moe_intermediate_size,
                n_dense_layers=hf.num_dense_layers,
                route_norm=bool(hf.norm_topk_prob),
                route_scale=float(hf.routed_scaling_factor),
                conv_taps=hf.conv_L_cache)
        return cls(
            vocab_size=hf.vocab_size,
            dim=hf.hidden_size,
            n_layers=hf.num_hidden_layers,
            n_heads=hf.num_attention_heads,
            n_kv_heads=getattr(hf, "num_key_value_heads", hf.num_attention_heads),
            mlp_dim=hf.intermediate_size,
            max_seq_len=getattr(hf, "max_position_embeddings", 8192),
            rope_theta=getattr(hf, "rope_theta", 10000.0),
            rope_scaling=rope_scaling_from_hf(getattr(hf, "rope_scaling", None)),
            rms_eps=getattr(hf, "rms_norm_eps", 1e-5),
            tie_embeddings=getattr(hf, "tie_word_embeddings", False),
            cross_attention_layers=tuple(
                getattr(hf, "cross_attention_layers", None) or ()),
        )


def rope_scaling_from_hf(rs) -> Optional[Tuple[float, float, float, int]]:
    """HF ``config.rope_scaling`` dict → the llama3 scaling tuple."""
    if not rs:
        return None
    rope_type = rs.get("rope_type", rs.get("type", "default"))
    if rope_type == "default":
        return None
    if rope_type != "llama3":
        raise ValueError(f"unsupported rope_scaling type {rope_type!r}")
    return (float(rs["factor"]), float(rs["low_freq_factor"]),
            float(rs["high_freq_factor"]),
            int(rs["original_max_position_embeddings"]))


def _dense_factory(dtype, quant: bool):
    """Projection factory: ``nn.Dense`` or its int8 weight-only drop-in
    (``ops.quant.QuantDense``) — same call signature, different param tree
    (kernel_q + scale), produced by ``ops.quant.quantize_params_tree``."""
    if quant:
        from ..ops.quant import QuantDense

        return lambda n_out, name: QuantDense(n_out, dtype=dtype, name=name)
    return lambda n_out, name: nn.Dense(
        n_out, use_bias=False, dtype=dtype, name=name)


class LlamaAttention(nn.Module):
    cfg: LlamaConfig
    dtype: Any = jnp.bfloat16
    attn_impl: str = "auto"
    quant: bool = False

    @nn.compact
    def __call__(
        self,
        x: jax.Array,                       # [B, T, dim]
        positions: jax.Array,               # [B, T] int32
        layer_cache: Optional[LayerCache],  # slots [B, S, Hkv, Dh] or None
        mask: Optional[jax.Array],          # [B, 1, T, S] bool or None
        write_index: Optional[jax.Array],   # scalar slot for cache writes
    ) -> Tuple[jax.Array, Optional[LayerCache]]:
        cfg = self.cfg
        B, T, _ = x.shape
        Dh = cfg.head_dim
        dense = _dense_factory(self.dtype, self.quant)
        q = dense(cfg.n_heads * Dh, "q")(x).reshape(B, T, cfg.n_heads, Dh)
        k = dense(cfg.n_kv_heads * Dh, "k")(x).reshape(B, T, cfg.n_kv_heads, Dh)
        v = dense(cfg.n_kv_heads * Dh, "v")(x).reshape(B, T, cfg.n_kv_heads, Dh)
        q = apply_rope(q, positions, cfg.rope_theta, cfg.rope_scaling)
        k = apply_rope(k, positions, cfg.rope_theta, cfg.rope_scaling)

        if layer_cache is None:
            # full-sequence scoring: attend within the (masked) sequence
            o = dot_product_attention(
                q, k, v, mask=mask, causal=mask is None, impl=self.attn_impl
            )
            new_cache = None
        else:
            # write new k/v into slots [write_index : write_index+T], attend
            # over the whole slot buffer with the caller-built validity mask
            idx = jnp.asarray(write_index, jnp.int32)
            kc = jax.lax.dynamic_update_slice(
                layer_cache["k"], k.astype(layer_cache["k"].dtype), (0, idx, 0, 0)
            )
            vc = jax.lax.dynamic_update_slice(
                layer_cache["v"], v.astype(layer_cache["v"].dtype), (0, idx, 0, 0)
            )
            o = dot_product_attention(q, kc, vc, mask=mask, impl=self.attn_impl)
            new_cache = {"k": kc, "v": vc}
        o = o.reshape(B, T, cfg.n_heads * Dh)
        return dense(cfg.dim, "o")(o), new_cache


class LlamaMLP(nn.Module):
    cfg: LlamaConfig
    dtype: Any = jnp.bfloat16
    quant: bool = False

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        cfg = self.cfg
        dense = _dense_factory(self.dtype, self.quant)
        gate = dense(cfg.mlp_dim, "gate")(x)
        up = dense(cfg.mlp_dim, "up")(x)
        return dense(cfg.dim, "down")(nn.silu(gate) * up)


class LlamaBlock(nn.Module):
    cfg: LlamaConfig
    dtype: Any = jnp.bfloat16
    attn_impl: str = "auto"
    quant: bool = False

    @nn.compact
    def __call__(self, x, positions, layer_cache, mask, write_index):
        cfg = self.cfg
        norm = lambda name: RMSNorm(eps=cfg.rms_eps, dtype=self.dtype, name=name)
        h, new_cache = LlamaAttention(
            cfg, dtype=self.dtype, attn_impl=self.attn_impl, quant=self.quant,
            name="attn"
        )(norm("attn_norm")(x), positions, layer_cache, mask, write_index)
        x = x + h
        x = x + LlamaMLP(cfg, dtype=self.dtype, quant=self.quant, name="mlp")(
            norm("mlp_norm")(x))
        return x, new_cache


class LlamaForCausalLM(nn.Module):
    """Decoder-only LM. Returns ``(logits, new_cache)``.

    ``cache=None`` → plain causal forward (scoring / perplexity path).
    With a cache, the caller supplies ``mask`` over all cache slots and the
    scalar ``write_index`` where this call's T tokens land.
    """

    cfg: LlamaConfig
    dtype: Any = jnp.bfloat16
    attn_impl: str = "auto"
    # int8 weight-only serving (params via ops.quant.quantize_params_tree)
    quant: bool = False

    @nn.compact
    def __call__(
        self,
        ids: jax.Array,                   # [B, T] int32
        positions: Optional[jax.Array] = None,
        cache: Optional[Cache] = None,
        mask: Optional[jax.Array] = None,
        write_index: Optional[jax.Array] = None,
    ) -> Tuple[jax.Array, Optional[Cache]]:
        cfg = self.cfg
        if cfg.cross_attention_layers or cfg.engine_only:
            raise ValueError(
                "mllama configs (cross_attention_layers) and configs with "
                "experts, window layers, head norms, an output gate, "
                "sandwich norms or latent attention run through the paged "
                "engine (engine.runner), not the contiguous-cache flax path")
        B, T = ids.shape
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
        embed = nn.Embed(
            cfg.vocab_size, cfg.dim, dtype=self.dtype,
            param_dtype=jnp.float32, name="embed",
        )
        x = embed(ids)
        new_cache: Optional[Cache] = [] if cache is not None else None
        for i in range(cfg.n_layers):
            x, lc = LlamaBlock(
                cfg, dtype=self.dtype, attn_impl=self.attn_impl,
                quant=self.quant, name=f"layer_{i}"
            )(x, positions, cache[i] if cache is not None else None, mask, write_index)
            if new_cache is not None:
                new_cache.append(lc)
        x = RMSNorm(eps=cfg.rms_eps, dtype=self.dtype, name="final_norm")(x)
        if cfg.tie_embeddings:
            logits = embed.attend(x.astype(jnp.float32))
        else:
            logits = _dense_factory(self.dtype, self.quant)(
                cfg.vocab_size, "lm_head")(x)
        return logits.astype(jnp.float32), new_cache


def init_cache(
    cfg: LlamaConfig, batch: int, seq: int, dtype=jnp.bfloat16
) -> Cache:
    """Preallocated contiguous KV cache: ``seq`` slots per layer."""
    shape = (batch, seq, cfg.n_kv_heads, cfg.head_dim)
    return [
        {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
        for _ in range(cfg.n_layers)
    ]


def prefill_mask(token_valid: jax.Array, n_slots: int) -> jax.Array:
    """[B, Tp] validity → [B, 1, Tp, S] prefill attention mask.

    Query t attends cache slots j <= t that hold valid prompt tokens; slots
    beyond the prompt bucket are still empty and masked out.
    """
    B, Tp = token_valid.shape
    cm = causal_mask(Tp, n_slots, offset=0)            # [1,1,Tp,S]
    slot_valid = jnp.zeros((B, n_slots), bool).at[:, :Tp].set(token_valid.astype(bool))
    return jnp.logical_and(cm, slot_valid[:, None, None, :])


def decode_mask(slot_valid: jax.Array) -> jax.Array:
    """[B, S] slot validity → [B, 1, 1, S] decode-step attention mask."""
    return slot_valid[:, None, None, :]


# ---------------------------------------------------------------------------
# Tensor-parallel sharding rules (Megatron column/row over the "tp" mesh axis)
# ---------------------------------------------------------------------------

def tp_rules(axis: str = "tp") -> ShardingRules:
    """TP plan: attention heads and MLP width split over ``axis``.

    q/k/v and gate/up kernels ``[in, out]`` are column-parallel (out split);
    o and down are row-parallel (in split, XLA inserts the psum); embedding
    and lm_head split the vocab-free dim so logits come back vocab-sharded
    only when lm_head is column-split — we keep embed replicated-on-vocab,
    split on feature, which keeps token gathers local.
    """
    return ShardingRules([
        (r"embed/embedding", P(None, axis)),
        # `kernel` patterns match `kernel_q` too (search semantics) — the
        # int8 kernel shards exactly like its float original; the [out]
        # per-channel scale splits with column-parallel outputs and stays
        # replicated after row-parallel psums
        (r"attn/(q|k|v)/kernel", P(None, axis)),
        (r"attn/(q|k|v)/scale", P(axis)),
        (r"attn/o/kernel", P(axis, None)),
        (r"mlp/(gate|up)/kernel", P(None, axis)),
        (r"mlp/(gate|up)/scale", P(axis)),
        (r"mlp/down/kernel", P(axis, None)),
        (r"lm_head/kernel", P(None, axis)),
        (r"lm_head/scale", P(axis)),
        (r".*norm/scale", P()),
    ])


def cache_leaves(cfg: LlamaConfig, li: Optional[int] = None
                 ) -> Dict[str, Tuple[int, ...]]:
    """What ONE token costs the paged pool in ONE layer, by leaf: the
    shape behind ``[num_blocks, block_size]``. Per-head keys and values,
    or — latent attention — one leaf ``c`` of ``latent_width`` lanes: the
    normed latent, the shared rotary key, zeros to a lane multiple. ``li``
    names the layer: a KDA or state-space layer costs the pool nothing
    (``{}``; what it costs a SLOT is ``state_leaves``), and so does a block
    that is a feed-forward part alone. ``None``: a layer that has rows."""
    if li is not None and (cfg.state_of(li) or cfg.part_of(li) == "ffn"):
        return {}
    if cfg.latent:
        return {"c": (cfg.latent_width,)}
    return {"k": (cfg.n_kv_heads, cfg.kv_lanes),
            "v": (cfg.n_kv_heads, cfg.kv_lanes)}


def state_leaves(cfg: LlamaConfig, li: Optional[int] = None
                 ) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    """What ONE slot costs in ONE recurrent layer, by leaf: ``(shape,
    dtype)`` behind ``[slots]``, as the model's recurrent KIND says
    (``state_shapes`` of the kind's module under ``ops``:
    ``_STATE_MODULES``); ``{}`` for a model with no such layer, and for
    layer ``li`` where it is none."""
    if not cfg.recurrent or (li is not None and not cfg.state_of(li)):
        return {}
    ops = importlib.import_module(
        "..ops." + _STATE_MODULES[cfg.state_kind], __package__)
    return ops.state_shapes(cfg)


def cache_specs(
    cfg: LlamaConfig, axis: str = "tp", axis_size: int = 1
) -> Dict[str, P]:
    """The pool's leaves (``cache_leaves``) sharded over kv heads (dim 2)
    when they have that axis and it divides, else replicated."""
    out = {}
    for name, per in cache_leaves(cfg).items():
        heads = len(per) == 2 and per[0] == cfg.n_kv_heads
        out[name] = (P(None, None, axis, None)
                     if heads and axis_size > 1
                     and cfg.n_kv_heads % axis_size == 0 else P())
    return out


# ---------------------------------------------------------------------------
# HF torch → flax conversion
# ---------------------------------------------------------------------------

def params_from_torch(model_or_sd, cfg: LlamaConfig) -> Dict[str, Any]:
    """Map an HF ``LlamaForCausalLM``-family state dict onto our tree."""
    sd = convert.state_dict_of(model_or_sd)
    pfx = "model." if any(k.startswith("model.") for k in sd) else ""
    tree: Dict[str, Any] = {
        "embed": convert.embedding(sd, f"{pfx}embed_tokens"),
        "final_norm": {"scale": convert.t2j(sd[f"{pfx}norm.weight"])},
    }
    for i in range(cfg.n_layers):
        lp = f"{pfx}layers.{i}"
        layer: Dict[str, Any] = {
            "mlp": {
                "gate": convert.linear(sd, f"{lp}.mlp.gate_proj"),
                "up": convert.linear(sd, f"{lp}.mlp.up_proj"),
                "down": convert.linear(sd, f"{lp}.mlp.down_proj"),
            },
            "attn_norm": {"scale": convert.t2j(sd[f"{lp}.input_layernorm.weight"])},
            "mlp_norm": {
                "scale": convert.t2j(sd[f"{lp}.post_attention_layernorm.weight"])
            },
        }
        if i in cfg.cross_attention_layers:
            # mllama gated cross-attention layer (HF MllamaCrossAttentionDecoderLayer)
            layer["cross_attn"] = {
                "q": convert.linear(sd, f"{lp}.cross_attn.q_proj"),
                "k": convert.linear(sd, f"{lp}.cross_attn.k_proj"),
                "v": convert.linear(sd, f"{lp}.cross_attn.v_proj"),
                "o": convert.linear(sd, f"{lp}.cross_attn.o_proj"),
                "q_norm": {"scale": convert.t2j(sd[f"{lp}.cross_attn.q_norm.weight"])},
                "k_norm": {"scale": convert.t2j(sd[f"{lp}.cross_attn.k_norm.weight"])},
            }
            layer["gate_attn"] = convert.t2j(sd[f"{lp}.cross_attn_attn_gate"])
            layer["gate_mlp"] = convert.t2j(sd[f"{lp}.cross_attn_mlp_gate"])
        else:
            layer["attn"] = {
                "q": convert.linear(sd, f"{lp}.self_attn.q_proj"),
                "k": convert.linear(sd, f"{lp}.self_attn.k_proj"),
                "v": convert.linear(sd, f"{lp}.self_attn.v_proj"),
                "o": convert.linear(sd, f"{lp}.self_attn.o_proj"),
            }
        tree[f"layer_{i}"] = layer
    if not cfg.tie_embeddings:
        tree["lm_head"] = convert.linear(sd, "lm_head")
    return {"params": tree}


#: latent attention's seeded leaves, as multiples of the tier's standard
#: deviation. Seeded weights at one deviation give an attention that is an
#: even average over thousands of keys: a hundredth of the residual stream,
#: the same vector in every row, so a broken rotary embedding or softmax
#: scale would pass any comparison and every row of a batch would decode,
#: and route, alike. A query ten times larger puts the scores' deviation
#: near 4, where a few keys carry most of a softmax over some thousand (as
#: in a trained model), and what the heads return is then as large as what
#: the MLP does; a latent projection at half makes the latent's norm (it
#: doubles it) a part of the result. Other architectures' draws are theirs.
LATENT_Q_GAIN = 10.0
LATENT_KVA_GAIN = 0.5

#: KDA's seeded leaves that are not N(0, std). The three depthwise
#: convolutions are drawn at the deviation of a depthwise ``Conv1d`` of 4
#: taps as the public code initialises it (uniform on +-0.5: 0.29): at the
#: tier's 0.02 a convolution's output is 0.04 of its input, ``v`` and with
#: it ``o`` so small that the per-head norm's epsilon, not ``o``, sets the
#: layer's output. ``A_log`` and ``dt_bias`` are drawn as the public
#: initialisation draws them: ``A`` uniform in (1, 16) a head,
#: ``softplus(dt_bias)`` log-uniform in (0.001, 0.1) a channel, so a
#: channel remembers from one token to some hundreds.
KDA_CONV_STD = 0.29
KDA_A_RANGE = (1.0, 16.0)
KDA_DT_RANGE = (1e-3, 1e-1)

#: a state-space mixer's seeded leaves that are not N(0, std), as the public
#: ``modeling_nemotron_h.py`` / ``mamba_ssm`` initialisation draws them: the
#: depthwise convolution's taps and bias uniform on +-0.5 (a ``Conv1d`` of
#: 4 taps a channel: +-1/sqrt(4)); ``A`` = 1 .. heads (``A_log`` its log);
#: ``softplus(dt_bias)`` log-uniform in (``time_step_min``,
#: ``time_step_max``) and never under ``time_step_floor``; ``D`` = 1. A head
#: then remembers from a fraction of a token (A 64, dt 0.1) to a thousand
#: (A 1, dt 0.001).
SSM_CONV_RANGE = (-0.5, 0.5)
SSM_DT_RANGE = (1e-3, 1e-1)
SSM_DT_FLOOR = 1e-4

def conv_tap_range(taps: int) -> Tuple[float, float]:
    """The range a gated short convolution's seeded taps are drawn from, as
    the public code's depthwise ``nn.Conv1d`` draws them by default: uniform
    on +-1 / sqrt(taps) (0.577 at 3 taps). At the tier's 0.02 the
    convolution would return a fiftieth of its input and the layer nothing
    beside the residual stream, so a reversed or dropped tap would pass any
    comparison."""
    return (-taps ** -0.5, taps ** -0.5)


#: the ``down`` leaf of a two-matrix ``relu ** 2`` part, as a multiple of the
#: tier's deviation. At one deviation throughout, ``relu(u) ** 2`` of a
#: unit-deviation ``u`` has a fourth-moment-sized second moment (1.5 against
#: a gated part's 0.17 for ``silu(g) * u``), so an expert returns five times
#: what a gated expert of the other routed configurations returns (an RMS of
#: 1.1 of the stream's 4 at 2688 x 1856) and ONE flipped choice of six (the
#: bfloat16 stream against the float32 reference, at a near-tie of two
#: scores) moves the stream by a sixth: the reference check's largest
#: difference then read 0.6-1.2 on sixteen weight seeds and 1.95, over its
#: bound, on a seventeenth (PERF.md section 6, PR 45). A quarter gives an
#: expert's output the size a gated expert's has (0.28), as a trained
#: model's experts are small beside its residual stream. Speed does not
#: depend on it.
RELU2_DOWN_GAIN = 0.25

#: geometry-tier weight statistics: float kernels ~ N(0, GEOMETRY_STD);
#: int8 kernels uniform on [-127, 127] under ONE constant per-channel scale
#: chosen so the dequantized weights have the same standard deviation
GEOMETRY_STD = 0.02
_GEOMETRY_INT8_SCALE = GEOMETRY_STD / (127.0 * 2 / 12 ** 0.5)


@functools.partial(jax.jit,
                   static_argnames=("shape", "dtype", "sharding", "std"))
def _geometry_leaf(key, *, shape, dtype, sharding, std=GEOMETRY_STD):
    """One seeded weight leaf, generated in its final dtype under its final
    sharding: each device computes only its own shard, and nothing wider
    than the leaf's own dtype outlives the call."""
    if dtype == jnp.int8:
        w = jax.random.randint(key, shape, -127, 128, jnp.int8)
    else:
        w = (std * jax.random.normal(key, shape, jnp.float32)
             ).astype(dtype)
    return w if sharding is None else jax.lax.with_sharding_constraint(
        w, sharding)


def geometry_params(cfg: LlamaConfig, dtype=jnp.bfloat16,
                    quant: bool = False, seed: int = 0,
                    mesh=None) -> Dict[str, Any]:
    """Shape-exact SEEDED param tree for the geometry tier.

    Mirrors :func:`params_from_torch`'s tree (incl. mllama cross layers).
    Every leaf is born on the device(s) in its final form: no host copy of N
    billion floats, kernels int8 at birth with ``quant`` (never a
    full-precision transient, so an int8 7B stays under one chip's HBM at
    every instant), and with ``mesh`` each leaf is created under its
    :func:`tp_rules` sharding so no chip ever holds more than its shard.
    Values are random but reproducible from ``seed`` — a broken kernel and a
    correct one no longer answer alike, as they did over zero weights.
    Decode cost is weight-value-independent, so throughput numbers are real;
    outputs are meaningless text.
    """
    if cfg.n_experts and (quant or mesh is not None):
        raise ValueError(
            "expert layers have no int8 weights and no sharding plan yet "
            "(quantization: int8 / tensor_parallel_size > 1 with experts)")
    if cfg.latent and (quant or mesh is not None):
        raise ValueError(
            "latent attention has no int8 weights and no sharding plan yet "
            "(quantization: int8 / tensor_parallel_size > 1 with a latent "
            "cache)")
    if cfg.recurrent and (quant or mesh is not None):
        raise ValueError(
            "recurrent layers have no int8 weights and no sharding plan yet "
            "(quantization: int8 / tensor_parallel_size > 1 with recurrent "
            "state)")
    D, HD = cfg.dim, cfg.head_dim
    q_out, kv_out = cfg.n_heads * HD, cfg.n_kv_heads * HD
    rules = tp_rules()
    root = jax.random.PRNGKey(seed)
    n_leaf = itertools.count()

    def sharding_of(path: str, ndim: int):
        if mesh is None:
            return None
        return NamedSharding(mesh, rules.spec_for(path, ndim=ndim))

    # float32 leaves are the CI-sized stand-ins': unit fan-in scale (at
    # 0.02 a width of 64 gives logits too flat for a broken layer to show)
    std = D ** -0.5 if jnp.dtype(dtype) == jnp.float32 else GEOMETRY_STD

    def rand(path: str, shape, dt, gain: float = 1.0, at: float = 0.0):
        return _geometry_leaf(
            jax.random.fold_in(root, next(n_leaf)), shape=tuple(shape),
            dtype=jnp.dtype(dt), sharding=sharding_of(path, len(shape)),
            std=at or std * gain)

    def uniform(shape, lo: float, hi: float):
        return jax.random.uniform(jax.random.fold_in(root, next(n_leaf)),
                                  shape, jnp.float32, lo, hi)

    def const(path: str, shape, value, dt):
        sh = sharding_of(path, len(shape))
        return jnp.full(shape, value, dt, device=sh)

    def lin(path: str, i: int, o: int, gain: float = 1.0):
        if quant:
            return {"kernel_q": rand(f"{path}/kernel_q", (i, o), jnp.int8),
                    "scale": const(f"{path}/scale", (o,),
                                   _GEOMETRY_INT8_SCALE, jnp.float32)}
        return {"kernel": rand(f"{path}/kernel", (i, o), dtype, gain)}

    def norm(path: str, n: int = D):
        return {"scale": const(f"{path}/scale", (n,), 1.0, dtype)}

    tree: Dict[str, Any] = {
        "embed": {"embedding": rand("embed/embedding",
                                    (cfg.vocab_size, D), dtype)},
        "final_norm": norm("final_norm"),
    }
    # a gated part has three matrices, a "relu2" part two (no gate)
    firsts = ("up",) if cfg.mlp_act == "relu2" else ("gate", "up")

    down_gain = RELU2_DOWN_GAIN if cfg.mlp_act == "relu2" else 1.0

    def mlp(path: str, width: int):
        return {**{n: lin(f"{path}/{n}", D, width) for n in firsts},
                "down": lin(f"{path}/down", width, D, down_gain)}

    # the router scores all E experts; the stacked leaves are the held ones
    E, F, Eh = cfg.n_experts, cfg.moe_mlp_dim, cfg.n_experts_held
    for i in range(cfg.n_layers):
        lp = f"layer_{i}"
        part = cfg.part_of(i)
        # a block of one part has ONE norm; a layer of two, one before each
        layer: Dict[str, Any] = {"norm": norm(f"{lp}/norm")} if part else {
            "attn_norm": norm(f"{lp}/attn_norm"),
            "mlp_norm": norm(f"{lp}/mlp_norm"),
        }
        if cfg.sandwich_norms:
            layer["post_attn_norm"] = norm(f"{lp}/post_attn_norm")
            layer["post_mlp_norm"] = norm(f"{lp}/post_mlp_norm")
        if part == "mixer":
            pass                                  # no feed-forward part
        elif cfg.moe_of(i):
            # a layer's experts are stacked leaves; the router and the
            # bias that only selects stay float32
            mo = f"{lp}/moe"
            layer["moe"] = {
                "router": {"kernel": rand(f"{mo}/router/kernel", (D, E),
                                          jnp.float32)},
                "bias": rand(f"{mo}/bias", (E,), jnp.float32),
                # a gated expert's first matrices are stacked ``[E, D, F]``;
                # an ungated one's ``up`` BY ROWS, ``[E, F, D]`` as ``down``
                # (``ops.pallas.moe_ffn.first_products`` says why)
                "experts": {
                    **{n: rand(f"{mo}/experts/{n}",
                               (Eh, D, F) if len(firsts) == 2 else (Eh, F, D),
                               dtype) for n in firsts},
                    "down": rand(f"{mo}/experts/down", (Eh, F, D), dtype,
                                 down_gain)},
                # no ``shared`` leaf where the model has no shared expert
                **({"shared": mlp(f"{mo}/shared", cfg.shared_width)}
                   if cfg.n_shared_experts else {}),
            }
        else:
            layer["mlp"] = mlp(f"{lp}/mlp", cfg.mlp_dim)
        if part == "ffn":
            pass                                  # no mixer
        elif cfg.ssm_of(i):
            # the public names: in_proj (z | x B C | dt), conv1d and its
            # bias, dt_bias, A_log, D, the gated norm, out_proj
            at, SH = f"{lp}/attn", cfg.ssm_heads
            inner = SH * cfg.ssm_head_dim
            wide = inner + 2 * cfg.ssm_groups * cfg.ssm_state
            dts = jnp.maximum(jnp.exp(uniform(
                (SH,), *(float(np.log(x)) for x in SSM_DT_RANGE))),
                SSM_DT_FLOOR)
            layer["attn"] = {
                "in": lin(f"{at}/in", D, inner + wide + SH),
                "conv": uniform((cfg.ssm_conv, wide),
                                *SSM_CONV_RANGE).astype(dtype),
                "conv_bias": uniform((wide,), *SSM_CONV_RANGE).astype(dtype),
                "A_log": jnp.log(jnp.arange(1, SH + 1, dtype=jnp.float32)),
                # softplus^-1 of the drawn dt
                "dt_bias": dts + jnp.log(-jnp.expm1(-dts)),
                "D": jnp.ones((SH,), jnp.float32),
                "norm": norm(f"{at}/norm", inner),
                "o": lin(f"{at}/o", inner, D),
            }
        elif cfg.conv_of(i):
            # the public names: in_proj (B | C | x), conv, out_proj; no bias
            at = f"{lp}/attn"
            layer["attn"] = {
                "in": lin(f"{at}/in", D, 3 * D),
                "conv": uniform((cfg.conv_taps, D),
                                *conv_tap_range(cfg.conv_taps)).astype(dtype),
                "o": lin(f"{at}/o", D, D),
            }
        elif i in cfg.cross_attention_layers:
            ca = f"{lp}/cross_attn"
            layer["cross_attn"] = {
                "q": lin(f"{ca}/q", D, q_out), "k": lin(f"{ca}/k", D, kv_out),
                "v": lin(f"{ca}/v", D, kv_out), "o": lin(f"{ca}/o", q_out, D),
                "q_norm": norm(f"{ca}/q_norm", HD),
                "k_norm": norm(f"{ca}/k_norm", HD),
            }
            layer["gate_attn"] = rand(f"{lp}/gate_attn", (1,), dtype)
            layer["gate_mlp"] = rand(f"{lp}/gate_mlp", (1,), dtype)
        elif cfg.kda_of(i):
            # the public names: q/k/v_proj and their depthwise q/k/v_conv1d,
            # f_a/f_b_proj with dt_bias and A_log (the decay), b_proj
            # (beta), g_a/g_b_proj (the output gate), o_norm, o_proj
            at, KH, Kd = f"{lp}/attn", cfg.kda_heads, cfg.kda_head_dim
            wide = KH * Kd
            layer["attn"] = {
                **{n: lin(f"{at}/{n}", D, wide) for n in ("q", "k", "v")},
                **{f"{n}_conv": rand(f"{at}/{n}_conv", (cfg.kda_conv, wide),
                                     dtype, at=KDA_CONV_STD)
                   for n in ("q", "k", "v")},
                "f_a": lin(f"{at}/f_a", D, Kd),
                "f_b": lin(f"{at}/f_b", Kd, wide),
                "A_log": jnp.log(uniform((KH,), *KDA_A_RANGE)),
                # softplus^-1 of a log-uniform dt
                "dt_bias": jnp.log(jnp.expm1(jnp.exp(uniform(
                    (wide,), *(float(np.log(x)) for x in KDA_DT_RANGE))))),
                "b": lin(f"{at}/b", D, KH),
                "g_a": lin(f"{at}/g_a", D, Kd),
                "g_b": lin(f"{at}/g_b", Kd, wide),
                "o_norm": norm(f"{at}/o_norm", Kd),
                "o": lin(f"{at}/o", wide, D),
            }
        elif cfg.latent:
            # HF's names: q_proj, kv_a_proj_with_mqa (the latent and the
            # shared rotary key), kv_a_layernorm, kv_b_proj (per head: keys
            # without position, then values), o_proj
            at, H, R = f"{lp}/attn", cfg.n_heads, cfg.kv_lora_rank
            layer["attn"] = {
                "q": lin(f"{at}/q", D, q_out, LATENT_Q_GAIN),
                "kv_a": lin(f"{at}/kv_a", D, R + cfg.qk_rope_head_dim,
                            LATENT_KVA_GAIN),
                "kv_norm": norm(f"{at}/kv_norm", R),
                "kv_b": lin(f"{at}/kv_b", R,
                            H * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
                "o": lin(f"{at}/o", H * cfg.v_head_dim, D),
            }
        else:
            at = f"{lp}/attn"
            layer["attn"] = {
                "q": lin(f"{at}/q", D, q_out), "k": lin(f"{at}/k", D, kv_out),
                "v": lin(f"{at}/v", D, kv_out), "o": lin(f"{at}/o", q_out, D),
            }
            if cfg.attn_gate:
                layer["attn"]["gate"] = lin(f"{at}/gate", D, q_out)
            if cfg.qk_norm:
                layer["attn"]["q_norm"] = norm(f"{at}/q_norm", HD)
                layer["attn"]["k_norm"] = norm(f"{at}/k_norm", HD)
        tree[lp] = layer
    if not cfg.tie_embeddings:
        tree["lm_head"] = lin("lm_head", D, cfg.vocab_size)
    return {"params": tree}


def replicate_kv_heads(params: Dict[str, Any], cfg: LlamaConfig,
                       tp: int) -> Tuple[Dict[str, Any], LlamaConfig]:
    """Widen GQA kv heads to ``tp`` by weight-side replication.

    The reference's biggest unit is TP=32 over a GQA model with 8 kv heads
    (``compile-vllm-job.yaml:54-55``, DeepSeek-R1-Distill-Llama-70B) — more
    ranks than kv heads. Head-local TP (the engine's shard_map'd paged
    kernel, ``EngineShardings``) needs the kv-head axis to divide ``tp``, so
    each kv head is duplicated ``tp // n_kv_heads`` times — the same
    resolution vLLM applies when ``tp > num_kv_heads``. Numerics are
    unchanged: query head ``h`` reads replica ``h // (n_heads/tp)`` which is
    a copy of its original group head ``h // (n_heads/n_kv_heads)``
    (``jnp.repeat`` preserves group order). HBM cost: kv weights and the KV
    cache replicate across the extra ranks — exactly what
    ``core.budget.causal_lm_budget`` charges (per-chip KV floors at one
    head).

    Works on real trees, geometry trees, and under ``jax.eval_shape`` (the
    abstract lowering legs). Returns ``(new_params, new_cfg)`` with
    ``n_kv_heads == tp``.
    """
    if tp <= cfg.n_kv_heads:
        return params, cfg
    if tp % cfg.n_kv_heads or cfg.n_heads % tp:
        raise ValueError(
            f"tp={tp} must be a multiple of n_kv_heads={cfg.n_kv_heads} and "
            f"divide n_heads={cfg.n_heads} for replicated-GQA TP")
    g, HD = tp // cfg.n_kv_heads, cfg.head_dim

    def widen(mat):
        # [..., kv*HD] -> [..., tp*HD]: repeat each head's HD-column group
        lead = mat.shape[:-1]
        m = mat.reshape(*lead, cfg.n_kv_heads, HD)
        return jnp.repeat(m, g, axis=len(lead)).reshape(*lead, tp * HD)

    tree = {"params": dict(params["params"])}
    for i in range(cfg.n_layers):
        name = f"layer_{i}"
        layer = dict(tree["params"][name])
        for attn_key in ("attn", "cross_attn"):
            if attn_key not in layer:
                continue
            attn = dict(layer[attn_key])
            for proj in ("k", "v"):
                p = dict(attn[proj])
                for leaf in ("kernel", "kernel_q"):
                    if leaf in p:
                        p[leaf] = widen(p[leaf])
                if "scale" in p:  # int8 per-out-channel scale widens with out
                    p["scale"] = widen(p["scale"])
                attn[proj] = p
            layer[attn_key] = attn
        tree["params"][name] = layer
    return tree, dataclasses.replace(cfg, n_kv_heads=tp)
