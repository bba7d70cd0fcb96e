"""Multi-head attention with GQA, masking, and implementation dispatch.

The single attention entry point for the model zoo. On TPU the hot path is
the Pallas flash-attention kernel (``ops.pallas.flash_attention``); elsewhere
(CPU tier, tiny shapes, or shapes the kernel doesn't cover) it falls back to
a fused XLA softmax-attention with fp32 accumulation. The reference gets this
op from vendored runtimes (neuronx-cc fused softmax via ``NEURON_FUSE_SOFTMAX=1``,
reference ``app/compile-sd2.py:2``; CUDA SDPA inside diffusers) — here it is
first-party.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30

# platforms whose default backend is the TPU chip
TPU_PLATFORMS = ("tpu",)

#: every platform name SHAI_PLATFORM_OVERRIDE may legally carry — the TPU
#: names plus the PJRT backends this code can dispatch for. A typo'd or
#: truncated value would silently steer kernel dispatch; reject it here,
#: at the decision site, instead of deep inside Mosaic.
KNOWN_PLATFORMS = TPU_PLATFORMS + ("cpu", "gpu", "cuda", "rocm", "metal")

_override_logged: set = set()


def _validated_override(ovr: str) -> str:
    """Validate the override against the known platform names and log ONCE
    per value when active: a ``tpu`` override leaked into a CPU process
    (e.g. a deviceless-AOT env var inherited by a test run) otherwise
    surfaces as a Mosaic dispatch crash far from the cause."""
    if ovr not in KNOWN_PLATFORMS:
        raise ValueError(
            f"SHAI_PLATFORM_OVERRIDE={ovr!r} is not a known platform "
            f"(expected one of {', '.join(KNOWN_PLATFORMS)}); unset it or "
            f"fix the value — a wrong override steers kernel dispatch for "
            f"a device the computation will never run on")
    if ovr not in _override_logged:
        _override_logged.add(ovr)
        import logging

        logging.getLogger(__name__).warning(
            "SHAI_PLATFORM_OVERRIDE=%s active: ops dispatch follows the "
            "override, not the process backend (deviceless-AOT mode)", ovr)
    return ovr


def effective_platform() -> str:
    """Platform the CURRENT computation will actually run on.

    ``jax.default_backend()`` ignores a ``jax.default_device(...)`` override
    — ``core.aot.host_init`` runs whole-model flax inits on the CPU device
    while the global backend stays the TPU, and dispatching a Mosaic kernel
    into that CPU-placed trace crashes with "Only interpret mode is
    supported on CPU backend" (first observed on-chip in the round-5 SD
    bench). Every TPU-or-not dispatch decision in the ops layer must go
    through this helper, not ``jax.default_backend()``.

    ``SHAI_PLATFORM_OVERRIDE`` wins over everything: deviceless AOT
    compilation (``perf.topo``) traces on a CPU-backed process while
    targeting a TPU topology, so the dispatch must follow the compile
    TARGET, not the process backend.
    """
    from ..obs.util import env_str

    ovr = env_str("SHAI_PLATFORM_OVERRIDE")
    if ovr:
        return _validated_override(ovr)
    dd = jax.config.jax_default_device
    if dd is not None:
        # the option accepts a platform STRING too (JAX_DEFAULT_DEVICE=cpu)
        return dd if isinstance(dd, str) else dd.platform
    return jax.default_backend()


def on_tpu_platform() -> bool:
    return effective_platform() in TPU_PLATFORMS

# Plain (non-causal, no-lengths) attention dispatch: measured on v5e
# (scripts/perf_attn.py), XLA's fused softmax-attention beats the flash
# kernel on every SD2.1 UNet shape — L0 self (T=S=4096, T*S=16.7M) runs
# ~2x faster through XLA (1.8ms vs 3.8ms above the sync floor). The kernel
# only wins plain attention when the [B,H,T,S] fp32 score materialization
# stops fitting comfortably in HBM (1024px-class shapes), hence a budget on
# T*S rather than a flat preference. Causal/ragged shapes always take the
# kernel: it skips key blocks past the diagonal/valid length, which XLA's
# masked softmax cannot.
_XLA_SCORE_BUDGET = 64 * 1024 * 1024

# Measured exception inside the XLA budget (scripts/perf_attn.py on v5e,
# round 3): at T*S ~ 1M (the UNet's 32x32 self-attention level, T=S=1024)
# jax's shipped block-tuned TPU flash kernel beat XLA's fused softmax
# (1163us vs 1481us above the sync floor) while losing at 16.7M (4910 vs
# 3225) and being noise at <=65k. The window dispatches exactly that level.
_JAX_FLASH_WINDOW = (2 ** 20, 2 ** 21)


def _jax_flash_eligible(q, k, mask, bias, kv_lengths, causal) -> bool:
    """Shapes jax's shipped TPU flash kernel covers: MHA, no mask/bias/
    lengths, tiling-friendly T/S, causal only when T == S (the kernel aligns
    the diagonal at 0; this API's decode offset is S - T)."""
    B, T, H, D = q.shape
    S = k.shape[1]
    return (mask is None and bias is None and kv_lengths is None
            and H == k.shape[2] and T % 128 == 0 and S % 128 == 0
            and (not causal or T == S))


def _xla_attention(q, k, v, mask, bias, scale) -> jax.Array:
    """Reference implementation: [B,T,H,D] x [B,S,Hkv,D] -> [B,T,H,D]."""
    B, T, H, D = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    if H != Hkv:
        # grouped-query attention: repeat kv heads over the group
        group = H // Hkv
        k = jnp.repeat(k, group, axis=2)
        v = jnp.repeat(v, group, axis=2)
    s = jnp.einsum("bthd,bshd->bhts", q, k, preferred_element_type=jnp.float32)
    s = s * scale
    if bias is not None:
        s = s + bias.astype(jnp.float32)
    if mask is not None:
        s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhts,bshd->bthd", p.astype(v.dtype), v)
    return o.astype(q.dtype)


def causal_mask(T: int, S: int, offset: int = 0) -> jax.Array:
    """[1,1,T,S] boolean mask; query i attends keys j <= i + offset."""
    qi = jnp.arange(T)[:, None] + offset
    kj = jnp.arange(S)[None, :]
    return (qi >= kj)[None, None, :, :]


def dot_product_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    mask: Optional[jax.Array] = None,
    bias: Optional[jax.Array] = None,
    causal: bool = False,
    scale: Optional[float] = None,
    kv_lengths: Optional[jax.Array] = None,
    impl: str = "auto",
    window: int = 0,
) -> jax.Array:
    """Scaled dot-product attention.

    Args:
      q: ``[B, T, H, D]``.
      k, v: ``[B, S, Hkv, D]`` with ``H % Hkv == 0`` (GQA/MQA supported).
      mask: boolean, broadcastable to ``[B, H, T, S]``; True = attend.
      bias: additive, broadcastable to ``[B, H, T, S]`` (e.g. T5 relative
        position bias).
      causal: apply causal masking (assumes key block starts at position 0
        and queries start at position ``S - T``, the decode-step layout).
      scale: defaults to ``1/sqrt(D)``.
      kv_lengths: ``[B]`` int32 valid key count per row (right-padded keys
        beyond it are masked). Unlike ``mask``, this keeps the flash kernel
        eligible — it is THE way bucketed LLM prefill reaches the pallas
        path (VERDICT r1 #3).
      impl: ``auto`` (pallas on TPU when eligible), ``xla``, or ``pallas``.
      window: with ``causal``, query ``i`` sees keys ``j`` with
        ``0 <= i - j < window`` (positions as ``causal`` lays them out);
        0 = every key behind it. The flash kernel skips the key blocks
        below the window; the XLA path masks them.
    """
    B, T, H, D = q.shape
    S = k.shape[1]
    if H % k.shape[2]:
        raise ValueError(f"q heads {H} not a multiple of kv heads {k.shape[2]}")
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    if window and not causal:
        raise ValueError("a window bounds causal attention only")
    if impl == "auto":
        # measured-dispatch escape hatch (scripts/perf_attn.py)
        from ..obs.util import env_str

        impl = env_str("SHAI_ATTN_IMPL", "auto")
        if impl == "auto" and not causal and kv_lengths is None:
            if (_jax_flash_eligible(q, k, mask, bias, kv_lengths, causal)
                    and _JAX_FLASH_WINDOW[0] <= T * S < _JAX_FLASH_WINDOW[1]
                    and on_tpu_platform()):
                impl = "jax-flash"
            elif T * S <= _XLA_SCORE_BUDGET:
                impl = "xla"

    if impl == "jax-flash":
        # jax's shipped, block-tuned TPU flash kernel (public pallas ops) —
        # a dispatch option for big self-attention shapes; needs a real TPU
        # (no interpreter mode)
        eligible = _jax_flash_eligible(q, k, mask, bias, kv_lengths, causal)
        on_tpu = on_tpu_platform()
        if eligible and on_tpu:
            from jax.experimental.pallas.ops.tpu.flash_attention import (
                flash_attention as jax_flash,
            )

            out = jax_flash(
                q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                v.transpose(0, 2, 1, 3), causal=causal, sm_scale=scale)
            return out.transpose(0, 2, 1, 3)
        if not eligible:
            # mirror impl="pallas": an explicit-but-ineligible request fails
            # loudly so measured dispatch tables never time the wrong path
            raise ValueError(
                f"jax-flash not eligible for q={q.shape} k={k.shape} "
                f"(mask={mask is not None}, bias={bias is not None}, "
                f"lengths={kv_lengths is not None}, causal={causal})")
        impl = "xla"  # eligible shape, no TPU: interpreter unsupported

    if impl in ("auto", "pallas"):
        # the flash kernel applies causal + length masking itself; arbitrary
        # masks and biases take the XLA path
        from .pallas.flash_attention import flash_attention, flash_eligible

        want = impl == "pallas"
        if flash_eligible(q, k, v, mask=mask, bias=bias) and (
            want or on_tpu_platform()
        ):
            return flash_attention(q, k, v, causal=causal, scale=scale,
                                   lengths=kv_lengths, window=window)
        if want:
            raise ValueError(
                f"pallas flash attention not eligible for shapes q={q.shape} "
                f"k={k.shape} (mask={mask is not None}, bias={bias is not None})"
            )
    elif impl != "xla":
        raise ValueError(f"unknown attention impl {impl!r}")

    if kv_lengths is not None:
        lm = (jnp.arange(S)[None, :]
              < kv_lengths.astype(jnp.int32)[:, None])[:, None, None, :]
        mask = lm if mask is None else jnp.logical_and(mask, lm)
    if causal:
        cm = causal_mask(T, S, offset=S - T)
        if window:
            cm = jnp.logical_and(cm, ~causal_mask(T, S, offset=S - T - window))
        mask = cm if mask is None else jnp.logical_and(mask, cm)
    return _xla_attention(q, k, v, mask, bias, scale)


# -- ragged paged attention (the deviceless reference) ----------------------


def ragged_gather_attention(
    q: jax.Array,           # [B, T, H, D] queries
    k_pool: jax.Array,      # [N, block_size, Hkv, D] (float or int8 pool)
    v_pool: jax.Array,
    tables: jax.Array,      # [B, M] physical block ids (0-padded)
    positions: jax.Array,   # [B, T] each query's own cache position
    k_scale: Optional[jax.Array] = None,   # [N, Hkv] f32 (int8 pools)
    v_scale: Optional[jax.Array] = None,
    *,
    scale: Optional[float] = None,
    window: int = 0,
) -> jax.Array:
    """XLA gather-based reference for ragged paged attention.

    Every query ``(b, t)`` attends pool positions ``<= positions[b, t]``
    through row ``b``'s block table — mixed context lengths in one call,
    no bucketing. This is THE deviceless oracle for the Pallas pool
    kernel (``ops.pallas.paged_attention``): a dense gather of the
    table window plus a per-query mask, exactly the engine's CPU decode
    path, so quant-off numerics are bit-identical to it. int8
    pools dequantize right after the gather (``ops.quant``). Returns
    ``[B, T, H, D]``.
    """
    B, T, H, D = q.shape
    _N, block_size, Hkv, _ = k_pool.shape
    M = tables.shape[1]
    L = M * block_size
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    if k_scale is not None:
        from .quant import dequantize_kv_blocks

        # block-shaped gather so the per-(block, head) scales broadcast;
        # the reshape lands in the same [B, L, Hkv, D] layout as the flat
        # gather below
        kctx = dequantize_kv_blocks(
            k_pool[tables], k_scale[tables], dtype=q.dtype
        ).reshape(B, L, Hkv, D)
        vctx = dequantize_kv_blocks(
            v_pool[tables], v_scale[tables], dtype=q.dtype
        ).reshape(B, L, Hkv, D)
    else:
        goff = (tables[:, :, None] * block_size
                + jnp.arange(block_size)[None, None, :]).reshape(B, L)
        kflat = k_pool.reshape(-1, Hkv, D)
        vflat = v_pool.reshape(-1, Hkv, D)
        kctx = kflat[goff]
        vctx = vflat[goff]
    mask = (jnp.arange(L)[None, None, :]
            <= positions[:, :, None])[:, None]         # [B, 1, T, L]
    if window:
        # a window layer: nothing a window or more behind the query
        mask = mask & (jnp.arange(L)[None, None, :]
                       > positions[:, :, None] - window)[:, None]
    return _xla_attention(q, kctx, vctx, mask, None, scale)
