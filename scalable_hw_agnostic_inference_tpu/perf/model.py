"""Roofline-calibrated throughput projections from deviceless AOT compiles.

Method (VERDICT r4 next-round #1/#2):

1. AOT-compile each serving family's hot executables against a v5e topology
   (:mod:`.topo`) — real XLA:TPU binaries, no device attached.
2. Read each executable's own accounting: ``flops`` and ``bytes accessed``
   from ``compiled.cost_analysis()`` (post-fusion HLO, so the bytes figure
   approximates true HBM traffic), plus XLA's internal ``optimal_seconds``
   latency estimate.
3. Workloads are compiled at *component* granularity — one denoise step, one
   VAE decode, one prefill, one decode step — because XLA's cost analysis
   counts a ``lax.scan``/``while`` body ONCE regardless of trip count
   (verified empirically: a 2-step and a 4-step SD pipeline report identical
   flops). Totals are composed analytically: ``t_img = steps * t_step +
   t_vae``, ``t_gen = t_prefill + new * t_decode``. The decomposition also
   yields the VAE share and the TTFT/TPOT split directly.
4. Roofline bound per component: ``t >= max(flops / MXU_peak, bytes /
   HBM_bw)``.
5. Calibrate an achieved-fraction ``eta = t_roofline / t_measured`` on the
   one on-chip measurement this repo has (SD2.1 512^2 batch-1 single-stream,
   0.9135 img/s, BENCH_r02.json) and project other configurations at the
   same eta. Holding eta constant is *conservative* for larger batches: the
   roofline already captures weight-traffic amortization (params are read
   once per step regardless of batch), while the additional MXU-utilization
   gain of bigger matmuls is upside the projection does not take.

The reference has no offline instrument at all — its capacity numbers exist
only as measured breaking points on live pods (reference
``README.md:122-133``, ``find-compute-breaking-point.yaml``). This module is
the TPU-native extra: capacity planning that works with zero chips attached,
cross-checked against on-chip benches as they land.
"""

from __future__ import annotations

import json
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from . import topo

# ---------------------------------------------------------------------------
# hardware + baseline constants
# ---------------------------------------------------------------------------

#: TPU v5e single-chip peaks (public: jax-ml.github.io/scaling-book — 197
#: bf16 TFLOP/s, 394 int8 TOP/s, 819 GB/s HBM, 16 GiB) and the cost basis
#: bench.py uses ($1.20/hr on-demand us-central).
V5E = {
    "bf16_flops": 197e12,
    "int8_ops": 394e12,
    "hbm_bytes_s": 819e9,
    "hbm_bytes": 16 * 1024**3,
    "cost_hr": 1.20,
}
#: reference inf2.xlarge SD2.1 unit at its breaking point: p50 0.67 s/img at
#: $0.7582/hr (reference README.md:192,261) — the throughput/$ denominator.
INF2 = {"sd_img_s": 1.0 / 0.67, "cost_hr": 0.7582}
NORTH_STAR_RATIO = 2.0   # BASELINE.md: >= 2x throughput/$ vs inf2

#: on-chip single-stream measurements banked so far, keyed by composition
#: name. SD batch-1 (the only real TPU number, round 2) is the calibration
#: anchor; add rows here as chip runs land.
MEASURED = {
    "sd_b1": {
        "seconds": 1.0 / 0.9135,
        "source": "BENCH_r02.json on-chip v5e-1 (0.9135 img/s single-stream,"
                  " 512^2, 25-step, bf16 UNet)",
    },
}

SD_STEPS = 25
GEN_NEW = 128


def _repl(mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def _tree_bytes(avals) -> int:
    return int(sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(avals)))


# ---------------------------------------------------------------------------
# workload builders: name -> (fn, args, meta)
# ---------------------------------------------------------------------------

def _sd_pipe(tiny: bool):
    from ..models import sd as sd_mod

    variant = sd_mod.SDVariant.tiny() if tiny else sd_mod.SDVariant.sd21_base()
    pipe = sd_mod.StableDiffusion(variant, None, None, None)
    size, steps, seq = (16, 2, 8) if tiny else (512, SD_STEPS, 77)
    return pipe, variant, size // pipe.vae_scale, steps, seq


def _sd_unet_avals(pipe, variant, lat, seq, s):
    D = variant.unet.cross_attention_dim
    return topo.with_sharding(topo.bf16_leaves(topo.abstract_params(
        lambda: pipe.unet.init(
            jax.random.PRNGKey(0),
            jnp.zeros((1, lat, lat, variant.unet.in_channels)),
            jnp.zeros((1,), jnp.int32), jnp.zeros((1, seq, D))))), s)


def wl_sd_step(batch: int, *, tiny: bool = False, attn: str = "auto"):
    """ONE CFG denoise step (UNet on 2B + guidance mix + scheduler update) —
    the scan body of the serving pipeline (models/sd.py _make_step).
    ``attn='pallas'`` compiles the flash-attention-everywhere variant
    (``SHAI_ATTN_IMPL``) so the score-materialization HBM lever is a
    measured delta, not an estimate."""
    pipe, variant, lat, steps, seq = _sd_pipe(tiny)
    D = variant.unet.cross_attention_dim
    mesh = topo.device_mesh(1)
    s = _repl(mesh)
    unet_avals = _sd_unet_avals(pipe, variant, lat, seq, s)
    fn = pipe._make_step(batch)
    args = (
        unet_avals,
        jax.ShapeDtypeStruct((batch, lat, lat, variant.unet.in_channels),
                             jnp.float32, sharding=s),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=s),
        jax.ShapeDtypeStruct((), jnp.float32, sharding=s),
        jax.ShapeDtypeStruct((), jnp.float32, sharding=s),
        jax.ShapeDtypeStruct((2 * batch, seq, D), jnp.bfloat16, sharding=s),
        jax.ShapeDtypeStruct((), jnp.float32, sharding=s),
    )
    meta = {
        "family": "sd", "component": "denoise_step", "batch": batch,
        "param_bytes": _tree_bytes(unet_avals),
        "detail": f"sd21-base one CFG denoise step, batch {batch} "
                  f"(UNet fwd on {2 * batch})"}
    if attn != "auto":
        meta["trace_env"] = {"SHAI_ATTN_IMPL": attn}
        meta["detail"] += f", attn={attn}"
    return fn, args, meta


def wl_sd_vae(batch: int, *, tiny: bool = False, split: bool = False):
    """VAE decode + uint8 quantize (models/sd.py _decode). ``split`` runs
    the batch as a ``lax.map`` of single-image decodes — the cost model
    found XLA's fused batch-2/4 decode pathological (b4: 115 GB accessed vs
    8 GB at b1; b8 is fine at 30 GB), so this variant quantifies the
    chunked alternative."""
    pipe, variant, lat, steps, seq = _sd_pipe(tiny)
    mesh = topo.device_mesh(1)
    s = _repl(mesh)
    vae_avals = topo.with_sharding(topo.abstract_params(
        lambda: pipe.vae.init(
            jax.random.PRNGKey(1),
            jnp.zeros((1, lat, lat, variant.vae.latent_channels)))), s)
    if split:
        decode = pipe._decode

        def fn(p, z):
            return jax.lax.map(lambda zi: decode(p, zi[None])[0], z)
    else:
        fn = pipe._decode
    args = (vae_avals,
            jax.ShapeDtypeStruct((batch, lat, lat,
                                  variant.vae.latent_channels),
                                 jnp.float32, sharding=s))
    return fn, args, {
        "family": "sd", "component": "vae_decode", "batch": batch,
        "param_bytes": _tree_bytes(vae_avals),
        "scan_trips": batch if split else None,
        "detail": f"sd21-base VAE decode to uint8, batch {batch}"
                  + (" (lax.map per image)" if split else "")}


def _llama_cfg(geometry: str, tiny: bool):
    from ..models import llama as llama_mod

    if tiny:
        return llama_mod.LlamaConfig.tiny()
    if geometry == "1b":
        return llama_mod.LlamaConfig.llama32_1b()
    if geometry == "3b":
        return llama_mod.LlamaConfig.llama32_3b()
    raise ValueError(geometry)


def wl_llama_prefill(geometry: str, *, quant: bool = False, batch: int = 8,
                     prompt: int = 128, tiny: bool = False):
    """Bucketed prefill incl. in-graph cache init + mask build — the TTFT
    executable of models/generate.py."""
    from ..models import llama as llama_mod

    cfg = _llama_cfg(geometry, tiny)
    if tiny:
        batch, prompt = 2, 16
    n_slots = prompt + (8 if tiny else GEN_NEW)
    model = llama_mod.LlamaForCausalLM(cfg, dtype=jnp.bfloat16, quant=quant)
    mesh = topo.device_mesh(1)
    s = _repl(mesh)
    params = topo.with_sharding(topo.abstract_params(
        lambda: llama_mod.geometry_params(cfg, quant=quant)), s)

    def prefill(p, ids, prompt_len):
        B, Tp = ids.shape
        positions = jnp.broadcast_to(jnp.arange(Tp, dtype=jnp.int32), (B, Tp))
        token_valid = positions < prompt_len[:, None]
        cache = llama_mod.init_cache(cfg, B, n_slots, dtype=jnp.bfloat16)
        mask = llama_mod.prefill_mask(token_valid, n_slots)
        return model.apply(p, ids, positions, cache, mask, jnp.int32(0))

    args = (params,
            jax.ShapeDtypeStruct((batch, prompt), jnp.int32, sharding=s),
            jax.ShapeDtypeStruct((batch,), jnp.int32, sharding=s))
    q = "-int8" if quant else ""
    return prefill, args, {
        "family": "llama", "component": "prefill", "batch": batch,
        "geometry": f"{geometry}{q}", "param_bytes": _tree_bytes(params),
        "detail": f"llama-{geometry}{q} prefill bs={batch} prompt={prompt}"}


def wl_llama_decode(geometry: str, *, quant: bool = False, batch: int = 8,
                    prompt: int = 128, tiny: bool = False):
    """ONE decode step (cache-attending forward on [B,1] + on-device
    sampling) — the TPOT executable, the scan body of generate."""
    from ..models import llama as llama_mod
    from ..ops.sampling import sample_logits

    cfg = _llama_cfg(geometry, tiny)
    if tiny:
        batch, prompt = 2, 16
    n_slots = prompt + (8 if tiny else GEN_NEW)
    model = llama_mod.LlamaForCausalLM(cfg, dtype=jnp.bfloat16, quant=quant)
    mesh = topo.device_mesh(1)
    s = _repl(mesh)
    params = topo.with_sharding(topo.abstract_params(
        lambda: llama_mod.geometry_params(cfg, quant=quant)), s)
    cache = topo.with_sharding(topo.abstract_params(
        lambda: llama_mod.init_cache(cfg, batch, n_slots,
                                     dtype=jnp.bfloat16)), s)

    def decode(p, tok, pos, cache, slot_valid, write_idx, rng):
        logits, cache = model.apply(
            p, tok[:, None], pos[:, None], cache,
            llama_mod.decode_mask(slot_valid), write_idx)
        nxt = sample_logits(logits[:, -1], rng, 1.0, 0, 1.0)
        return nxt, cache

    args = (params,
            jax.ShapeDtypeStruct((batch,), jnp.int32, sharding=s),
            jax.ShapeDtypeStruct((batch,), jnp.int32, sharding=s),
            cache,
            jax.ShapeDtypeStruct((batch, n_slots), jnp.bool_, sharding=s),
            jax.ShapeDtypeStruct((), jnp.int32, sharding=s),
            topo.with_sharding(topo.abstract_params(
                lambda: jax.random.PRNGKey(0)), s))
    q = "-int8" if quant else ""
    return decode, args, {
        "family": "llama", "component": "decode_step", "batch": batch,
        "geometry": f"{geometry}{q}", "param_bytes": _tree_bytes(params),
        "detail": f"llama-{geometry}{q} one decode step bs={batch} "
                  f"(cache {n_slots} slots)"}


#: tiny paged-decode geometry: still lowers the REAL Pallas paged kernel
#: for the TPU target, so head_dim must satisfy Mosaic's 128-lane tiling
_TINY_DECODE_KW = dict(vocab_size=512, dim=256, n_layers=2, n_heads=2,
                       n_kv_heads=2, mlp_dim=128, max_seq_len=256,
                       rope_theta=10000.0, tie_embeddings=True)


def wl_mllama_decode(*, tiny: bool = False):
    """The cova caption stage's decode step: gated cross-attention over the
    full vision buffer, born-int8 11B geometry, bs=1 — constants fixed to
    bench.py's mllama caption path (prompt shapes aside)."""
    from ..models import llama as llama_mod

    if tiny:
        cfg = llama_mod.LlamaConfig(cross_attention_layers=(1,),
                                    **_TINY_DECODE_KW)
        return _paged_decode(cfg, "mllama-tiny", quant=False, batch=1,
                             ctx=32, block_size=8, lv=32)
    cfg = llama_mod.LlamaConfig.mllama_11b_text()
    return _paged_decode(cfg, "mllama-11b-int8", quant=True, batch=1,
                         ctx=1024, block_size=128,
                         lv=4 * (1 + (560 // 14) ** 2))


def wl_vllm_decode(geometry: str = "1b", *, quant: bool = False,
                   batch: int = 8, ctx: int = 1024, block_size: int = 16,
                   tiny: bool = False):
    """ONE paged-engine decode step (engine/runner.py make_decode, the
    Pallas paged-attention path) — the TPOT executable of the vllm unit."""
    from ..models import llama as llama_mod

    if tiny:
        cfg = llama_mod.LlamaConfig(**_TINY_DECODE_KW)
        return _paged_decode(cfg, "llama-tiny", quant=quant, batch=batch,
                             ctx=32, block_size=block_size, lv=0)
    cfg = _llama_cfg(geometry, tiny=False)
    name = f"llama-{geometry}" + ("-int8" if quant else "")
    return _paged_decode(cfg, name, quant=quant, batch=batch, ctx=ctx,
                         block_size=block_size, lv=0)


def _paged_decode(cfg, name: str, *, quant: bool, batch: int, ctx: int,
                  block_size: int, lv: int, tp: int = 1):
    """Shared paged-decode workload assembly (single-device or TP-sharded).

    The KV pool is sized to exactly the bucketed context in use
    (1 null block + batch x ctx blocks): XLA's cost analysis counts a
    Pallas custom call's whole pool operand as accessed, so an over-sized
    pool would overstate HBM traffic; at full occupancy pool size == true
    working set.

    ``tp > 1`` compiles the REAL sharded serving path: EngineShardings over
    a tp-wide topology mesh, plain avals (placement comes from the jit's
    in_shardings exactly as in serving), per-device cost numbers."""
    from ..engine.runner import EngineShardings, make_decode
    from ..models import llama as llama_mod

    m_ctx = max(1, ctx // block_size)
    n_cross = len(cfg.cross_attention_layers)
    n_self = cfg.n_layers - n_cross
    params_avals = topo.abstract_params(
        lambda: llama_mod.geometry_params(cfg, quant=quant))
    if tp > 1:
        mesh = topo.device_mesh(tp, axes=("tp",))
        sh = EngineShardings(mesh, params_avals, cfg)
        s = None
    else:
        sh = None
        s = _repl(topo.device_mesh(1))
    fn = make_decode(cfg, block_size, m_ctx, batch, shardings=sh,
                     paged=True)

    def aval(shape, dtype):
        if s is None:
            return jax.ShapeDtypeStruct(shape, dtype)
        return jax.ShapeDtypeStruct(shape, dtype, sharding=s)

    def atree(build):
        t = topo.abstract_params(build)
        return t if s is None else topo.with_sharding(t, s)

    params = (params_avals if s is None
              else topo.with_sharding(params_avals, s))
    pool = aval((1 + batch * m_ctx, block_size, cfg.n_kv_heads,
                 cfg.head_dim), jnp.bfloat16)
    kv = [{"k": pool, "v": pool} for _ in range(n_self)]
    vec = lambda dt: aval((batch,), dt)  # noqa: E731
    args = (params, kv, vec(jnp.int32), vec(jnp.int32),
            aval((batch, m_ctx), jnp.int32), vec(jnp.bool_),
            atree(lambda: jax.random.PRNGKey(0)), aval((), jnp.int32),
            vec(jnp.float32), vec(jnp.int32), vec(jnp.float32))
    if n_cross:
        cbuf = aval((batch, lv, cfg.n_kv_heads, cfg.head_dim), jnp.bfloat16)
        args += ([{"k": cbuf, "v": cbuf} for _ in range(n_cross)],
                 vec(jnp.float32), vec(jnp.int32), vec(jnp.int32))
    meta = {
        "family": "mllama" if n_cross else "llama",
        "component": "paged_decode_step", "batch": batch,
        "param_bytes": _tree_bytes(params_avals),
        "detail": f"{name} paged-engine decode step bs={batch} "
                  f"ctx={m_ctx * block_size}"
                  + (f" cross Lv={lv}" if n_cross else "")
                  + (f" tp={tp}; per-device numbers" if tp > 1 else "")}
    if tp > 1:
        meta["n_devices"] = tp
    return fn, args, meta


def wl_vllm_verify(geometry: str = "1b", *, k: int = 4, quant: bool = False,
                   batch: int = 8, ctx: int = 1024, block_size: int = 16,
                   tiny: bool = False):
    """ONE speculative VERIFY step (engine/runner.py make_verify): k+1
    scored positions per sequence through the paged pool — the executable
    whose cost, divided by the expected committed tokens per step
    (:func:`spec_decode_model`), is the speculative decode ms/token."""
    from ..engine.runner import make_verify
    from ..models import llama as llama_mod

    if tiny:
        cfg = llama_mod.LlamaConfig(**_TINY_DECODE_KW)
        ctx, block_size = 32, 8
    else:
        cfg = _llama_cfg(geometry, tiny=False)
    name = f"llama-{geometry}" + ("-int8" if quant else "")
    m_ctx = max(1, ctx // block_size)
    params_avals = topo.abstract_params(
        lambda: llama_mod.geometry_params(cfg, quant=quant))
    s = _repl(topo.device_mesh(1))
    fn = make_verify(cfg, block_size, m_ctx, batch, k, paged=True)

    def aval(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=s)

    params = topo.with_sharding(params_avals, s)
    pool = aval((1 + batch * m_ctx, block_size, cfg.n_kv_heads,
                 cfg.head_dim), jnp.bfloat16)
    kv = [{"k": pool, "v": pool} for _ in range(cfg.n_layers)]
    vec = lambda dt: aval((batch,), dt)  # noqa: E731
    args = (params, kv, aval((batch, k + 1), jnp.int32), vec(jnp.int32),
            aval((batch, m_ctx), jnp.int32), vec(jnp.bool_),
            topo.with_sharding(topo.abstract_params(
                lambda: jax.random.PRNGKey(0)), s),
            aval((), jnp.int32),
            vec(jnp.float32), vec(jnp.int32), vec(jnp.float32))
    return fn, args, {
        "family": "llama", "component": "spec_verify_step", "batch": batch,
        "param_bytes": _tree_bytes(params_avals),
        "detail": f"{name} speculative verify step k={k} bs={batch} "
                  f"ctx={m_ctx * block_size}"}


def wl_vllm_decode_tp8(*, tiny: bool = False):
    """The TP-sharded paged decode step AOT-compiled for the TPU target:
    llama-70B int8 geometry over a tp=8 topology mesh — the deepest
    validation the sharded engine path can get without chips. Catches what
    neither the CPU lowering legs (no Mosaic) nor interpret mode can: the
    shard_map'd Pallas kernel and the EngineShardings placement must
    partition AND lower for real XLA:TPU."""
    from ..models import llama as llama_mod

    if tiny:
        cfg = llama_mod.LlamaConfig(**_TINY_DECODE_KW)
        return _paged_decode(cfg, "llama-tiny", quant=False, batch=2,
                             ctx=32, block_size=8, lv=0, tp=2)
    cfg = llama_mod.LlamaConfig.llama3_70b()
    return _paged_decode(cfg, "llama-70b-int8", quant=True, batch=8,
                         ctx=1024, block_size=128, lv=0, tp=8)


def wl_t5(*, batch: int = 32, seq: int = 128, tiny: bool = False):
    from ..models import t5 as t5_mod

    cfg = t5_mod.T5Config.tiny() if tiny else t5_mod.T5Config.t5_v1_1_large()
    if tiny:
        batch, seq = 2, 16
    model = t5_mod.T5Encoder(cfg, dtype=jnp.bfloat16)
    mesh = topo.device_mesh(1)
    s = _repl(mesh)
    params = topo.with_sharding(topo.bf16_leaves(topo.abstract_params(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 8), jnp.int32),
                           jnp.ones((1, 8), jnp.int32)))), s)

    def embed(p, ids, mask):
        return t5_mod.mean_pool(model.apply(p, ids, mask), mask)

    args = (params,
            jax.ShapeDtypeStruct((batch, seq), jnp.int32, sharding=s),
            jax.ShapeDtypeStruct((batch, seq), jnp.int32, sharding=s))
    return embed, args, {
        "family": "t5", "component": "embed", "batch": batch,
        "param_bytes": _tree_bytes(params),
        "detail": f"t5-v1.1-large embed bs={batch} len={seq}"}


def wl_flux_tp8(*, size: int = 512, t5_len: int = 512, tiny: bool = False):
    """ONE denoise step of the FULL flux-dev 12B geometry, TP=8 over an
    8-chip v5e mesh — the executable no single chip can hold (VERDICT r4
    weak #4: the full-geometry TP=8 flux path had no perf instrument).
    Cost analysis reports the per-partition (per-device) module."""
    from ..models import flux as flux_mod

    fcfg = (flux_mod.FluxConfig.tiny() if tiny
            else flux_mod.FluxConfig.flux_dev())
    lat = 4 if tiny else size // 8
    if tiny:
        t5_len = 8
    model = flux_mod.FluxTransformer(fcfg, dtype=jnp.bfloat16)

    def _ids():
        # ONLY ever traced (eval_shape): an eager make_ids would be this
        # process's first eager op, and eager dispatch resolves the default
        # device through the real backend registry, which a deviceless
        # compile must not initialize
        return flux_mod.make_ids(1, t5_len, lat, lat)

    n_img = (lat // 2) * (lat // 2)
    mesh = topo.device_mesh(8, axes=("tp",))
    repl = _repl(mesh)
    params_avals = topo.bf16_leaves(topo.abstract_params(
        lambda: model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, n_img, fcfg.in_channels)),
            jnp.zeros((1, t5_len, fcfg.t5_dim)),
            jnp.zeros((1, fcfg.clip_dim)), jnp.zeros((1,)), jnp.zeros((1,)),
            _ids())))
    specs = flux_mod.tp_rules().tree_specs(params_avals)
    params = jax.tree.map(
        lambda a, sp: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=NamedSharding(mesh, sp)),
        params_avals, specs)

    def step(p, img, txt, vec, t, g, pos_ids):
        return model.apply(p, img, txt, vec, t, g, pos_ids)

    args = (params,
            jax.ShapeDtypeStruct((1, n_img, fcfg.in_channels), jnp.bfloat16,
                                 sharding=repl),
            jax.ShapeDtypeStruct((1, t5_len, fcfg.t5_dim), jnp.bfloat16,
                                 sharding=repl),
            jax.ShapeDtypeStruct((1, fcfg.clip_dim), jnp.bfloat16,
                                 sharding=repl),
            jax.ShapeDtypeStruct((1,), jnp.float32, sharding=repl),
            jax.ShapeDtypeStruct((1,), jnp.float32, sharding=repl),
            topo.with_sharding(topo.abstract_params(_ids), repl))
    return step, args, {
        "family": "flux", "component": "denoise_step", "batch": 1,
        "n_devices": 8, "param_bytes": _tree_bytes(params_avals),
        "detail": f"flux-dev 12B TP=8 one denoise step {size}px "
                  f"(t5_len={t5_len}); per-device numbers"}


#: the full ladder ``scripts/perf_model.py`` runs by default
WORKLOADS: Dict[str, Callable[[], Tuple[Callable, Tuple, Dict]]] = {
    **{f"sd_step_b{b}": (lambda b=b: wl_sd_step(b)) for b in (1, 2, 4, 8)},
    **{f"sd_step_b{b}_flash": (lambda b=b: wl_sd_step(b, attn="pallas"))
       for b in (1, 2, 4, 8)},
    **{f"sd_vae_b{b}": (lambda b=b: wl_sd_vae(b)) for b in (1, 2, 4, 8)},
    **{f"sd_vae_b{b}_split": (lambda b=b: wl_sd_vae(b, split=True))
       for b in (2, 4)},
    "llama1b_prefill": lambda: wl_llama_prefill("1b"),
    "llama1b_decode": lambda: wl_llama_decode("1b"),
    "llama1b_int8_prefill": lambda: wl_llama_prefill("1b", quant=True),
    "llama1b_int8_decode": lambda: wl_llama_decode("1b", quant=True),
    "llama3b_prefill": lambda: wl_llama_prefill("3b"),
    "llama3b_decode": lambda: wl_llama_decode("3b"),
    "llama3b_int8_prefill": lambda: wl_llama_prefill("3b", quant=True),
    "llama3b_int8_decode": lambda: wl_llama_decode("3b", quant=True),
    "t5": lambda: wl_t5(),
    "flux_tp8_step": lambda: wl_flux_tp8(),
    "vllm_decode_b8": lambda: wl_vllm_decode("1b"),
    "vllm_verify_b8_k4": lambda: wl_vllm_verify("1b", k=4),
    "mllama_decode_b1": lambda: wl_mllama_decode(),
    "vllm_decode_70b_tp8": lambda: wl_vllm_decode_tp8(),
}


# acceptance rates the speculative projection is tabulated at: 0 (pure
# overhead — every draft rejected), the mid regime, and the
# quote-heavy/self-repetitive regime prompt lookup is built for
SPEC_ALPHAS = (0.0, 0.3, 0.5, 0.7, 0.9)


def spec_decode_model(t_decode_s: float, t_verify_s: float,
                      accept_rate: float, k: int) -> Dict[str, float]:
    """Speculative decode cost as a function of acceptance rate.

    With i.i.d. per-draft acceptance probability ``a`` and ``k`` drafts,
    the accepted prefix length J has ``P(J >= j) = a^j``, so a verify step
    commits ``E[1 + J] = 1 + a(1 - a^k)/(1 - a)`` tokens (the +1 is the
    bonus/correction sample — a verify step NEVER commits fewer tokens than
    a vanilla decode step). Modeled decode seconds per token is then
    ``t_verify / E[1+J]``; ``speedup_vs_decode`` compares against the
    vanilla single-token roofline. The break-even acceptance rate solves
    ``E[1+J] = t_verify / t_decode``.
    """
    a = min(max(float(accept_rate), 0.0), 1.0)
    if a >= 1.0:
        committed = float(k + 1)
    else:
        committed = 1.0 + a * (1.0 - a ** k) / (1.0 - a)
    return {
        "accept_rate": a,
        "tokens_per_verify": committed,
        "s_per_token": t_verify_s / committed,
        "speedup_vs_decode": t_decode_s * committed / t_verify_s,
    }


# ---------------------------------------------------------------------------
# roofline + composition + projection math (pure; unit-tested)
# ---------------------------------------------------------------------------

def roofline(flops: float, bytes_accessed: float,
             hw: Dict[str, float] = V5E) -> Dict[str, Any]:
    t_mxu = flops / hw["bf16_flops"]
    t_hbm = bytes_accessed / hw["hbm_bytes_s"]
    t = max(t_mxu, t_hbm)
    return {"t_mxu_s": t_mxu, "t_hbm_s": t_hbm, "t_roofline_s": t,
            "bound": "mxu" if t_mxu >= t_hbm else "hbm",
            "mfu_ceiling": (flops / (t * hw["bf16_flops"])) if t else 0.0}


def _tsum(rows: Dict[str, Dict], parts: Dict[str, float], key: str) -> float:
    """sum(mult * rows[name][key]) — one composition rule for roofline and
    XLA-optimal estimates alike. None if any part is missing."""
    tot = 0.0
    for name, mult in parts.items():
        row = rows.get(name)
        if row is None or row.get(key) is None:
            return None
        tot += mult * row[key]
    return tot


def compose(rows: Dict[str, Dict]) -> Dict[str, Dict]:
    """Analytic totals from component rows (scan bodies x trip counts)."""
    out: Dict[str, Dict] = {}
    for b in (1, 2, 4, 8):
        for suffix in ("", "_flash"):
            # serving decodes per-image at batches 2-4 (models/sd.py
            # _decode_body) — compose with the matching split-decode row
            vae = (f"sd_vae_b{b}_split"
                   if 2 <= b <= 4 and f"sd_vae_b{b}_split" in rows
                   else f"sd_vae_b{b}")
            parts = {f"sd_step_b{b}{suffix}": float(SD_STEPS), vae: 1.0}
            if all(p in rows for p in parts):
                out[f"sd_b{b}{suffix}"] = {
                    "family": "sd", "work": b, "work_unit": "images",
                    "parts": parts,
                    "t_roofline_s": _tsum(rows, parts, "t_roofline_s"),
                    "t_xla_optimal_s": _tsum(rows, parts, "optimal_seconds"),
                    "flops": _tsum(rows, parts, "flops"),
                    "bytes_accessed": _tsum(rows, parts, "bytes_accessed"),
                }
    for geo in ("1b", "3b"):
        for q in ("", "_int8"):
            pre, dec = f"llama{geo}{q}_prefill", f"llama{geo}{q}_decode"
            if pre in rows and dec in rows:
                batch = rows[dec]["batch"]
                parts = {pre: 1.0, dec: float(GEN_NEW)}
                out[f"llama{geo}{q}_gen"] = {
                    "family": "llama", "work": batch * GEN_NEW,
                    "work_unit": "tokens", "parts": parts,
                    "t_roofline_s": _tsum(rows, parts, "t_roofline_s"),
                    "t_xla_optimal_s": _tsum(rows, parts, "optimal_seconds"),
                    "flops": _tsum(rows, parts, "flops"),
                    "bytes_accessed": _tsum(rows, parts, "bytes_accessed"),
                    # serving-level split: TTFT ~ prefill, TPOT ~ decode step
                    "ttft_roofline_s": rows[pre]["t_roofline_s"],
                    "tpot_roofline_s": rows[dec]["t_roofline_s"],
                }
    if "vllm_decode_b8" in rows and "vllm_verify_b8_k4" in rows:
        dec, ver = rows["vllm_decode_b8"], rows["vllm_verify_b8_k4"]
        out["vllm_spec_decode_b8_k4"] = {
            "family": "llama", "work": ver["batch"], "work_unit": "tokens",
            "parts": {"vllm_verify_b8_k4": 1.0},
            "t_roofline_s": ver["t_roofline_s"],
            "t_xla_optimal_s": ver.get("optimal_seconds"),
            "flops": ver["flops"],
            "bytes_accessed": ver["bytes_accessed"],
            # decode ms/token as a function of acceptance rate: the compiled
            # verify cost divided by expected committed tokens per step
            "spec_model": {
                f"{a:.1f}": spec_decode_model(
                    dec["t_roofline_s"], ver["t_roofline_s"], a, 4)
                for a in SPEC_ALPHAS},
        }
    for nm in ("vllm_decode_b8", "mllama_decode_b1", "vllm_decode_70b_tp8"):
        if nm in rows:
            row = rows[nm]
            out[f"{nm}_tpot"] = {
                "family": row["family"], "work": row["batch"],
                "work_unit": "tokens", "parts": {nm: 1.0},
                "t_roofline_s": row["t_roofline_s"],
                "t_xla_optimal_s": row.get("optimal_seconds"),
                "flops": row["flops"],
                "bytes_accessed": row["bytes_accessed"],
            }
    if "t5" in rows:
        row = rows["t5"]
        out["t5_embed"] = {
            "family": "t5", "work": row["batch"], "work_unit": "sequences",
            "parts": {"t5": 1.0}, "t_roofline_s": row["t_roofline_s"],
            "t_xla_optimal_s": row.get("optimal_seconds"),
            "flops": row["flops"], "bytes_accessed": row["bytes_accessed"],
        }
    if "flux_tp8_step" in rows:
        # flux-dev serving default: 28 steps (BASELINE.md cova stage); VAE
        # decode is ~the SD VAE at the same latent size — reuse sd_vae_b1 as
        # the closest compiled proxy if present, else ignore (<2% of total).
        parts = {"flux_tp8_step": 28.0}
        if "sd_vae_b1" in rows:
            parts["sd_vae_b1"] = 1.0
        out["flux_dev_tp8_28step"] = {
            "family": "flux", "work": 1, "work_unit": "images",
            "parts": parts, "t_roofline_s": _tsum(rows, parts, "t_roofline_s"),
            "t_xla_optimal_s": _tsum(rows, parts, "optimal_seconds"),
            "flops": _tsum(rows, parts, "flops"),
            "bytes_accessed": _tsum(rows, parts, "bytes_accessed"),
        }
    return out


def calibrate_eta(composed: Dict[str, Dict], anchor: str = "sd_b1",
                  measured: Dict = MEASURED) -> Optional[Dict[str, Any]]:
    """eta = modeled_s / measured_s for the anchor workload (<= 1), for both
    the roofline and the XLA-optimal estimates."""
    if anchor not in composed or anchor not in measured:
        return None
    t_meas = measured[anchor]["seconds"]
    row = composed[anchor]
    if not t_meas or not row.get("t_roofline_s"):
        return None
    out = {"anchor": anchor, "measured_s": t_meas,
           "source": measured[anchor]["source"],
           "eta_roofline": row["t_roofline_s"] / t_meas,
           "mfu_measured": row["flops"] / (t_meas * V5E["bf16_flops"])}
    if row.get("t_xla_optimal_s"):
        out["eta_xla"] = row["t_xla_optimal_s"] / t_meas
    return out


def project(composed: Dict[str, Dict], cal: Optional[Dict],
            hw: Dict = V5E) -> Dict[str, Dict]:
    """Per-composition projections: roofline ceiling and (when calibrated)
    the conservative eta-held-constant figure, with throughput/$ against the
    reference's inf2 SD unit for the SD family."""
    out: Dict[str, Dict] = {}
    for name, row in composed.items():
        work, t_roof = row["work"], row.get("t_roofline_s")
        if not t_roof:
            continue
        p: Dict[str, Any] = {
            "work_unit": row["work_unit"],
            "ceiling_per_s": work / t_roof,
        }
        if cal is not None:
            t_proj = t_roof / cal["eta_roofline"]
            p["projected_s_per_call"] = t_proj
            p["projected_per_s"] = work / t_proj
            if row.get("t_xla_optimal_s") and cal.get("eta_xla"):
                p["projected_xla_per_s"] = (
                    work / (row["t_xla_optimal_s"] / cal["eta_xla"]))
        if row["family"] == "sd":
            for key in ("ceiling_per_s", "projected_per_s",
                        "projected_xla_per_s"):
                if key in p:
                    ratio = (p[key] / hw["cost_hr"]) / (
                        INF2["sd_img_s"] / INF2["cost_hr"])
                    p[key.replace("_per_s", "_per_dollar_vs_inf2")] = round(
                        ratio, 3)
        out[name] = p
    return out


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def run_workload(name: str,
                 builder: Callable[[], Tuple[Callable, Tuple, Dict]],
                 verbose: bool = True) -> Dict[str, Any]:
    with topo.platform_override("tpu"):
        # the override covers the BUILDERS too: their eval_shape traces hit
        # the ops-layer platform dispatch, which must neither touch the real
        # backend nor pick CPU kernels for a TPU-target executable
        fn, args, meta = builder()
        with topo.env_override(meta.get("trace_env", {})):
            res = topo.compile_workload(fn, args)
    res.pop("compiled", None)
    trips = meta.pop("scan_trips", None)
    if trips:
        # the workload's own loop body is counted once by XLA (scan/map
        # semantics) — scale to the declared trip count
        for key in ("flops", "bytes_accessed", "optimal_seconds"):
            if res.get(key):
                res[key] = res[key] * trips
    row = {**meta, **res}
    row.update(roofline(row["flops"], row["bytes_accessed"]))
    if verbose:
        print(f"  {name}: flops={row['flops']:.3e} "
              f"bytes={row['bytes_accessed']:.3e} "
              f"t_roofline={row['t_roofline_s'] * 1e3:.2f}ms "
              f"bound={row['bound']} (compile {row['compile_s']:.0f}s)",
              flush=True)
    return row


def run(names=None, verbose: bool = True) -> Dict[str, Any]:
    names = list(names or WORKLOADS)
    rows: Dict[str, Dict] = {}
    errors: Dict[str, str] = {}
    for name in names:
        if verbose:
            print(f"compiling {name} ...", flush=True)
        try:
            rows[name] = run_workload(name, WORKLOADS[name], verbose)
        except Exception as e:   # keep going: one family must not sink all
            errors[name] = f"{type(e).__name__}: {e}"[:500]
            if verbose:
                print(f"  {name} FAILED: {errors[name]}", flush=True)
    composed = compose(rows)
    cal = calibrate_eta(composed)
    return {
        "hw": V5E, "inf2": INF2, "north_star_ratio": NORTH_STAR_RATIO,
        "platform": "tpu-v5e (deviceless AOT topology compile)",
        "jax": jax.__version__,
        "calibration": cal,
        "components": rows,
        "composed": composed,
        "projections": project(composed, cal),
        "errors": errors,
    }


def save(results: Dict[str, Any], json_path: str, md_path: str) -> None:
    with open(json_path, "w") as f:
        json.dump(results, f, indent=1, default=lambda o: None)
    with open(md_path, "w") as f:
        f.write(render_md(results))


# ---------------------------------------------------------------------------
# report
# ---------------------------------------------------------------------------

def _fmt(x, scale=1.0, nd=2, suffix=""):
    return "-" if x is None else f"{x * scale:.{nd}f}{suffix}"


def render_md(res: Dict[str, Any]) -> str:
    hw, cal = res["hw"], res.get("calibration")
    need_img_s = (NORTH_STAR_RATIO * INF2["sd_img_s"] / INF2["cost_hr"]
                  * hw["cost_hr"])
    lines = [
        "# PERF_MODEL — offline TPU perf model "
        "(deviceless AOT + roofline)", "",
        "Generated by `python scripts/perf_model.py` "
        "(machinery: `scalable_hw_agnostic_inference_tpu/perf/`). "
        "Raw numbers: `PERF_MODEL.json`.", "",
        "**Method.** Each serving family's hot executables are AOT-compiled "
        "against a deviceless TPU v5e topology "
        "(`jax.experimental.topologies.get_topology_desc('tpu','v5e:2x2')`), "
        "producing real XLA:TPU binaries with no device attached. "
        "`compiled.cost_analysis()` supplies per-executable FLOPs and bytes "
        "accessed (post-fusion), plus XLA's own `optimal_seconds` estimate. "
        "Scan bodies are compiled separately and composed analytically "
        "(XLA counts a `lax.scan` body once — verified). Roofline: "
        f"`t >= max(flops/{hw['bf16_flops'] / 1e12:.0f}e12, "
        f"bytes/{hw['hbm_bytes_s'] / 1e9:.0f}e9)` (v5e bf16 MXU peak / HBM "
        "bandwidth, public scaling-book numbers).", "",
    ]
    if cal:
        lines += [
            "**Calibration.** The one on-chip measurement this repo has — "
            f"{cal['source']} — gives measured {cal['measured_s']:.3f} s/img "
            f"vs a composed roofline bound of "
            f"{cal['measured_s'] * cal['eta_roofline']:.3f} s: achieved "
            f"fraction **eta = {cal['eta_roofline']:.3f}** "
            f"(measured MFU {cal['mfu_measured'] * 100:.1f}%)."
            + (f" XLA's optimal-seconds model gives eta_xla = "
               f"{cal['eta_xla']:.3f}." if cal.get("eta_xla") else ""),
            "",
            "Projections hold eta constant. That is conservative at larger "
            "batch: weight-traffic amortization is already in the roofline, "
            "but the MXU-utilization gain of wider matmuls is not taken.",
            "",
        ]
    lines += ["## Component executables (XLA:TPU cost analysis)", "",
              "| executable | detail | GFLOP | MB accessed | t_mxu ms | "
              "t_hbm ms | bound | XLA opt ms | compile s |",
              "|---|---|---|---|---|---|---|---|---|"]
    for name, row in res["components"].items():
        lines.append(
            f"| {name} | {row.get('detail', '')} | "
            f"{_fmt(row['flops'], 1e-9)} | "
            f"{_fmt(row['bytes_accessed'], 1e-6, 1)} | "
            f"{_fmt(row['t_mxu_s'], 1e3)} | {_fmt(row['t_hbm_s'], 1e3)} | "
            f"{row['bound']} | {_fmt(row.get('optimal_seconds'), 1e3)} | "
            f"{_fmt(row.get('compile_s'), 1, 0)} |")
    lines += ["", "## Composed workloads and projections", "",
              "| workload | work/call | roofline s | ceiling /s | "
              "projected /s (eta) | XLA-model /s | $-ratio vs inf2 "
              "(proj) |", "|---|---|---|---|---|---|---|"]
    for name, row in res["composed"].items():
        p = res["projections"].get(name, {})
        lines.append(
            f"| {name} | {row['work']} {row['work_unit']} | "
            f"{_fmt(row.get('t_roofline_s'), 1, 3)} | "
            f"{_fmt(p.get('ceiling_per_s'))} | "
            f"{_fmt(p.get('projected_per_s'))} | "
            f"{_fmt(p.get('projected_xla_per_s'))} | "
            f"{_fmt(p.get('projected_per_dollar_vs_inf2'))} |")
    # -- the north-star verdict ------------------------------------------
    lines += ["", "## The 2x-throughput/$ question (SD2.1, BASELINE.md "
              "north star)", "",
              f"Required: **{need_img_s:.2f} img/s/chip** (= "
              f"{NORTH_STAR_RATIO}x the inf2 unit's "
              f"{INF2['sd_img_s']:.2f} img/s at {INF2['cost_hr']:.4f} $/hr, "
              f"scaled to the v5e's {hw['cost_hr']:.2f} $/hr).", ""]
    for b in (1, 2, 4, 8):
        for suffix, label in (("", "coalesced"), ("_flash", "+ flash")):
            p = res["projections"].get(f"sd_b{b}{suffix}")
            if p:
                lines.append(
                    f"- batch {b} {label}: projected "
                    f"{_fmt(p.get('projected_per_s'))} img/s "
                    f"({_fmt(p.get('projected_per_dollar_vs_inf2'))}x per-$ "
                    f"vs inf2), roofline ceiling {_fmt(p['ceiling_per_s'])} "
                    f"img/s ({_fmt(p.get('ceiling_per_dollar_vs_inf2'))}x).")
    # independent bullets: a failed/excluded flux workload must not drop
    # the caption comparison (subset runs and per-workload failures are
    # tolerated by run())
    flux = res["projections"].get("flux_dev_tp8_28step")
    mll = res["projections"].get("mllama_decode_b1_tpot")
    stage_lines = []
    if flux and flux.get("projected_s_per_call"):
        stage_lines.append(
            f"- **image stage**: the reference serves Flux.1-dev 512^2 "
            f"in 5.61 s on an inf2.48xl TP=8 group (reference "
            f"cova/README.md:98). Modeled v5e-8 TP=8 28-step flux-dev "
            f"render: projected {_fmt(flux['projected_s_per_call'])} s "
            f"(ceiling {_fmt(1 / flux['ceiling_per_s'])} s) — "
            f"{_fmt(5.61 / flux['projected_s_per_call'], 1, 1)}x "
            f"faster at the projected eta.")
    if mll and mll.get("projected_s_per_call"):
        t_cap = 64 * mll["projected_s_per_call"]
        stage_lines.append(
            f"- **caption stage**: the reference captions in 5.70 s "
            f"(mllama-11B on trn1 TP=32, same source). Modeled v5e-1 "
            f"int8 caption decode: {_fmt(mll['projected_s_per_call'] * 1e3, 1, 1)}"
            f" ms/token -> ~{_fmt(t_cap, 1, 1)} s for a 64-token caption "
            f"(+ prefill/vision encode) on ONE chip — "
            f"{_fmt(5.70 / (t_cap + 1.0), 1, 1)}x faster with the 1 s "
            f"prefill+vision allowance, at 1/32nd the accelerator count.")
    if stage_lines:
        lines += ["", "## Reference-stage comparisons (cova chain)", ""]
        lines += stage_lines
        lines.append("")
    # -- lever analysis, computed from the compiled evidence --------------
    comp, cps = res["composed"], res["components"]
    lines += ["", "## Levers (evidence-ranked)", ""]
    b4, b4f = cps.get("sd_step_b4"), cps.get("sd_step_b4_flash")
    if b4 and b4f:
        lines.append(
            f"- **Flash attention on every UNet level** (the sd21-tpub8 "
            f"tier's `SHAI_ATTN_IMPL=pallas`): XLA-attention batched steps "
            f"are HBM-bound on score traffic — flash cuts step bytes "
            f"{b4['bytes_accessed'] / 1e9:.1f} -> "
            f"{b4f['bytes_accessed'] / 1e9:.1f} GB at batch 4 and flips the "
            f"bound to `{b4f['bound']}`. Largest single lever found; the "
            f"round-3 on-chip micro-bench preferred XLA at batch 1-2, so "
            f"it is re-measured on the chip (bench.py sd8) before this "
            f"becomes the default below batch 4.")
    best = None
    for key in ("sd_b8_flash", "sd_b4_flash", "sd_b8"):
        if key in comp and comp[key].get("t_roofline_s"):
            best = key
            break
    if best and cal:
        row = comp[best]
        eta_needed = need_img_s * row["t_roofline_s"] / row["work"]
        lines.append(
            f"- **Coalescing depth**: throughput/image improves through the "
            f"batch ladder (weight traffic amortizes; XLA fuses activations "
            f"better at batch). Best modeled config `{best}`: ceiling "
            f"{row['work'] / row['t_roofline_s']:.2f} img/s; reaching "
            f"{need_img_s:.2f} img/s (2x/$) requires achieved-fraction "
            f"eta >= **{eta_needed:.2f}** vs the {cal['eta_roofline']:.2f} "
            f"measured at batch-1 — plausible for an MXU-bound batched "
            f"executable, to be proven by an on-chip sd8 bench.")
    b8 = cps.get("sd_step_b8") or b4
    if b8:
        share = b8.get("param_bytes", 0) / b8["bytes_accessed"]
        lines.append(
            f"- **int8 UNet: evaluated and rejected** — UNet weights are "
            f"{b8.get('param_bytes', 0) / 1e9:.1f} GB of "
            f"{b8['bytes_accessed'] / 1e9:.1f} GB accessed per batched step "
            f"({share * 100:.0f}%); halving them moves the roofline by "
            f"<{max(1, round(share * 50))}%. Decode LLMs are the opposite "
            f"case (weights dominate): int8 already ships there, and the "
            f"model shows it "
            + (f"({cps['llama3b_decode']['t_roofline_s'] * 1e3:.0f} -> "
               f"{cps['llama3b_int8_decode']['t_roofline_s'] * 1e3:.0f} "
               f"ms/step on the 3B decode)."
               if "llama3b_int8_decode" in cps else "."))
    spec = comp.get("vllm_spec_decode_b8_k4")
    dec_row = cps.get("vllm_decode_b8")
    if spec and dec_row and spec.get("spec_model"):
        lines += ["", "## Speculative decoding (prompt-lookup k=4, "
                  "modeled vs acceptance rate)", "",
                  f"Vanilla decode roofline: "
                  f"{dec_row['t_roofline_s'] * 1e3:.2f} ms/token; verify "
                  f"(k+1 positions, one dispatch): "
                  f"{spec['t_roofline_s'] * 1e3:.2f} ms/step. A verify step "
                  f"commits `1 + a(1-a^k)/(1-a)` tokens at per-draft "
                  f"acceptance `a` — measured live as "
                  f"`spec_acceptance_rate` (serve /stats, bench.py "
                  f"llama_spec).", "",
                  "| accept rate | tokens/verify | modeled ms/token | "
                  "speedup vs decode |", "|---|---|---|---|"]
        for a, m in spec["spec_model"].items():
            lines.append(
                f"| {a} | {m['tokens_per_verify']:.2f} | "
                f"{m['s_per_token'] * 1e3:.2f} | "
                f"{m['speedup_vs_decode']:.2f}x |")
    if res.get("errors"):
        lines += ["", "## Errors", ""]
        lines += [f"- `{k}`: {v}" for k, v in res["errors"].items()]
    lines.append("")
    return "\n".join(lines)
