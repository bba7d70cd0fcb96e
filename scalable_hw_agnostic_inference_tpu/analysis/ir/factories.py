"""The jaxpr-lint program registry: build every registered executable
factory at tiny geometry for IR inspection.

Each builder returns an :class:`~.program.IrProgram` wrapping the REAL
factory from ``engine/runner.py`` / ``core/aot.py`` / ``parallel/ring.py``
— never a copy of its body — called with a config small enough that
trace+lower stays in the hundreds of milliseconds. ``@tp2``/``@sp2``
variants build on a 2-way mesh of virtual CPU devices (the same
virtual-device discipline as the dryrun legs and ``tests/conftest.py``);
``@tp2_paged`` lowers the Pallas paged path for the ``tpu`` platform
(trace + SPMD partition only — the Mosaic kernel cannot compile on CPU,
which is also why donation aliasing for that leg is judged at the
lowering tier).

Geometry is shared across builders so composition members compare
like-for-like: B=2 slots, block_size=8, blocks_per_seq=4, 16-block pool,
one 16-token prefill bucket, k=2 speculative draft.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

from .program import IrProgram

# shared tiny geometry (every builder; compositions must match shapes)
B = 2            # slot batch
BS = 8           # block size
BPS = 4          # blocks per sequence
TOT = 16         # pool blocks
BUCKET = 16      # prefill bucket
K_SPEC = 2       # speculative draft length
LV = 8           # vision-state rows (cross programs)

RUNNER = "engine/runner.py"
AOT = "core/aot.py"
RING = "parallel/ring.py"


def _tiny_cfg(cross: bool = False):
    from ...models.llama import LlamaConfig

    if cross:
        return LlamaConfig(
            vocab_size=128, dim=32, n_layers=3, n_heads=2, n_kv_heads=2,
            mlp_dim=64, max_seq_len=64, tie_embeddings=True,
            cross_attention_layers=(1,))
    return LlamaConfig(
        vocab_size=128, dim=32, n_layers=2, n_heads=2, n_kv_heads=2,
        mlp_dim=64, max_seq_len=64, tie_embeddings=True)


def _mesh(axis: str):
    """A 2-way mesh over virtual CPU devices (dryrun discipline)."""
    import jax

    from ...core.mesh import build_mesh

    devs = jax.devices()
    if len(devs) < 2:
        raise RuntimeError(
            f"jaxpr-lint needs >= 2 devices for @{axis}2 programs; jax "
            f"sees {len(devs)}. Run via scripts/shai_lint.py --ir (it "
            f"forces the 8-virtual-CPU-device platform) or set XLA_FLAGS="
            f"--xla_force_host_platform_device_count=8 before jax import.")
    return build_mesh(f"{axis}=2", devices=devs[:2])


def _param_sds(cfg, shardings=None):
    import jax

    from ...models.llama import geometry_params

    shapes = jax.eval_shape(lambda: geometry_params(cfg))
    if shardings is None:
        return shapes, jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), shapes)
    return shapes, jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        shapes, shardings.params)


def _kv_sds(cfg, shardings=None, quant: bool = False):
    import jax
    import jax.numpy as jnp

    n_self = cfg.n_layers - len(cfg.cross_attention_layers)
    shape = (TOT, BS, cfg.n_kv_heads, cfg.head_dim)
    sc_shape = (TOT, cfg.n_kv_heads)
    blk_dt = jnp.int8 if quant else jnp.bfloat16

    def lay():
        if shardings is None:
            d = {n: jax.ShapeDtypeStruct(shape, blk_dt) for n in ("k", "v")}
            if quant:
                d.update({n: jax.ShapeDtypeStruct(sc_shape, jnp.float32)
                          for n in ("ks", "vs")})
            return d
        d = {n: jax.ShapeDtypeStruct(shape, blk_dt,
                                     sharding=shardings.kv_layer[n])
             for n in ("k", "v")}
        if quant:
            d.update({n: jax.ShapeDtypeStruct(
                sc_shape, jnp.float32, sharding=shardings.kv_scale)
                for n in ("ks", "vs")})
        return d

    return [lay() for _ in range(n_self)]


def _sds(shape, dtype, sharding=None):
    import jax

    if sharding is None:
        return jax.ShapeDtypeStruct(shape, dtype)
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _engine_shardings(cfg, mesh):
    import jax

    from ...engine.runner import EngineShardings
    from ...models.llama import geometry_params

    shapes = jax.eval_shape(lambda: geometry_params(cfg))
    return EngineShardings(mesh, shapes, cfg)


def _decode_args(cfg, rep=None, shardings=None, quant: bool = False):
    import jax.numpy as jnp

    _, params = _param_sds(cfg, shardings)
    kv = _kv_sds(cfg, shardings, quant=quant)
    return (params, kv,
            _sds((B,), jnp.int32, rep),        # tokens
            _sds((B,), jnp.int32, rep),        # pos
            _sds((B, BPS), jnp.int32, rep),    # tables
            _sds((B,), jnp.bool_, rep),        # active
            _sds((2,), jnp.uint32, rep),       # rng
            _sds((), jnp.int32, rep),          # fold
            _sds((B,), jnp.float32, rep),      # temperature
            _sds((B,), jnp.int32, rep),        # top_k
            _sds((B,), jnp.float32, rep))      # top_p


def _build_decode(key: str, feedback: bool, tp: bool = False,
                  paged: bool = False, artifact: bool = False,
                  compile_cpu: bool = False,
                  kv_quant: bool = False) -> IrProgram:
    from ...engine.runner import make_decode

    cfg = _tiny_cfg()
    sh = _engine_shardings(cfg, _mesh("tp")) if tp else None
    fn = make_decode(cfg, BS, BPS, max_num_seqs=B, shardings=sh,
                     paged=paged, feedback=feedback, kv_quant=kv_quant)
    args = _decode_args(cfg, rep=sh.rep if sh else None, shardings=sh,
                        quant=kv_quant)
    return IrProgram(
        key=key, factory="make_decode", anchor_path=RUNNER, jitted=fn,
        args=args, donate_args=(1, 3) if feedback else (1,),
        compile_cpu=compile_cpu,
        lowering_platforms=("tpu",) if paged else None,
        artifact=artifact)


def _build_prefill(key: str, tp: bool = False,
                   kv_quant: bool = False) -> IrProgram:
    import jax.numpy as jnp

    from ...engine.runner import make_prefill

    cfg = _tiny_cfg()
    sh = _engine_shardings(cfg, _mesh("tp")) if tp else None
    fn = make_prefill(cfg, BS, BPS, BUCKET, n_seqs=1, shardings=sh,
                      kv_quant=kv_quant)
    rep = sh.rep if sh else None
    _, params = _param_sds(cfg, sh)
    args = (params, _kv_sds(cfg, sh, quant=kv_quant),
            _sds((1, BUCKET), jnp.int32, rep),
            _sds((1,), jnp.int32, rep),
            _sds((1, BPS), jnp.int32, rep))
    return IrProgram(key=key, factory="make_prefill", anchor_path=RUNNER,
                     jitted=fn, args=args, donate_args=(1,),
                     compile_cpu=not tp)


def _build_prefill_cont(key: str) -> IrProgram:
    import jax.numpy as jnp

    from ...engine.runner import make_prefill_cont

    cfg = _tiny_cfg()
    fn = make_prefill_cont(cfg, BS, BPS, BUCKET, start_blocks=2)
    _, params = _param_sds(cfg)
    args = (params, _kv_sds(cfg),
            _sds((1, BUCKET), jnp.int32),
            _sds((1,), jnp.int32),
            _sds((1, BPS), jnp.int32))
    return IrProgram(key=key, factory="make_prefill_cont",
                     anchor_path=RUNNER, jitted=fn, args=args,
                     donate_args=(1,))


def _build_tier_restore_quant(key: str) -> IrProgram:
    # the quantized restore scatter: int8 blocks + f32 scale rows move in
    # ONE donated call per layer (all four pool buffers donate-and-rebind)
    import jax.numpy as jnp

    from ...kvtier.restore import make_tier_restore

    cfg = _tiny_cfg()
    fn = make_tier_restore(quant=True)
    pool = (TOT, BS, cfg.n_kv_heads, cfg.head_dim)
    sc = (TOT, cfg.n_kv_heads)
    host = (2, BS, cfg.n_kv_heads, cfg.head_dim)
    host_sc = (2, cfg.n_kv_heads)
    args = (_sds(pool, jnp.int8), _sds(pool, jnp.int8),
            _sds(sc, jnp.float32), _sds(sc, jnp.float32),
            _sds((2,), jnp.int32),
            _sds(host, jnp.int8), _sds(host, jnp.int8),
            _sds(host_sc, jnp.float32), _sds(host_sc, jnp.float32))
    return IrProgram(key=key, factory="make_tier_restore",
                     anchor_path="kvtier/restore.py", jitted=fn, args=args,
                     donate_args=(0, 1, 2, 3), compile_cpu=True)


def _build_verify(key: str) -> IrProgram:
    import jax.numpy as jnp

    from ...engine.runner import make_verify

    cfg = _tiny_cfg()
    fn = make_verify(cfg, BS, BPS, max_num_seqs=B, k=K_SPEC, paged=False)
    _, params = _param_sds(cfg)
    args = (params, _kv_sds(cfg),
            _sds((B, K_SPEC + 1), jnp.int32),
            _sds((B,), jnp.int32),
            _sds((B, BPS), jnp.int32),
            _sds((B,), jnp.bool_),
            _sds((2,), jnp.uint32),
            _sds((), jnp.int32),
            _sds((B,), jnp.float32),
            _sds((B,), jnp.int32),
            _sds((B,), jnp.float32))
    return IrProgram(key=key, factory="make_verify", anchor_path=RUNNER,
                     jitted=fn, args=args, donate_args=(1,))


def _build_cross_kv(key: str) -> IrProgram:
    import jax.numpy as jnp

    from ...engine.runner import make_cross_kv

    cfg = _tiny_cfg(cross=True)
    fn = make_cross_kv(cfg)
    _, params = _param_sds(cfg)
    args = (params, _sds((LV, cfg.dim), jnp.float32))
    return IrProgram(key=key, factory="make_cross_kv", anchor_path=RUNNER,
                     jitted=fn, args=args, donate_args=())


def _build_cross_slot_write(key: str) -> IrProgram:
    import jax.numpy as jnp

    from ...engine.runner import make_cross_slot_write

    cfg = _tiny_cfg(cross=True)
    fn = make_cross_slot_write(cfg)
    n_cross = len(cfg.cross_attention_layers)
    cross_kv = [{n: _sds((B, LV, cfg.n_kv_heads, cfg.head_dim),
                         jnp.bfloat16) for n in ("k", "v")}
                for _ in range(n_cross)]
    per_layer = [{n: _sds((LV, cfg.n_kv_heads, cfg.head_dim),
                          jnp.bfloat16) for n in ("k", "v")}
                 for _ in range(n_cross)]
    args = (cross_kv, per_layer, _sds((), jnp.int32))
    return IrProgram(key=key, factory="make_cross_slot_write",
                     anchor_path=RUNNER, jitted=fn, args=args,
                     donate_args=(0,), compile_cpu=True)


def _build_ring(key: str, causal: bool) -> IrProgram:
    import jax
    import jax.numpy as jnp

    from ...parallel.ring import ring_attention

    mesh = _mesh("sp")

    def fn(q, k, v):
        return ring_attention(q, k, v, mesh, causal=causal)

    qkv = tuple(_sds((1, 2, 16, 8), jnp.float32) for _ in range(3))
    return IrProgram(key=key, factory="ring_attention", anchor_path=RING,
                     jitted=jax.jit(fn), args=qkv, donate_args=())


def _build_ulysses(key: str) -> IrProgram:
    import jax
    import jax.numpy as jnp

    from ...parallel.ring import ulysses_attention

    mesh = _mesh("sp")

    def fn(q, k, v):
        return ulysses_attention(q, k, v, mesh)

    qkv = tuple(_sds((1, 2, 16, 8), jnp.float32) for _ in range(3))
    return IrProgram(key=key, factory="ulysses_attention",
                     anchor_path=RING, jitted=jax.jit(fn), args=qkv,
                     donate_args=())


def _build_tier_restore(key: str) -> IrProgram:
    import jax.numpy as jnp

    from ...kvtier.restore import make_tier_restore

    cfg = _tiny_cfg()
    fn = make_tier_restore()
    pool = (TOT, BS, cfg.n_kv_heads, cfg.head_dim)
    host = (2, BS, cfg.n_kv_heads, cfg.head_dim)  # a 2-block restore batch
    args = (_sds(pool, jnp.bfloat16), _sds(pool, jnp.bfloat16),
            _sds((2,), jnp.int32),
            _sds(host, jnp.bfloat16), _sds(host, jnp.bfloat16))
    return IrProgram(key=key, factory="make_tier_restore",
                     anchor_path="kvtier/restore.py", jitted=fn, args=args,
                     donate_args=(0, 1), compile_cpu=True)


def _build_aot_export(key: str) -> IrProgram:
    # the artifact tier: the SAME decode executable, but inspected after a
    # jax.export serialize/deserialize roundtrip — what AotCache persists
    # and a booting pod loads. Anchored at AotCache.export.
    p = _build_decode(key, feedback=False, artifact=True)
    return IrProgram(key=key, factory="AotCache.export", anchor_path=AOT,
                     jitted=p.jitted, args=p.args, donate_args=(1,),
                     artifact=True)


BUILDERS = {
    "prefill": lambda k: _build_prefill(k),
    "prefill@tp2": lambda k: _build_prefill(k, tp=True),
    "prefill_cont": lambda k: _build_prefill_cont(k),
    "decode": lambda k: _build_decode(k, feedback=False, compile_cpu=True),
    "decode_feedback": lambda k: _build_decode(k, feedback=True,
                                               compile_cpu=True),
    "decode@tp2": lambda k: _build_decode(k, feedback=False, tp=True,
                                          compile_cpu=True),
    "decode_feedback@tp2": lambda k: _build_decode(k, feedback=True,
                                                   tp=True,
                                                   compile_cpu=True),
    "decode@tp2_paged": lambda k: _build_decode(k, feedback=False, tp=True,
                                                paged=True),
    # int8 KV pool (SHAI_KV_QUANT): the quantized scatter (prefill write),
    # the requantizing decode write + in-executable dequant reads, and the
    # scale-carrying tier restore
    "prefill_kvquant": lambda k: _build_prefill(k, kv_quant=True),
    "decode_kvquant": lambda k: _build_decode(k, feedback=False,
                                              kv_quant=True,
                                              compile_cpu=True),
    "tier_restore_quant": lambda k: _build_tier_restore_quant(k),
    "verify": lambda k: _build_verify(k),
    "cross_kv": lambda k: _build_cross_kv(k),
    "cross_slot_write": lambda k: _build_cross_slot_write(k),
    "tier_restore": lambda k: _build_tier_restore(k),
    "aot_decode_export": lambda k: _build_aot_export(k),
    "ring@sp2": lambda k: _build_ring(k, causal=False),
    "ring_causal@sp2": lambda k: _build_ring(k, causal=True),
    "ulysses@sp2": lambda k: _build_ulysses(k),
}


def build_programs(contract, keys: Optional[Tuple[str, ...]] = None
                   ) -> List[IrProgram]:
    """Build (not yet prepare) the registered programs. ``keys`` narrows
    the selection; unknown keys raise so a contract typo cannot silently
    skip a factory."""
    wanted = tuple(keys) if keys else tuple(contract.ir.programs)
    unknown = [k for k in wanted if k not in BUILDERS]
    if unknown:
        raise KeyError(
            f"unknown IR program key(s) {unknown}; registered: "
            f"{sorted(BUILDERS)}")
    return [BUILDERS[k](k) for k in wanted]
