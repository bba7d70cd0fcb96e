"""A prefill program writes its own blocks, not the whole pool.

The block scatter of ``engine.runner._scatter_blocks`` has to update the
donated pool leaf IN PLACE. Given a scatter into the 4-D leaf the TPU
compiler re-lays the whole leaf into a layout with a block's tokens on the
sublanes and back again wherever the leaf has fewer than eight kv heads a
device (two pool-sized copies a leaf a program: 10 ms of Trinity-Mini's
27 ms prefill program); through the leaf's flat view it compiles to bitcast,
scatter, bitcast. That is a property of the COMPILED program, so this file
compiles for the v5e with no chip attached (``perf.topo``, as
``tests/test_kernel_lowering.py`` does) and reads the program's text: the
seam alone over each benchmark configuration's pool leaf, then one whole
prefill and one whole continuation program at Trinity-Mini's widths.
"""

import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from scalable_hw_agnostic_inference_tpu.engine import runner
from scalable_hw_agnostic_inference_tpu.models.llama import (
    LlamaConfig,
    geometry_params,
)

SDS = jax.ShapeDtypeStruct
BLOCK, HEAD_DIM = 16, 128
#: `%name = dtype[dims]{layout} opcode(`: an instruction with an array result
_INSTR = re.compile(
    r"%[\w.\-]+ = \w+\[([\d,]+)\](?:\{[^}]*\})? ([\w\-]+)\(")
#: what may yield a pool-sized array: the leaf coming in, the scatter (and
#: the fusion libtpu wraps it in), and views that move no byte
_IN_PLACE = {"parameter", "scatter", "fusion", "bitcast",
             "get-tuple-element"}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental.compilation_cache import compilation_cache

    from scalable_hw_agnostic_inference_tpu.perf import topo

    try:
        topo.topology_devices(4)
    except Exception as e:   # no libtpu / no deviceless topology support
        pytest.skip(f"v5e topology unavailable: {type(e).__name__}: {e}")
    # where an earlier test of this process turned JAX's persistent cache
    # on: a deviceless compile is written to it but cannot be read back
    # without a chip, and the next run would warn at every case
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def pool_sized(compiled, elements):
    """``[(opcode, line)]`` of every instruction of the compiled program,
    fused computations included, whose result has ``elements`` elements."""
    out = []
    for line in compiled.as_text().splitlines():
        m = _INSTR.search(line)
        if m and math.prod(int(d) for d in m.group(1).split(",")) == elements:
            out.append((m.group(2), line.strip()[:160]))
    return out


def _shardings(topo, cfg, tp):
    if tp == 1:
        return None, NamedSharding(topo.device_mesh(1), P())
    sh = runner.EngineShardings(
        topo.device_mesh(tp),
        jax.eval_shape(lambda: geometry_params(cfg)), cfg)
    return sh, sh.rep


def _pool(cfg, n_blocks, n_layers, sh, rep, quant=False):
    heads = (n_blocks, BLOCK, cfg.n_kv_heads, cfg.head_dim)
    at = (lambda n: rep) if sh is None else (
        lambda n: sh.kv_pool(1, quant)[0][n])
    layer = {n: SDS(heads, jnp.int8 if quant else jnp.bfloat16,
                    sharding=at(n)) for n in ("k", "v")}
    if quant:
        layer.update({n: SDS((n_blocks, cfg.n_kv_heads), jnp.float32,
                             sharding=at(n)) for n in ("ks", "vs")})
    return [dict(layer) for _ in range(n_layers)]


# name -> (kv heads, devices, int8 pool, blocks): the pool leaves of the
# benchmark's configurations (Mistral-7B on one chip and split over four,
# Trinity-Mini's four heads over its 10,241 blocks) and the int8 pool
SEAM_CASES = {
    "bf16-8h": (8, 1, False, 8192),
    "bf16-4h": (4, 1, False, 10241),
    "bf16-2h": (2, 1, False, 8192),
    "bf16-8h-tp4": (8, 4, False, 8192),
    "int8-8h": (8, 1, True, 8192),
    "int8-4h": (4, 1, True, 10241),
}


@pytest.mark.parametrize("case", SEAM_CASES)
def test_block_scatter_is_in_place(case, topo):
    heads, tp, quant, n_blocks = SEAM_CASES[case]
    cfg = LlamaConfig(vocab_size=512, dim=heads * 4 * HEAD_DIM, n_layers=1,
                      n_heads=heads * 4, n_kv_heads=heads,
                      head_dim=HEAD_DIM, mlp_dim=512)
    sh, rep = _shardings(topo, cfg, tp)
    layer = _pool(cfg, n_blocks, 1, sh, rep, quant)[0]
    B, m = 2, 64
    fresh = SDS((B, m, BLOCK, heads, HEAD_DIM), jnp.bfloat16,
                sharding=rep if sh is None else NamedSharding(
                    sh.mesh, P(None, None, None, "tp", None)))
    compiled = jax.jit(
        lambda layer, tbl, k, v: runner._scatter_blocks(
            layer, tbl, k, v, quant, sh),
        donate_argnums=0).lower(
            layer, SDS((B, m), jnp.int32, sharding=rep), fresh,
            fresh).compile()
    # the int8 pool's scales [N, Hkv] f32 are not held to this: the chip
    # stores them block-minor and re-lays them around a row scatter in any
    # form, 262 KB a leaf where a pool leaf is 134 MB
    leaf = n_blocks * BLOCK * (heads // tp) * HEAD_DIM
    found = pool_sized(compiled, leaf)
    assert sum(op == "scatter" for op, _ in found) == 2, found
    moved = [line for op, line in found if op not in _IN_PLACE]
    assert not moved, "\n".join(moved)
    itemsize = 1 if quant else 2
    assert compiled.memory_analysis().temp_size_in_bytes < leaf * itemsize


@pytest.mark.parametrize("program", ["prefill", "cont"])
def test_trinity_program_copies_no_pool_leaf(program, topo):
    """Trinity-Mini's widths, a window layer and a full one (both expert
    layers), the cell's pool and its largest bucket: no instruction of the
    whole program copies a pool leaf."""
    cfg = LlamaConfig.trinity_mini(
        ("sliding_attention", "full_attention"), 0)
    n_blocks, blocks_per_seq, bucket = 10241, 320, 1024
    _, rep = _shardings(topo, cfg, 1)
    params = jax.tree.map(
        lambda a: SDS(a.shape, a.dtype, sharding=rep),
        jax.eval_shape(lambda: geometry_params(cfg)))
    kv = _pool(cfg, n_blocks, cfg.n_layers, None, rep)
    if program == "prefill":
        fn, K = runner.make_prefill(cfg, BLOCK, blocks_per_seq, bucket,
                                    n_seqs=2), 2
    else:
        fn, K = runner.make_prefill_cont(cfg, BLOCK, blocks_per_seq, bucket,
                                         start_blocks=bucket // BLOCK), 1
    with topo.platform_override("tpu"):   # the chip's kernels, not the CPU's
        lowered = fn.lower(
            params, kv, SDS((K, bucket), jnp.int32, sharding=rep),
            SDS((K,), jnp.int32, sharding=rep),
            SDS((K, blocks_per_seq), jnp.int32, sharding=rep))
    compiled = lowered.compile()
    leaf = n_blocks * BLOCK * cfg.n_kv_heads * cfg.head_dim
    found = pool_sized(compiled, leaf)
    assert sum(op == "scatter" for op, _ in found) >= 2 * cfg.n_layers
    copies = [line for op, line in found if op == "copy"]
    assert not copies, "\n".join(copies)


def test_lfm2_decode_program_compiles_and_copies_no_pool_leaf(topo):
    """LFM2-24B-A2B's stage at the cell's sizes (128 rows, 12,864 blocks,
    every expert held): the whole decode program compiles for the v5e, the
    pool's leaves come in and go out in their own row-major order on 128
    lanes a head (``head_lanes``; at the declared 64 the chip lays such a
    leaf out with its BLOCKS on the lanes and Mosaic refuses the paged
    kernel), and nothing pool-sized is copied."""
    from scalable_hw_agnostic_inference_tpu.models.llama import (
        cache_leaves,
        state_leaves,
    )

    cfg = LlamaConfig.lfm2_24b_stage()
    n_blocks, M, B = 12864, 100, 128
    _, rep = _shardings(topo, cfg, 1)
    s = lambda shape, dt: SDS(shape, dt, sharding=rep)    # noqa: E731
    params = jax.tree.map(lambda a: s(a.shape, a.dtype),
                          jax.eval_shape(lambda: geometry_params(cfg)))
    leaf = {n: s((n_blocks, BLOCK) + per, jnp.bfloat16)
            for n, per in cache_leaves(cfg).items()}
    assert leaf["k"].shape == (n_blocks, BLOCK, 8, 128)
    arena = {n: s((B + 1,) + tuple(shp), jnp.bfloat16)
             for n, (shp, _) in state_leaves(cfg).items()}
    kv = [dict(arena if pi in cfg.state_layers else leaf) for pi in range(9)]
    with topo.platform_override("tpu"):
        lowered = runner.make_decode(
            cfg, BLOCK, M, B, paged=True, feedback=True).lower(
            params, kv, s((B,), jnp.int32), s((B,), jnp.int32),
            s((B, M), jnp.int32), s((B,), jnp.float32),
            s((2,), jnp.uint32), s((), jnp.int32), s((B,), jnp.float32),
            s((B,), jnp.int32), s((B,), jnp.float32), s((B,), jnp.int32))
    compiled = lowered.compile()
    text = compiled.as_text()
    assert "bf16[12864,16,8,128]{3,2,1,0:T(8,128)(2,1)} parameter" in text
    found = pool_sized(compiled, n_blocks * BLOCK * 8 * 128)
    assert sum(op == "scatter" for op, _ in found) == 4      # k, v x 2
    copies = [line for op, line in found if op == "copy"]
    assert not copies, "\n".join(copies)
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes == pytest.approx(12.05e9, rel=1e-3)
    assert mem.temp_size_in_bytes < 2 ** 28


def test_nemotron_decode_program_steps_each_arena_in_place(topo):
    """Nemotron-3-Nano's stage at the cell's sizes (128 rows, 16,704
    blocks, 64 of 128 experts held): the compiled decode program holds ONE
    custom call named ``ssm_decode_step`` a mixer block, each with a state
    arena as an operand ALIASED to its output, and nothing else yields an
    arena-sized array but the arenas coming in: no copy, gather or scatter
    of 129 slots of 2 MiB. ``ssm_decode_share.ssm`` and
    ``ssm_decode_hbm_roofline.ssm`` read the device's trace by that one
    name, and price a row's state read and written once."""
    from scalable_hw_agnostic_inference_tpu.models.llama import (
        cache_leaves,
        state_leaves,
    )

    cfg = LlamaConfig.nemotron3_nano_stage()
    n_blocks, M, B = 16704, 130, 128
    mixers = len(cfg.state_layers)
    _, rep = _shardings(topo, cfg, 1)
    s = lambda shape, dt: SDS(shape, dt, sharding=rep)    # noqa: E731
    params = jax.tree.map(lambda a: s(a.shape, a.dtype),
                          jax.eval_shape(lambda: geometry_params(cfg)))
    leaf = {n: s((n_blocks, BLOCK) + per, jnp.bfloat16)
            for n, per in cache_leaves(cfg).items()}
    arena = {n: s((B + 1,) + tuple(shp), jnp.dtype(dt or jnp.bfloat16))
             for n, (shp, dt) in state_leaves(cfg).items()}
    assert arena["s"].shape == (129, 64, 64, 128) and mixers == 4
    assert arena["s"].dtype == jnp.float32
    kv = [dict(arena if pi in cfg.state_layers else leaf) for pi in range(5)]
    with topo.platform_override("tpu"):
        lowered = runner.make_decode(
            cfg, BLOCK, M, B, paged=True, feedback=True).lower(
            params, kv, s((B,), jnp.int32), s((B,), jnp.int32),
            s((B, M), jnp.int32), s((B,), jnp.float32),
            s((2,), jnp.uint32), s((), jnp.int32), s((B,), jnp.float32),
            s((B,), jnp.int32), s((B,), jnp.float32), s((B,), jnp.int32))
    compiled = lowered.compile()
    calls = [line for line in compiled.as_text().splitlines()
             if "tpu_custom_call" in line
             and re.search(r'op_name="[^"]*ssm_decode_step', line)]
    assert len(calls) == mixers, len(calls)
    for line in calls:
        # output 1 of (y, arena) IS an operand, and it is the one arena
        assert re.search(r"output_to_operand_aliasing=\{\{1\}: \(\d+, ", line)
        result = line.split(" custom-call(")[0]
        assert result.count("f32[129,64,64,128]") == 1, line[:300]
    found = pool_sized(compiled, 129 * 64 * 64 * 128)
    moved = [line for op, line in found
             if op not in ("parameter", "bitcast", "get-tuple-element")]
    assert not moved, "\n".join(moved)
    assert sum(op == "parameter" for op, _ in found) == mixers
