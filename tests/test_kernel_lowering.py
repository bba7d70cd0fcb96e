"""Every Pallas kernel lowers AND Mosaic-compiles for the v5e target.

Deviceless: ``perf.topo`` hands out compile-target devices, so this runs in
tier-1 on a CPU-only container and catches what interpret mode cannot — a
block shape, layout or broadcast the TPU compiler refuses. The cases are the
ones ``chip_smoke.py`` then runs on the chip against the XLA oracles
(``ops.kernel_check``): Mistral-7B heads on one chip and as a tp=4 shard,
block sizes 16 and 128, bf16 and int8-KV pools; and Kanana-2's latent
attention at its published widths: flash over keys of 192 beside values of
128 at the cell's buckets and its last continuation start, the absorbed
kernel over 640-lane rows at 64 rows, at its tile's edges and at the edges
of the groups a tile is waited for in (two slots of 1,024 x 640 bfloat16:
2.6 MB of scratch, inside Mosaic's default scoped VMEM, so the call states
no limit); and the streamed expert product at
both routed cells' widths and largest decode buckets (128 experts of 2048 x
768 at 64 rows, of 2048 x 1024 at 32) and the tiled one at the three routed
configurations' largest prefill programs (2048 rows over 128 of Kimi's 256
experts of 2304 x 1024, 2048 over Kanana's 128, 1024 over Trinity's); and
Kimi-Linear's: flash over 16,384
keys, KDA's chunk and step kernels at 32 heads of 128 (the chunk kernel
eight heads a grid step on the layer's own layout, no operand-sized array
made beside it); and Nemotron-3-Nano's:
the state-space chunk kernel over its largest prefill program (4 rows of
512) and its step kernel at 128 rows (64 heads of 64 x 128, 8 groups), both
expert kernels on two-matrix ``relu ** 2`` experts of 2688 x 1856 (1856 = 14
x 128 + 64: whole-width blocks), 64 of 128 held, and flash and the paged
kernel at 32 query heads over 2 key/value heads; and LFM2-24B-A2B's: both
expert kernels at 64 experts of 2048 x 1536 top-4, flash at 32 heads over 8
of 64 and, at the 128 lanes such heads are served on, flash and the paged
kernel (which Mosaic refuses at 64). The case builders themselves
are checked against their oracles in interpret mode by the smoke's dry run
(``tests/test_chip_smoke.py``; the expert cases by
``tests/test_moe_ffn_kernel.py``).
"""

import dataclasses

import jax
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from scalable_hw_agnostic_inference_tpu.ops import kernel_check


def _cases():
    seen = {}
    for tp in (1, 4):
        for block_size in (16, 128):
            for c in kernel_check.engine_cases(
                    32, 8, 128, tp=tp, block_size=block_size):
                seen.setdefault(c.name, c)   # flash repeats across blocks
    for c in kernel_check.latent_cases(32, 192, 128, 640, 512,
                                       max_num_seqs=64):
        seen.setdefault(c.name, c)
    for top_k, F, rows, prefill in ((6, 768, 64, 2048), (8, 1024, 32, 1024)):
        for c in kernel_check.expert_cases(128, top_k, 2048, F,
                                           max_num_seqs=rows,
                                           prefill_rows=prefill):
            seen.setdefault(c.name, c)
    # Kimi-Linear's share of its experts: 128 held of the 256 routed over
    seen.setdefault("experts-tiled-kimi", kernel_check.expert_cases(
        256, 8, 2304, 1024, max_num_seqs=16, prefill_rows=2048,
        held=128)[-1])
    # Kimi-Linear's flash call at its last continuation start (16k keys of
    # 192 beside values of 128: past Mosaic's default VMEM, which the call
    # asks to be raised), and KDA's two kernels at 32 heads of 128: the
    # chunk kernel over a 2048-token program, the step kernel at 16 rows
    seen.setdefault("flash-kimi-16k", kernel_check._latent_flash_case(
        32, 192, 128, 2048, 16384))
    for c in kernel_check.kda_cases(32, 128, bucket=2048, max_num_seqs=16):
        seen.setdefault(c.name, c)
    # head counts that eight does not divide, at the same 128 channels: 12
    # are padded to two groups of eight (16 heads a step are refused for
    # scoped VMEM), 5 go in one step as a whole dimension
    for heads in (12, 5):
        c = kernel_check._kda_chunk_case(heads, 128, 256, 1)
        seen.setdefault(c.name, c)
    # Nemotron-3-Nano's stage: both state-space kernels, the expert kernels
    # at a width that is 64 mod 128 (the streamed at 128 and 8 rows, the
    # tiled over a 2048-row program with half the experts held), and the
    # attention block's 32 query heads over TWO key/value heads
    for c in kernel_check.ssm_cases(64, 64, 128, 8, bucket=512,
                                    prefill_rows=4, max_num_seqs=128):
        seen.setdefault(c.name, c)
    for c in kernel_check.expert_cases(128, 6, 2688, 1856, max_num_seqs=128,
                                       prefill_rows=2048, held=64,
                                       act="relu2"):
        seen.setdefault(c.name, c)
    for c in kernel_check.engine_cases(32, 2, 128, buckets=(256, 512),
                                       max_model_len=2080,
                                       max_num_seqs=128):
        if "int8" not in c.name:      # the boot refuses an 8-bit pool here
            seen.setdefault(c.name, c)
    # LFM2-24B-A2B's stage: 64 experts of 3 x 2048 x 1536 top-4, all held
    # (the streamed kernel at 128 rows, the tiled one over 2048); flash at
    # its heads AS DECLARED (32 over 8 of 64), and flash and the paged
    # kernel at the 128 lanes the engine serves them on (``head_lanes``:
    # Mosaic refuses the paged kernel at 64, the test below holds that)
    for c in kernel_check.expert_cases(64, 4, 2048, 1536, max_num_seqs=128,
                                       prefill_rows=2048):
        seen.setdefault(c.name, c)
    for lanes in (64, 128):
        for c in kernel_check.engine_cases(32, 8, lanes, buckets=(256, 512),
                                           max_model_len=1600,
                                           max_num_seqs=128):
            if "int8" not in c.name and (lanes == 128 or "flash" in c.name):
                seen.setdefault(f"{c.name}-D{lanes}",
                                dataclasses.replace(
                                    c, name=f"{c.name}-D{lanes}"))
    return list(seen.values())


@pytest.fixture(scope="module")
def v5e_sharding():
    from scalable_hw_agnostic_inference_tpu.perf import topo

    try:
        devs = topo.topology_devices(1)
    except Exception as e:   # no libtpu / no deviceless topology support
        pytest.skip(f"v5e topology unavailable: {type(e).__name__}: {e}")
    return NamedSharding(Mesh(np.array(devs), ("x",)), P())


@pytest.mark.parametrize("case", _cases(), ids=lambda c: c.name)
def test_kernel_compiles_for_v5e(case, v5e_sharding):
    avals = jax.eval_shape(case.make_inputs, jax.random.PRNGKey(0))
    avals = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                       sharding=v5e_sharding), avals)
    jax.jit(lambda *a: case.kernel(*a, interpret=False)).lower(
        *avals).compile()


def test_the_paged_kernel_is_refused_at_heads_of_64(v5e_sharding):
    """Why a 64-wide head is cached on 128 lanes (``LlamaConfig.head_lanes``):
    Mosaic cannot slice 64 lanes of the pool's 128-lane tiles, so the paged
    kernel does not compile over a pool ``[N, bs * Hkv, 64]`` (and the v5e
    would store such a leaf with its blocks on the lanes and re-lay it whole
    before every call). A libtpu that takes it makes this test fail: the
    pad can then go (``ROADMAP.md`` Queue 1)."""
    case = [c for c in kernel_check.engine_cases(
        32, 8, 64, buckets=(256,), max_model_len=1600, max_num_seqs=128)
        if c.name.startswith("paged") and "int8" not in c.name][0]
    avals = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                       sharding=v5e_sharding),
        jax.eval_shape(case.make_inputs, jax.random.PRNGKey(0)))
    with pytest.raises(Exception, match="aligned to tiling"):
        jax.jit(lambda *a: case.kernel(*a, interpret=False)).lower(
            *avals).compile()


def _kimi_chunk_case():
    case = kernel_check.kda_cases(32, 128, bucket=2048, max_num_seqs=16)[0]
    assert case.name == "kda-chunk-H32x128-T2048-b1"
    return case


def test_the_kda_chunk_kernel_takes_the_layers_own_layout(v5e_sharding):
    """Kimi's 32 heads go eight a grid step, on blocks of the operands as
    the layer has them: the compiled call holds the kernel and NO copy,
    transpose or fusion of an operand-sized array beside it (the head-major
    form wrote seven of them a layer)."""
    from scalable_hw_agnostic_inference_tpu.ops.pallas import kda_chunk

    assert kda_chunk.HEAD_GROUP == 8
    case = _kimi_chunk_case()
    avals = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                       sharding=v5e_sharding),
        jax.eval_shape(case.make_inputs, jax.random.PRNGKey(0)))
    compiled = jax.jit(lambda *a: kda_chunk.kda_chunk_prefill(
        *a, interpret=False)).lower(*avals).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and kda_chunk.KERNEL_NAME in text
    # the operands are 33.5 MB each: nothing that size is made beside them
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 20


def test_the_kimi_shaped_chunk_case_still_refuses_a_bfloat16_state():
    """The case that lowers above is the one the chip holds the kernel to:
    at Kimi's shape its tolerance is still tens of times under what a state
    rounded to bfloat16 after every token gives (plain ``jnp``, no kernel)."""
    case = _kimi_chunk_case()
    assert case.tol == kernel_check.TOL_KDA == 2e-4
    assert kernel_check.kda_state_bf16_err(case) > 10 * case.tol
