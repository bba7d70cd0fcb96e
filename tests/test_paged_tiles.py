"""The paged kernel's tiles and the engine's account of them.

``ops.pallas.paged_attention.tile_tokens`` decides how many tokens one tile
of the pool kernel covers; the kernel walks ``cdiv(lengths[b], tile)`` tiles
a row and ``LLMEngine._note_dispatch_pad`` counts exactly those. The kernel
half is in ``tests/test_ops.py``, shown by poison: every pool block outside
a row's live tiles holds NaN, so a kernel that walks (or multiplies) more
than the count says returns NaN.
"""

import types

import jax.numpy as jnp
import numpy as np
import pytest

from scalable_hw_agnostic_inference_tpu.engine.engine import LLMEngine
from scalable_hw_agnostic_inference_tpu.obs.steploop import StepTelemetry
from scalable_hw_agnostic_inference_tpu.ops.pallas.paged_attention import (
    live_tile_tokens,
    tile_tokens,
)


@pytest.mark.parametrize("block_size,hkv,d,dtype,tile", [
    (16, 8, 128, jnp.bfloat16, 256),     # Mistral-7B on one chip
    (16, 2, 128, jnp.bfloat16, 256),     # its tp=4 shard
    (128, 8, 128, jnp.bfloat16, 256),    # two 128-token blocks a tile
    (16, 8, 128, jnp.int8, 256),         # int8 KV
    (16, 8, 128, jnp.float32, 128),      # the VMEM budget binds
    (48, 2, 64, jnp.bfloat16, 240),      # a whole number of blocks
    (4096, 8, 128, jnp.bfloat16, 256),   # a block larger than a tile: a part
])
def test_tile_tokens_from_what_the_call_sees(block_size, hkv, d, dtype, tile):
    assert tile_tokens(block_size, hkv, d, dtype) == tile
    # a tile is whole blocks, or an even part of one
    assert tile % block_size == 0 or block_size % tile == 0


@pytest.mark.parametrize("n,tile,walked", [
    (0, 256, 256), (1, 256, 256), (255, 256, 256), (256, 256, 256),
    (257, 256, 512), (2048, 256, 2048), (300, 128, 384)])
def test_live_tile_tokens(n, tile, walked):
    assert live_tile_tokens(n, tile) == walked


def _engine_stub(lengths, block_size, hkv, d, dtype):
    """What ``_note_dispatch_pad`` reads of an engine, and nothing else."""
    seqs = {i: types.SimpleNamespace(n_tokens=n)
            for i, n in enumerate(lengths)}
    running = [types.SimpleNamespace(req=types.SimpleNamespace(req_id=i))
               for i in seqs]
    eng = types.SimpleNamespace(
        _attn_tile=tile_tokens(block_size, hkv, d, dtype),
        cache=types.SimpleNamespace(seq=seqs.__getitem__),
        obs=StepTelemetry(), _window_pool_layers=())
    return eng, running


@pytest.mark.parametrize("lengths,block_size,Bb", [
    ([1, 19, 300, 2048], 16, 4),
    ([255, 256, 257], 16, 4),            # one batch pad row
    ([64, 448, 704, 130, 512, 513, 90, 333], 16, 8),
    ([5, 129, 256, 257], 128, 8),        # four pad rows
    ([700], 4096, 1),                    # a tile is a part of a block
])
def test_dispatch_pad_counts_the_kernels_live_tiles(lengths, block_size, Bb):
    eng, running = _engine_stub(lengths, block_size, 8, 128, jnp.bfloat16)
    LLMEngine._note_dispatch_pad(eng, running, Bb)
    tile = tile_tokens(block_size, 8, 128, jnp.bfloat16)
    walked = (sum(-(-n // tile) for n in lengths)
              + (Bb - len(lengths))) * tile
    by = eng.obs.snapshot()
    assert by["pad_by_phase"]["decode"] == {
        "real": sum(lengths), "pad": walked - sum(lengths)}


def test_dispatch_pad_scales_with_verify_rows():
    eng, running = _engine_stub([300, 20], 16, 8, 128, jnp.bfloat16)
    LLMEngine._note_dispatch_pad(eng, running, 2, rows_per_seq=4)
    assert eng.obs.snapshot()["pad_by_phase"]["verify"] == {
        "real": 4 * 320, "pad": 4 * (512 + 256 - 320)}


@pytest.mark.parametrize("hkv", [8, 2])   # one chip, the tp=4 shard
def test_decode_sat_like_batches_pad_under_35_percent(hkv):
    """The benchmark's ``decode-sat`` traffic: prompts log-uniform 64-448,
    answers uniform 128-256, 8 rows each somewhere along its answer."""
    rng = np.random.default_rng(25)
    eng, _ = _engine_stub([], 16, hkv, 128, jnp.bfloat16)
    for _ in range(400):
        lengths = [int(np.exp(rng.uniform(np.log(64), np.log(448))))
                   + int(rng.integers(0, rng.integers(128, 257)))
                   for _ in range(8)]
        step, running = _engine_stub(lengths, 16, hkv, 128, jnp.bfloat16)
        step.obs = eng.obs
        LLMEngine._note_dispatch_pad(step, running, 8)
    by = eng.obs.snapshot()["pad_by_phase"]["decode"]
    frac = by["pad"] / (by["real"] + by["pad"])
    assert 0.10 < frac < 0.35, frac
