"""``scripts/mla_bench.py``'s own parts, on the CPU at a tiny shape: the
lengths it draws, the operands it builds, and that its chained program is
the kernel called ``STEPS`` times on unchanged queries."""

import importlib.util
import os

import jax
import numpy as np
import pytest

from scalable_hw_agnostic_inference_tpu.ops import mla
from scalable_hw_agnostic_inference_tpu.ops.pallas.mla_paged_attention import (
    mla_paged_decode,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def bench():
    spec = importlib.util.spec_from_file_location(
        "mla_bench", os.path.join(ROOT, "scripts", "mla_bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_the_lengths_are_the_cells(bench):
    """A prompt log-uniform in 2,048-8,192 and a part of an answer of
    1,024-2,048: the mean the cell's own counter reads (5.2k), the same
    draw for the same seed."""
    n = bench.cell_lengths(4096, 8192, seed=1)
    assert n.min() >= 2048 and n.max() <= 8192 + 2048
    assert 5000 < n.mean() < 5400
    np.testing.assert_array_equal(n, bench.cell_lengths(4096, 8192, seed=1))
    assert (n != bench.cell_lengths(4096, 8192, seed=2)).any()


def test_the_operands_are_a_decode_steps(bench):
    lengths = np.asarray([0, 5, 16, 40], np.int32)
    q, pool, tables, n = bench.operands(4, 128, 8, 6, lengths)
    assert q.shape == (4, 4, 128) and pool.shape == (25, 8, 128)
    np.testing.assert_array_equal(np.asarray(n), lengths)
    tables = np.asarray(tables)
    held = np.arange(6)[None, :] * 8 < lengths[:, None]
    assert (tables[~held] == 0).all()           # 0 past a row's last block
    live = tables[held]
    assert live.min() >= 1 and len(set(live.tolist())) == live.size


def test_the_chained_program_is_the_kernel_again_and_again(bench):
    lengths = np.asarray([3, 70, 21], np.int32)
    args = bench.operands(4, 128, 8, 10, lengths)
    kernel = lambda q, c, t, n: mla_paged_decode(      # noqa: E731
        q, c, t, n, rank=32, scale=0.1, interpret=True)
    got = bench.chained(kernel, 32)(*args)
    # the queries come back as they went in: every step saw the same ones
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(args[0], np.float32))
    u = kernel(*args)
    want = mla.latent_gather_attention(
        args[0][:, None], args[1], args[2], (args[3] - 1)[:, None],
        rank=32, scale=0.1)[:, 0]
    np.testing.assert_allclose(np.asarray(u, np.float32),
                               np.asarray(want, np.float32), atol=2 ** -5)
    rec = bench.time_kernel(bench.chained(kernel, 32), "tiny", lengths, H=4,
                            width=128, block_size=8, blocks_per_seq=10, n=1,
                            trace=False, rank=32)
    assert rec["visible_tokens"] == 94 and rec["ms_host_clock"] > 0
    assert "ms_device_trace" not in rec     # no device, no device time
