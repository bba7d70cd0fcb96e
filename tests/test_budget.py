"""HBM budget validator (core.budget) — VERDICT r3 missing #2 / weak #4.

The declared production geometries must provably fit chips x 16 GiB and
shard legally, from config alone (jax.eval_shape — no hardware, no big
arrays). These tests pin the math, the failure modes, and the committed
geometries themselves.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from scalable_hw_agnostic_inference_tpu.core.budget import (
    GIB,
    HbmBudgetError,
    causal_lm_budget,
    params_bytes_per_chip,
)
from scalable_hw_agnostic_inference_tpu.engine import EngineConfig
from scalable_hw_agnostic_inference_tpu.models.llama import (
    LlamaConfig,
    LlamaForCausalLM,
    tp_rules,
)


def _ecfg(**kw):
    base = dict(max_model_len=256, max_num_seqs=2, block_size=16,
                context_encoding_buckets=(64, 256), tensor_parallel_size=1)
    base.update(kw)
    return EngineConfig(**base)


def test_param_bytes_exact_for_tiny():
    cfg = LlamaConfig.tiny()
    model = LlamaForCausalLM(cfg, dtype=jnp.float32)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))
    n_elems = sum(int(np.prod(l.shape))
                  for l in jax.tree_util.tree_leaves(shapes))
    total = params_bytes_per_chip(shapes, tp_rules(), {"tp": 1}, 2.0)
    assert total == pytest.approx(2.0 * n_elems)


def test_tp_divides_sharded_params():
    cfg = LlamaConfig.tiny()  # dim 64, mlp 128 — divisible by 2
    model = LlamaForCausalLM(cfg, dtype=jnp.float32)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))
    full = params_bytes_per_chip(shapes, tp_rules(), {"tp": 1}, 2.0)
    half = params_bytes_per_chip(shapes, tp_rules(), {"tp": 2}, 2.0)
    # sharded weights halve; norms/embedding-per-token stay replicated
    assert full / 2 < half < full


def test_illegal_sharding_raises():
    # dim 64 heads: a tp that does not divide the projection out-dim
    cfg = LlamaConfig.tiny()
    model = LlamaForCausalLM(cfg, dtype=jnp.float32)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))
    with pytest.raises(HbmBudgetError, match="not divisible"):
        params_bytes_per_chip(shapes, tp_rules(), {"tp": 48}, 2.0)


def test_tiny_fits_and_absurd_window_does_not():
    cfg = LlamaConfig.tiny()
    assert causal_lm_budget(cfg, _ecfg()).fits
    # 1M-token window x 64 seqs of dense KV cannot fit one chip
    big = _ecfg(max_model_len=1 << 20, max_num_seqs=64,
                context_encoding_buckets=(1 << 20,))
    b = causal_lm_budget(LlamaConfig.llama3_8b(), big)
    assert not b.fits
    with pytest.raises(HbmBudgetError, match="OVER BUDGET"):
        b.check()


@pytest.mark.slow  # tier-1 budget: see scripts/check_tier1_budget.py
def test_70b_needs_multichip():
    cfg = LlamaConfig.llama3_70b()
    one = causal_lm_budget(cfg, _ecfg(max_model_len=8192, max_num_seqs=1,
                                      context_encoding_buckets=(1024, 8192)))
    assert not one.fits          # 140 GiB of bf16 params on one 16 GiB chip
    tp32 = causal_lm_budget(cfg, _ecfg(max_model_len=8192, max_num_seqs=1,
                                       context_encoding_buckets=(1024, 8192),
                                       tensor_parallel_size=32))
    assert tp32.fits


def test_int8_counts_per_leaf_not_uniform():
    """ADVICE r4: int8 quantizes ONLY the matmul kernels — embeddings and
    norms stay bf16, so the budget must count them at full width (a uniform
    1.02 bytes/elem under-counted the 11B mllama embed by ~0.5 GiB)."""
    from scalable_hw_agnostic_inference_tpu.ops.quant import (
        quantized_kernel_paths,
    )

    cfg = LlamaConfig.llama3_8b()
    bf16 = causal_lm_budget(cfg, _ecfg())
    int8 = causal_lm_budget(cfg, _ecfg(quantization="int8"))
    # strictly above the old uniform under-count, strictly below bf16
    assert bf16.params_gib * 1.02 / 2 < int8.params_gib < bf16.params_gib

    # exact cross-check against the quantizer's own conversion predicate
    # (quantized_kernel_paths shares _is_quant_node with the converter)
    model = LlamaForCausalLM(cfg, dtype=jnp.float32)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))
    qpaths = quantized_kernel_paths(shapes)
    assert qpaths and all(p.endswith("/kernel") for p in qpaths)
    assert not any("embed" in p or "norm" in p for p in qpaths)
    expected = 0.0
    for path, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0]:
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        expected += int(np.prod(leaf.shape)) * (1.02 if name in qpaths
                                                else 2.0)
    assert int8.params_gib == pytest.approx(expected / GIB, rel=1e-6)
    # KV pool is NOT quantized (weight-only)
    assert int8.kv_gib == pytest.approx(bf16.kv_gib)


def test_cross_attention_kv_counted():
    cfg = LlamaConfig.tiny()
    mcfg = LlamaConfig(
        vocab_size=512, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
        mlp_dim=128, max_seq_len=256, rope_theta=10000.0,
        tie_embeddings=True, cross_attention_layers=(1,))
    plain = causal_lm_budget(cfg, _ecfg())
    cross = causal_lm_budget(mcfg, _ecfg(), cross_seq_len=128)
    # one layer moved from the paged pool to per-slot cross buffers; the
    # budget must count the cross buffers, not silently drop the layer
    assert cross.kv_gib > 0
    assert cross.kv_gib != plain.kv_gib


def test_sd_batch4_fits_one_chip_but_batch64_does_not():
    """The sd21-tpu unit declares SD_BATCH_MAX=4 (deploy/gen_units.py);
    the budget proves the batched denoise + decode fit one v5e chip, and
    the model correctly rejects an absurd batch."""
    from scalable_hw_agnostic_inference_tpu.core.budget import (
        diffusion_budget,
    )
    from scalable_hw_agnostic_inference_tpu.models.sd import SDVariant

    v = SDVariant.sd21_base()
    b4 = diffusion_budget(v, batch=4, height=512, width=512)
    assert b4.fits, b4.describe()
    b64_ = diffusion_budget(v, batch=64, height=512, width=512)
    assert not b64_.fits, b64_.describe()


def test_deepseek_8b_single_chip_needs_int8():
    """The deepseek-tpu unit (deploy/gen_units.py) serves an 8B distill
    from ONE v5e chip: bf16 params alone (~15 GiB) bust the 14.72 usable,
    int8 weight-only fits with headroom — the QUANTIZATION=int8 env is the
    fit-enabler, not an optimization flourish."""
    from scalable_hw_agnostic_inference_tpu.core.budget import (
        causal_lm_budget,
    )
    from scalable_hw_agnostic_inference_tpu.engine import EngineConfig

    mcfg = LlamaConfig.llama3_8b()

    def ecfg(q):
        return EngineConfig(
            model="deepseek-ai/DeepSeek-R1-Distill-Llama-8B",
            max_model_len=640, max_num_seqs=4, block_size=16,
            context_encoding_buckets=(128, 640), tensor_parallel_size=1,
            quantization=q)

    bf16 = causal_lm_budget(mcfg, ecfg(None))
    assert not bf16.fits, bf16.describe()
    int8 = causal_lm_budget(mcfg, ecfg("int8"))
    assert int8.fits, int8.describe()


@pytest.mark.slow  # tier-1 budget: see scripts/check_tier1_budget.py
def test_declared_production_geometries_fit():
    """The dryrun's shape-level legs, as a CI test: every committed
    geometry (units + cova ConfigMap) fits and shards legally."""
    import __graft_entry__ as g

    g.dryrun_production_geometries()


def test_mllama_tp8_prefill_lowers_at_full_shape():
    """The caption unit's sharded prefill partitions legally at FULL
    production shape (11B params abstract, TP=8, 1024-token bucket) — the
    SPMD-level leg beyond byte-math budgets."""
    import __graft_entry__ as g

    g.dryrun_lower_mllama_tp8(jax.devices()[:8])


def test_engine_enforces_budget_when_opted_in(monkeypatch):
    monkeypatch.setenv("SHAI_ENFORCE_HBM", "1")
    # a CPU device reports no HBM: enforcing a budget on one needs the size
    # declared
    monkeypatch.setenv("SHAI_HBM_GIB", "16")
    from scalable_hw_agnostic_inference_tpu.engine.engine import LLMEngine

    cfg = LlamaConfig.tiny()
    model = LlamaForCausalLM(cfg, dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    # over-budget: tiny model but an enormous dense pool on one chip
    ecfg = _ecfg(max_model_len=1 << 20, max_num_seqs=64, block_size=1 << 14,
                 context_encoding_buckets=(1 << 14,),
                 num_blocks=1 << 16)
    with pytest.raises(HbmBudgetError):
        LLMEngine(cfg, params, ecfg)
    # within budget boots fine under enforcement
    LLMEngine(cfg, params, _ecfg())


# ---------------------------------------------------------------------------
# detect_hbm_gib: runtime first, then the device-kind table, else an error
# ---------------------------------------------------------------------------

class _FakeDevice:
    """Mock device: controllable memory_stats + device_kind."""

    def __init__(self, stats=None, kind="", raises=False):
        self._stats = stats
        self._raises = raises
        self.device_kind = kind

    def memory_stats(self):
        if self._raises:
            raise RuntimeError("backend has no memory stats")
        return self._stats


def test_detect_hbm_gib_prefers_runtime_memory_stats():
    from scalable_hw_agnostic_inference_tpu.core.budget import detect_hbm_gib

    dev = _FakeDevice(stats={"bytes_limit": int(32 * GIB)}, kind="TPU v5e")
    # the runtime's own limit wins even when the kind table disagrees
    assert detect_hbm_gib(dev) == pytest.approx(32.0)


def test_detect_hbm_gib_falls_back_to_device_kind_table():
    from scalable_hw_agnostic_inference_tpu.core.budget import detect_hbm_gib

    # a runtime that reports no limit falls through to the kind table
    for silent in (_FakeDevice(stats=None, kind="TPU v5 lite"),
                   _FakeDevice(stats={}, kind="TPU v5 lite"),
                   _FakeDevice(stats={"bytes_limit": 0}, kind="TPU v5 lite")):
        assert detect_hbm_gib(silent) == pytest.approx(16.0)
    assert detect_hbm_gib(_FakeDevice(kind="TPU v4")) == pytest.approx(32.0)
    assert detect_hbm_gib(_FakeDevice(kind="TPU v5p")) == pytest.approx(95.0)
    # order matters: "v5 lite" must hit the 16 GiB row, not the bare "v5"
    assert detect_hbm_gib(_FakeDevice(kind="tpu v5litepod-8")) == \
        pytest.approx(16.0)


def test_detect_hbm_gib_refuses_to_guess(monkeypatch):
    from scalable_hw_agnostic_inference_tpu.core.budget import (
        HbmBudgetError,
        detect_hbm_gib,
    )

    # unknown kind, no limit from the runtime: an error, never a 16 GiB v5e
    with pytest.raises(HbmBudgetError, match="FutureAccelerator 9000"):
        detect_hbm_gib(_FakeDevice(kind="FutureAccelerator 9000"))
    # a failing memory_stats() is the runtime's error to report, not ours
    # to swallow
    with pytest.raises(RuntimeError, match="no memory stats"):
        detect_hbm_gib(_FakeDevice(raises=True, kind="TPU v5 lite"))
    # the operator's declaration still wins over everything
    monkeypatch.setenv("SHAI_HBM_GIB", "24")
    assert detect_hbm_gib(_FakeDevice(kind="FutureAccelerator 9000")) == \
        pytest.approx(24.0)
