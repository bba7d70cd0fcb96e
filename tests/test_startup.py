"""Process start-up: the device that was asked for, one compile-cache owner,
a bench that needs the chip, and geometry weights born in their final form."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from scalable_hw_agnostic_inference_tpu.core import aot
from scalable_hw_agnostic_inference_tpu.models import llama
from scalable_hw_agnostic_inference_tpu.utils.env import ServeConfig

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))


@pytest.fixture
def config_updates(monkeypatch):
    """Record ``jax.config.update`` calls instead of applying them (the
    cache directory of the test process itself must not move)."""
    calls = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.__setitem__(name, value))
    return calls


def test_device_tpu_on_a_cpu_backend_fails_the_boot(
        monkeypatch, config_updates):
    """DEVICE=tpu means a TPU: the shared boot function raises (the pod
    entrypoint then exits non-zero) instead of serving from the CPU — and
    before it has built anything."""
    from scalable_hw_agnostic_inference_tpu.serve.__main__ import boot

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    with pytest.raises(RuntimeError, match="DEVICE=tpu.*'cpu'"):
        boot("vllm", ServeConfig(device="tpu", model_id="tiny"))
    assert "jax_compilation_cache_dir" not in config_updates
    service, multihost = boot("vllm", ServeConfig(device="cpu",
                                                  model_id="tiny"))
    assert service.task == "text-generation" and not multihost
    # the one boot function is also where the compile cache is turned on
    assert "jax_compilation_cache_dir" in config_updates


def test_cache_dir_from_the_environment_is_left_to_jax(
        monkeypatch, config_updates, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cc"))
    assert aot.enable_persistent_cache() == str(tmp_path / "cc")
    assert "jax_compilation_cache_dir" not in config_updates
    assert "jax_persistent_cache_min_compile_time_secs" in config_updates
    assert not (tmp_path / "cc").exists()   # JAX owns it, not this code


def test_default_cache_dir_is_the_checkout_from_any_cwd(
        monkeypatch, config_updates, tmp_path):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(ROOT, ".jax_cache")
    for cwd in (tmp_path, ROOT):
        monkeypatch.chdir(cwd)
        config_updates.clear()
        assert aot.enable_persistent_cache() == want
        assert config_updates["jax_compilation_cache_dir"] == want


def test_bench_needs_the_chip_unless_told_cpu():
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench.py"), "kvtier"],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert "metric" not in r.stdout
    assert "no accelerator" in r.stderr


# ---------------------------------------------------------------------------
# geometry tier: seeded, int8 at birth, sharded at birth
# ---------------------------------------------------------------------------

def _leaves(tree):
    return {jax.tree_util.keystr(p): x
            for p, x in jax.tree_util.tree_flatten_with_path(tree)[0]}


def test_geometry_weights_are_seeded_and_never_zero():
    cfg = llama.LlamaConfig.tiny()
    a = _leaves(llama.geometry_params(cfg, seed=3))
    b = _leaves(llama.geometry_params(cfg, seed=3))
    c = _leaves(llama.geometry_params(cfg, seed=4))
    for name, x in a.items():
        assert np.array_equal(np.asarray(x), np.asarray(b[name])), name
        assert np.any(np.asarray(x) != 0), name
    kernels = [n for n in a if n.endswith("['kernel']")]
    assert kernels and all(
        not np.array_equal(np.asarray(a[n]), np.asarray(c[n]))
        for n in kernels)


def test_geometry_int8_is_born_int8_with_no_float_copy():
    from scalable_hw_agnostic_inference_tpu.ops.quant import (
        quantize_params_tree,
    )

    cfg = llama.LlamaConfig.tiny()
    tree = llama.geometry_params(cfg, quant=True, seed=1)
    leaves = _leaves(tree)
    q = {n: x for n, x in leaves.items() if n.endswith("['kernel_q']")}
    assert len(q) == cfg.n_layers * 7    # q k v o gate up down
    assert all(x.dtype == jnp.int8 and np.any(np.asarray(x) != 0)
               for x in q.values())
    assert not any(n.endswith("['kernel']") for n in leaves)
    # the boot's quantize pass finds nothing left to convert
    assert _leaves(quantize_params_tree(tree)).keys() == leaves.keys()
    # and inside the initialiser no float array of the leaf's shape exists
    shape = (cfg.dim, cfg.mlp_dim)
    jaxpr = jax.make_jaxpr(lambda k: llama._geometry_leaf(
        k, shape=shape, dtype=jnp.dtype(jnp.int8), sharding=None))(
            jax.random.PRNGKey(0))

    def avals(jp):
        for eqn in jp.eqns:
            yield from (v.aval for v in eqn.outvars)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from avals(sub)

    full = [a for a in avals(jaxpr.jaxpr) if a.shape == shape]
    assert full and not any(jnp.issubdtype(a.dtype, jnp.floating)
                            for a in full)


def test_geometry_weights_are_born_sharded():
    from scalable_hw_agnostic_inference_tpu.core.mesh import build_mesh

    cfg = llama.LlamaConfig.tiny()
    mesh = build_mesh("tp=2", devices=jax.devices()[:2])
    tree = llama.geometry_params(cfg, quant=True, seed=1, mesh=mesh)
    specs = llama.tp_rules().tree_specs(tree)
    split = 0
    for x, spec in zip(jax.tree.leaves(tree), jax.tree.leaves(
            specs, is_leaf=lambda s: isinstance(s, jax.sharding.PartitionSpec))):
        want = jax.sharding.NamedSharding(mesh, spec)
        assert x.sharding.is_equivalent_to(want, x.ndim)
        if not want.is_fully_replicated:
            split += 1
            assert all(s.data.size * 2 == x.size
                       for s in x.addressable_shards)
    assert split >= cfg.n_layers * 7
    # same seed, same values, whatever the placement
    one = llama.geometry_params(cfg, quant=True, seed=1)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(one)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
