"""Deviceless TPU AOT compilation helpers.

``jax.experimental.topologies.get_topology_desc`` builds a PJRT topology for
a named TPU geometry (e.g. ``v5e:2x2``) without any attached device; a
function jitted with shardings over that topology's devices can be
``lower().compile()``-d into a real XLA:TPU executable whose
``cost_analysis()`` reports FLOPs and bytes moved. This is how the perf
model (:mod:`.model`) and the kernel-lowering test produce on-target
executables with no chip attached. The process keeps its *default* backend
on CPU (``JAX_PLATFORMS=cpu``): host-side constants (scheduler tables,
example arrays) land there, and the topology is purely a compile target.

The smallest v5e topology the plugin accepts is ``2x2`` (one host, 4 chips);
single-chip workloads compile against a 1-device mesh carved from it, which
yields the same executable a real v5e-1 would build (SPMD partitioning is
by mesh, not by topology size).
"""

from __future__ import annotations

import os
import time
from contextlib import contextmanager
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import numpy as np


@contextmanager
def env_override(env: Dict[str, str]):
    """Scope env vars that trace-time dispatch reads (attention impl etc.)."""
    old = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    try:
        yield
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def platform_override(name: str = "tpu"):
    """Scope ``SHAI_PLATFORM_OVERRIDE`` so traces dispatch for the compile
    TARGET (ops.attention.effective_platform): the serving executables pick
    their TPU kernels even though this process's backend is CPU."""
    return env_override({"SHAI_PLATFORM_OVERRIDE": name})

#: topology names by minimum device count (v5e host is 2x2; one host max 8)
_TOPO_BY_MIN = ((8, "v5e:2x4"), (4, "v5e:2x2"), (1, "v5e:2x2"))
_TOPO_CACHE: Dict[Tuple[str, str], Any] = {}


def _get_topology(platform: str, name: str):
    """One libtpu touch per (platform, topology): the description is cached
    for the life of the process."""
    key = (platform, name)
    if key not in _TOPO_CACHE:
        from jax.experimental import topologies

        # compile-only client: never drives a chip, so it may share libtpu
        # with a process that does
        os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "true")
        _TOPO_CACHE[key] = topologies.get_topology_desc(
            platform=platform, topology_name=name)
    return _TOPO_CACHE[key]


def topology_devices(n_devices: int = 1, platform: str = "tpu"):
    """``n_devices`` compile-target devices from the smallest topology that
    holds them. Raises whatever the plugin raises if deviceless topology
    support is unavailable (callers surface that as the probe stage)."""
    for min_n, name in sorted(_TOPO_BY_MIN):
        if n_devices <= min_n:
            return list(_get_topology(platform, name).devices)[:n_devices]
    raise ValueError(f"no single-host v5e topology holds {n_devices} devices")


def device_mesh(n_devices: int = 1, axes: Tuple[str, ...] = ("tp",),
                shape: Optional[Tuple[int, ...]] = None):
    """A :class:`jax.sharding.Mesh` over topology (not attached) devices."""
    devs = topology_devices(n_devices)
    if shape is None:
        if len(axes) != 1:
            raise ValueError("pass an explicit shape for multi-axis meshes")
        shape = (n_devices,)
    return jax.sharding.Mesh(np.array(devs).reshape(shape), axes)


def abstract_params(build: Callable[[], Any]):
    """Shape-evaluate a zero-arg param builder (e.g. a flax ``init`` closure)
    into a pytree of :class:`jax.ShapeDtypeStruct` — no FLOPs, no devices."""
    return jax.eval_shape(build)


def bf16_leaves(avals):
    """f32 leaves -> bf16 (the serving cast) on an abstract tree."""
    import jax.numpy as jnp

    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, jnp.bfloat16)
        if a.dtype == jnp.float32 else jax.ShapeDtypeStruct(a.shape, a.dtype),
        avals)


def with_sharding(avals, sharding):
    """Attach one sharding to every leaf (replicated single-device case)."""
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        avals)


def compile_workload(fn: Callable, args: Tuple, *,
                     donate_argnums: Tuple[int, ...] = ()) -> Dict[str, Any]:
    """AOT-compile ``fn(*args)`` (args = aval trees with shardings attached)
    and return the XLA accounting: flops, bytes accessed, peak memory,
    compile seconds. ``fn`` may already be jitted; shardings ride on the
    avals, so no ``in_shardings`` are needed here."""
    jfn = fn if hasattr(fn, "lower") else jax.jit(
        fn, donate_argnums=donate_argnums)
    t0 = time.perf_counter()
    with platform_override("tpu"):
        lowered = jfn.lower(*args)
    t_lower = time.perf_counter() - t0
    t0 = time.perf_counter()
    compiled = lowered.compile()
    t_compile = time.perf_counter() - t0
    ca = dict(compiled.cost_analysis() or {})
    mem = {}
    try:
        m = compiled.memory_analysis()
        for k in ("temp_size_in_bytes", "argument_size_in_bytes",
                  "output_size_in_bytes", "alias_size_in_bytes",
                  "generated_code_size_in_bytes"):
            v = getattr(m, k, None)
            if v is not None:
                mem[k] = int(v)
    except Exception:       # pragma: no cover - analysis is best-effort
        pass
    # the deviceless TPU backend emits a meaningless negative sentinel for
    # optimal_seconds — keep only physically-possible values
    opt = float(ca.get("optimal_seconds", 0.0))
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
        "optimal_seconds": opt if opt > 0 else None,
        "utilization_operand0": ca.get("utilization operand 0 {}"),
        "memory": mem,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "compiled": compiled,
    }
