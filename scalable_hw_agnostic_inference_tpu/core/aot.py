"""AOT compilation cache and artifact store.

The reference's artifact story: ``torch_neuronx.trace`` -> NEFF files ->
pushed to the HF hub -> pulled at pod boot by ``COMPILED_MODEL_ID`` (reference
``app/compile-sd2.py:18-20``, ``sd21-inf2-deploy.yaml:60-61``). The TPU-native
equivalent has two tiers:

1. **XLA persistent compilation cache** (:func:`enable_persistent_cache`) —
   keyed by HLO fingerprint, at ``JAX_COMPILATION_CACHE_DIR`` (deployments
   point it at a PV, GCS bucket, or baked image layer), so a restarted pod
   skips the multi-minute compile the reference calls out as its 5-15 min
   cold start (``README.md:82``).
2. **Exported StableHLO artifacts** (:class:`AotCache`) — portable serialized
   functions keyed by (name, shapes, dtypes, mesh, jax version), the
   distributable analog of per-rank NEFFs on the hub. ``compilectl`` writes
   them at build time; servers load them at boot.
"""

from __future__ import annotations

import hashlib
import json
import logging
import os
import time
from typing import Any, Callable, Dict, Optional, Sequence

log = logging.getLogger(__name__)

MANIFEST = "manifest.json"

# process-wide AOT event counters (obs): how many artifact traces/exports
# and deserialize-loads this process performed, and the wall time traced.
# A serving pod whose export count moves AFTER readiness is compiling
# post-warm — the same bucket-miss signal the engine's telemetry counts,
# visible here for the artifact tier. Exposed through ``/stats`` (serve.app).
_COMPILE_STATS = {"exports": 0, "export_s": 0.0, "loads": 0,
                  "cache_hits": 0}


def compile_stats() -> Dict[str, float]:
    """Snapshot of this process's AOT compile/export/load counters."""
    return dict(_COMPILE_STATS)


#: default cache location: ``<checkout>/.jax_cache``, resolved from this
#: file so it is the same directory from every working directory. The path
#: is part of JAX's cache key, so a directory that moves never hits.
_CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_persistent_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    THE one owner of the cache location, called by every entry point before
    its first compile. Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX has
    already read it and no directory is set here — only the threshold;
    otherwise the cache lives at ``<checkout>/.jax_cache``.
    """
    import jax

    from ..obs.util import env_str

    cache_dir = env_str("JAX_COMPILATION_CACHE_DIR", "")
    if not cache_dir:
        cache_dir = _CHECKOUT_CACHE
        os.makedirs(cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    # every executable, however quick its compile: a threshold makes the
    # borderline ones flip in and out of the cache from run to run, and a
    # warm boot then still pays for each of them
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache_dir


def host_init(init_fn, *arg_thunks):
    """Run a flax ``init`` eagerly on the CPU backend; return host params.

    The jitted init graph of a full model is the single largest compile a
    bench/perf session would send to the device, for values that do not
    affect throughput — so build them on CPU and transfer once with
    :func:`to_default_device`. ``arg_thunks`` are zero-arg callables so the
    example inputs are also created on the CPU backend.
    """
    import jax

    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        return init_fn(*[t() for t in arg_thunks])


def to_default_device(tree):
    """Transfer a host pytree to the default (accelerator) device in one
    batched ``device_put``."""
    import jax

    return jax.device_put(tree, jax.devices()[0])


def _spec_of(x) -> Dict[str, Any]:
    import jax.numpy as jnp  # noqa: F401

    shape = tuple(getattr(x, "shape", ()))
    dtype = str(getattr(x, "dtype", type(x).__name__))
    return {"shape": list(shape), "dtype": dtype}


def aot_key(name: str, args: Sequence, mesh=None, extra: str = "") -> str:
    """Stable content key for one compiled function variant."""
    import jax

    payload = {
        "name": name,
        "args": [_spec_of(a) for a in args],
        "mesh": None,
        "jax": jax.__version__,
        "extra": extra,
    }
    if mesh is not None:
        payload["mesh"] = {
            "axes": list(mesh.axis_names),
            "shape": list(mesh.devices.shape),
        }
    blob = json.dumps(payload, sort_keys=True).encode()
    return f"{name}-{hashlib.sha256(blob).hexdigest()[:16]}"


class AotCache:
    """Directory-backed store of exported (StableHLO) jitted functions.

    Layout::

        <root>/<key>.shlo       serialized jax.export artifact
        <root>/manifest.json    key -> {name, specs, created, mesh}
    """

    def __init__(self, root: str):
        self.root = root
        os.makedirs(root, exist_ok=True)
        self._manifest_path = os.path.join(root, MANIFEST)
        self._manifest: Dict[str, Dict] = {}
        # freshly-exported callables, so get_or_export need not re-deserialize
        # and re-compile what was just traced (the cold-start path)
        self._live: Dict[str, Callable] = {}
        if os.path.exists(self._manifest_path):
            try:
                with open(self._manifest_path) as f:
                    self._manifest = json.load(f)
            except Exception:
                log.warning("corrupt AOT manifest at %s; starting fresh", self._manifest_path)

    def _save_manifest(self) -> None:
        # merge-on-save: artifact roots are shared (PV/GCS) across pods, so
        # re-read the disk manifest and union entries before the atomic
        # replace — concurrent writers then lose no keys (last metadata wins
        # per key, which is fine: entries are content-addressed)
        if os.path.exists(self._manifest_path):
            try:
                with open(self._manifest_path) as f:
                    on_disk = json.load(f)
                on_disk.update(self._manifest)
                self._manifest = on_disk
            except Exception:
                pass
        tmp = f"{self._manifest_path}.{os.getpid()}.tmp"
        with open(tmp, "w") as f:
            json.dump(self._manifest, f, indent=1, sort_keys=True)
        os.replace(tmp, self._manifest_path)

    def keys(self) -> Dict[str, Dict]:
        return dict(self._manifest)

    def export(
        self,
        name: str,
        fn: Callable,
        args: Sequence,
        mesh=None,
        extra: str = "",
    ) -> str:
        """Trace+export ``fn`` at ``args``' shapes and persist it; returns key."""
        import jax
        from jax import export as jexport

        key = aot_key(name, args, mesh=mesh, extra=extra)
        path = os.path.join(self.root, key + ".shlo")
        if key in self._manifest and os.path.exists(path):
            _COMPILE_STATS["cache_hits"] += 1
            return key
        jitted = fn if hasattr(fn, "lower") else jax.jit(fn)
        t0 = time.perf_counter()
        exported = jexport.export(jitted)(*args)
        _COMPILE_STATS["exports"] += 1
        _COMPILE_STATS["export_s"] += time.perf_counter() - t0
        self._live[key] = exported.call
        data = exported.serialize()
        with open(path, "wb") as f:
            f.write(data)
        self._manifest[key] = {
            "name": name,
            "args": [_spec_of(a) for a in args],
            "created": time.time(),
            "bytes": len(data),
            "extra": extra,
        }
        self._save_manifest()
        log.info("AOT exported %s (%d bytes)", key, len(data))
        return key

    def load(self, key: str) -> Callable:
        """Load an exported function; calling it compiles via the persistent
        cache (fast when warm) and runs on the current backend."""
        from jax import export as jexport

        path = os.path.join(self.root, key + ".shlo")
        if not os.path.exists(path):
            raise KeyError(f"no AOT artifact {key} under {self.root}")
        with open(path, "rb") as f:
            exported = jexport.deserialize(f.read())
        _COMPILE_STATS["loads"] += 1
        return exported.call

    def get_or_export(self, name: str, fn: Callable, args: Sequence, mesh=None, extra: str = ""):
        key = self.export(name, fn, args, mesh=mesh, extra=extra)
        live = self._live.get(key)
        return live if live is not None else self.load(key)
