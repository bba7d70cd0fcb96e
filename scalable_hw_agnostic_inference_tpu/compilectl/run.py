"""compilectl implementation: warm the compile cache, export, self-test."""

from __future__ import annotations

import json
import logging
import os
import time
from typing import Any, Dict, Optional

log = logging.getLogger(__name__)


def compile_model(name: str, cfg=None, artifact_root: Optional[str] = None,
                  self_test: bool = True) -> Dict[str, Any]:
    """AOT-compile serving unit ``name`` into the artifact root.

    Runs the unit's real ``load() + warmup()`` with the persistent XLA cache
    on (``core.aot.enable_persistent_cache`` owns where), then
    (compile-yolo.py's pattern, reference
    ``app/compile-yolo.py:22-27``) self-tests with one real inference.
    Returns a report with cache contents and timings.
    """
    from ..core.aot import enable_persistent_cache
    from ..models.registry import get_model
    from ..utils.env import ServeConfig

    cfg = cfg or ServeConfig.from_env()
    root = artifact_root or cfg.artifact_root
    cache_dir = enable_persistent_cache()

    service = get_model(name)(cfg)
    t0 = time.perf_counter()
    service.load()
    t_load = time.perf_counter() - t0
    t0 = time.perf_counter()
    service.warmup()
    t_warm = time.perf_counter() - t0

    test_out = None
    if self_test:
        out = service.infer(service.example_payload())
        test_out = sorted(out) if isinstance(out, dict) else str(type(out))

    # portable StableHLO exports (AotCache) alongside the XLA cache — the
    # hub-distributable artifact tier; serve loads them at boot
    n_exported = service.export_artifacts(root)

    entries = sorted(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else []
    report = {
        "model": name,
        "artifact_root": root,
        "cache_dir": cache_dir,
        "cache_entries": len(entries),
        "aot_exported": n_exported,
        "load_s": round(t_load, 2),
        "warmup_s": round(t_warm, 2),
        "self_test_keys": test_out,
    }
    # merge-on-save right before the atomic replace: concurrent compile Jobs
    # sharing one artifact root then lose no entries (same policy as AotCache)
    manifest_path = os.path.join(root, "compile-manifest.json")
    manifest: Dict[str, Any] = {}
    if os.path.exists(manifest_path):
        try:
            with open(manifest_path) as f:
                manifest = json.load(f)
        except Exception:
            pass
    manifest[name] = {**report, "created": time.time()}
    tmp = f"{manifest_path}.{os.getpid()}.tmp"
    with open(tmp, "w") as f:
        json.dump(manifest, f, indent=1, sort_keys=True)
    os.replace(tmp, manifest_path)
    return report
