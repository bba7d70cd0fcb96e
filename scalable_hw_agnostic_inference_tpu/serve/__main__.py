"""Pod entrypoint: ``python -m scalable_hw_agnostic_inference_tpu.serve <model>``.

The reference's per-model ``run-*.sh`` → ``uvicorn run-X:app`` launch
(reference ``app/run-sd.sh:14``) collapses to one module: the model name comes
from argv or the ``MODEL`` env var, everything else from the env contract
(``utils.env.ServeConfig``).
"""

import logging
import sys
from typing import Tuple

from ..models.registry import get_model, list_models
from ..utils.env import ServeConfig, env_str
from .app import ModelService, serve_forever


def boot(name: str, cfg: ServeConfig) -> Tuple[ModelService, bool]:
    """THE start-up sequence, shared by the pod entrypoint and
    ``chip_smoke.py``: pin the platform, join the cluster, check that the
    backend is the tier ``DEVICE`` asked for, turn the compile cache on, and
    build the unit. Returns ``(service, multihost)``; the caller wraps the
    service in an app (``create_app`` / ``serve_forever``), which loads and
    warms it."""
    from ..core.aot import enable_persistent_cache
    from ..core.device import (
        apply_platform,
        maybe_distributed_init,
        resolve_device,
    )

    apply_platform(cfg.device)
    # multi-host slice units (SHAI_COORDINATOR set by the StatefulSet): join
    # the cluster before any backend touch so meshes span all hosts
    multihost = maybe_distributed_init()
    # no chip behind DEVICE=tpu is fatal here, not a CPU pod labelled tpu
    resolve_device(cfg.device)
    # a pod booting with the compile Job's cache directory skips the cold
    # XLA compile (reference's COMPILED_MODEL_ID pull,
    # ``sd21-inf2-deploy.yaml:60-61``, minus the hub round-trip)
    enable_persistent_cache()
    return get_model(name)(cfg), multihost


def main() -> None:
    logging.basicConfig(
        level=env_str("LOG_LEVEL", "INFO"),
        format="%(asctime)s %(name)s %(levelname)s %(message)s",
    )
    name = sys.argv[1] if len(sys.argv) > 1 else env_str("MODEL", "")
    if not name:
        print(f"usage: python -m scalable_hw_agnostic_inference_tpu.serve <model>\n"
              f"available: {', '.join(list_models())}", file=sys.stderr)
        raise SystemExit(2)
    cfg = ServeConfig.from_env()
    service, multihost = boot(name, cfg)
    if multihost:
        # leader owns HTTP and broadcasts every request; followers mirror it
        # so their devices enter the same collectives (serve.multihost)
        from .multihost import serve_multihost

        serve_multihost(cfg, service)
    else:
        serve_forever(cfg, service)


if __name__ == "__main__":
    main()
