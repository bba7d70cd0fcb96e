"""CLI: ``python -m scalable_hw_agnostic_inference_tpu.compilectl <model>``.

Same env contract as serving (``utils.env.ServeConfig``); a compile Job is a
serving Deployment with this command (reference ``compile-vllm-job.yaml``).
"""

import argparse
import json
import logging

from ..models.registry import list_models
from ..utils.env import ServeConfig
from .run import compile_model


def main() -> None:
    logging.basicConfig(level="INFO")
    ap = argparse.ArgumentParser(prog="compilectl")
    ap.add_argument("model", help=f"one of: {', '.join(list_models())}")
    ap.add_argument("--artifact-root", default=None,
                    help="override ARTIFACT_ROOT")
    ap.add_argument("--no-self-test", action="store_true")
    args = ap.parse_args()

    cfg = ServeConfig.from_env()
    from ..core.device import apply_platform, resolve_device

    apply_platform(cfg.device)
    resolve_device(cfg.device)
    report = compile_model(args.model, cfg, artifact_root=args.artifact_root,
                           self_test=not args.no_self_test)
    print(json.dumps(report, indent=1))


if __name__ == "__main__":
    main()
