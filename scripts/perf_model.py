"""CLI for the offline perf model (VERDICT r4 #1): AOT-compile the hot
executables against a deviceless v5e topology and write PERF_MODEL.{json,md}.

Runs with the default backend pinned to CPU: the topology compile path needs
no attached device at all, and host-side constants must not claim one.

    python scripts/perf_model.py                  # full ladder
    python scripts/perf_model.py --workloads sd_step_b1,sd_vae_b1
"""

import argparse
import os
import sys

# pin BEFORE jax import: the topology compile needs no backend
os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workloads", default="",
                    help="comma-separated subset (default: all)")
    ap.add_argument("--merge", action="store_true",
                    help="merge this run's component rows into the existing "
                         "out-json (recomposing projections) instead of "
                         "replacing it — incremental additions without "
                         "recompiling the whole ladder")
    ap.add_argument("--out-json", default="PERF_MODEL.json")
    ap.add_argument("--out-md", default="PERF_MODEL.md")
    args = ap.parse_args()

    import json

    from scalable_hw_agnostic_inference_tpu.core.aot import (
        enable_persistent_cache,
    )
    from scalable_hw_agnostic_inference_tpu.perf import model as pm

    enable_persistent_cache()   # re-runs only pay changed compiles
    names = [w for w in args.workloads.split(",") if w] or None
    res = pm.run(names)
    if args.merge and os.path.exists(args.out_json):
        with open(args.out_json) as f:
            prev = json.load(f)
        rows = {**prev.get("components", {}), **res["components"]}
        composed = pm.compose(rows)
        cal = pm.calibrate_eta(composed)
        # a workload that failed in a prior run but succeeded now must not
        # keep its stale error entry
        errors = {k: v for k, v in {**prev.get("errors", {}),
                                    **res["errors"]}.items()
                  if k not in rows}
        res.update(components=rows, composed=composed, calibration=cal,
                   projections=pm.project(composed, cal), errors=errors)
    pm.save(res, args.out_json, args.out_md)
    done = len(res["components"])
    print(f"wrote {args.out_json} + {args.out_md} "
          f"({done} executables, {len(res['errors'])} errors)")


if __name__ == "__main__":
    main()
