"""A cell's reference check at published widths, right and under every
variant its reference module lists, in one boot a weight seed:
``chiprun -- python scripts/reference_variants.py --workload <cell> --seeds
A,B`` (``--dry-run``: the tiny stand-in on the CPU).

Not part of a measured run: the builder of a ``model_config`` PR uses it on
the chip to set the bounds in the tolerance file from two readings (the right
path's largest over its seeds, and each broken variant's) and writes both
into the file's ``reason``. For each seed it boots the cell's system under
test as ``benchmark/run.py`` does, then runs ``reference_check`` (the engine
generates on the timed path, the reference scores the same tokens) once for
the right path and once a variant (``REFUSED_VARIANTS``, ``REFUSED_BY_MEAN``,
``NOT_REFUSED_RELIABLY``, ``ACCEPTED_VARIANTS``, whichever the module
has), and prints one ``variant`` line each. Writes
``chiprun_out/reference_variants.<config>.<seed>.json``; several seeds run
one process each.
"""

import argparse
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import run as bench_run  # noqa: E402
from benchmark.server import SystemUnderTest  # noqa: E402
from benchmark.spec import Spec  # noqa: E402

LISTS = ("REFUSED_VARIANTS", "REFUSED_BY_MEAN", "NOT_REFUSED_RELIABLY",
         "ACCEPTED_VARIANTS")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--variants", default="",
                    help="comma-separated; default: every list's")
    ap.add_argument("--dry-run", action="store_true")
    args = ap.parse_args(argv)
    bench_run.keep_cache_in_checkout()
    spec = Spec(ROOT)
    w, cfg, _ = spec.cell(args.workload)
    ref = spec.reference(cfg["reference"]["module"])
    names = [v for lst in LISTS for v in getattr(ref, lst, ())]
    if args.variants:
        names = [v for v in args.variants.split(",") if v]
    seeds = [int(s) for s in args.seeds.split(",")]
    if len(seeds) > 1:
        # one boot a PROCESS: a second boot here would find the first one's
        # weights and pool still on the chip (PR 34: RESOURCE_EXHAUSTED)
        rest = [a for a in (argv or sys.argv[1:])]
        at = rest.index("--seeds")
        return max(subprocess.call(
            [sys.executable, os.path.abspath(__file__)] + rest[:at]
            + ["--seeds", str(s)] + rest[at + 2:]) for s in seeds)
    out = []
    for seed in seeds:
        sut = SystemUnderTest(cfg, spec.harness, seed, os.path.join(
            ROOT, spec.harness["out_dir"], "variants-" + args.workload),
            args.dry_run)
        sut.devices()
        try:
            sut.start()
            for variant in [""] + names:
                got = bench_run.reference_check(spec, sut, cfg, seed,
                                                args.dry_run, variant)
                rec = {"seed": seed, "variant": variant or "(right path)",
                       **got}
                bench_run.say("variant", rec)
                out.append(rec)
        finally:
            sut.stop()
    if not args.dry_run:
        os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
        with open(os.path.join(ROOT, "chiprun_out",
                               f"reference_variants.{w['config']}.{seeds[0]}"
                               ".json"),
                  "w") as fh:
            json.dump(out, fh, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
