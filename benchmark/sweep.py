#!/usr/bin/env python3
"""Find a cell's fixed rate, once: one boot, rates in turn, seeds in turn.

    python3 benchmark/sweep.py --workload <cell> --seconds S \\
        --rates 3,4,5 --seeds 11,12 [--ttft-ms L --gap-ms G] [--dry-run]

Not part of a measured run: the builder of a benchmark PR uses it on the
chip to fill ``rate_per_s`` in a traffic file, and writes the table into
PERF.md. For each (rate, seed) it prints one ``sweep`` line: attempted,
failed, the tails, the highest ``waiting`` the engine's gauge showed, the
share of requests inside both limits, and whether the backlog grew (time to
first token of the window's last quarter against its first).
"""

from __future__ import annotations

import argparse
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run as bench_run, stats  # noqa: E402
from benchmark.server import SystemUnderTest  # noqa: E402
from benchmark.spec import Spec  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated; clients for a closed loop")
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--ttft-ms", type=float, default=0.0)
    ap.add_argument("--gap-ms", type=float, default=0.0)
    ap.add_argument("--dry-run", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    args.trace = 0
    bench_run.keep_cache_in_checkout()
    spec = Spec(ROOT)
    w, cfg, mix = spec.cell(args.workload)
    out_dir = os.path.join(ROOT, args.out or os.path.join(
        spec.harness["out_dir"], "sweep-" + args.workload))
    seeds = [int(s) for s in args.seeds.split(",")]
    sut = SystemUnderTest(cfg, spec.harness, seeds[0], out_dir, args.dry_run)
    sut.devices()
    try:
        sut.start()
        extra = bench_run.set_up(spec, sut, cfg, seeds[0], args.dry_run)
        key = "clients" if mix["loop"] == "closed" else "rate_per_s"
        for rate in [float(r) for r in args.rates.split(",")]:
            for seed in seeds:
                info = {}
                over = {key: int(rate) if key == "clients" else rate}
                res = bench_run.one_run(spec, args, seed, sut,
                                        time.monotonic(), extra, over, info)
                recs, t0, t1 = info["records"], info["t0"], info["t1"]
                att = stats.attempted(recs)
                ok = [r for r in att if r.failure is None and r.token_times]
                ttft = sorted((r.due, (r.token_times[0] - r.due) * 1e3)
                              for r in ok)
                q = max(1, len(ttft) // 4)
                first = sum(v for _, v in ttft[:q]) / q if ttft else 0.0
                last = sum(v for _, v in ttft[-q:]) / q if ttft else 0.0
                met, max_gaps = 0, []
                for r in att:
                    t = r.token_times
                    gaps = [(b - a) * 1e3 for a, b in zip(t, t[1:])]
                    if gaps:
                        max_gaps.append(max(gaps))
                    if (r.failure is None and t
                            and (not args.ttft_ms
                                 or (t[0] - r.due) * 1e3 <= args.ttft_ms)
                            and (not args.gap_ms or not gaps
                                 or max(gaps) <= args.gap_ms)):
                        met += 1
                line = {"rate": rate, "seed": seed,
                        "attempted": res["attempted"],
                        "failed": res["failed"],
                        "waiting_max": max((s[1] for s in info["samples"]
                                            if t0 <= s[0] < t1), default=0),
                        "met_both_share": met / max(1, len(att)),
                        "ttft_first_quarter_ms": first,
                        "ttft_last_quarter_ms": last,
                        "max_gap_p90_ms": (stats.percentile(max_gaps, 90)
                                           if max_gaps else None),
                        "ended_after_window": sum(r.done > t1 for r in att),
                        "out_tok_per_s": stats.client_metric(
                            "out_tok_per_s", recs, t0, t1, 1.0)}
                for name in ("ttft_p50_ms", "ttft_p90_ms", "gap_p50_ms",
                             "gap_p95_ms"):
                    line[name] = stats.client_metric(name, recs, t0, t1,
                                                     120.0)
                bench_run.say("sweep", line)
    finally:
        sut.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
