#!/usr/bin/env python3
"""Run one cell of the benchmark and print its result.

    python3 benchmark/run.py --workload <config>.<mix> --seed N \\
        --seconds S --trace 0|1 [--dry-run] [--out DIR]

Boots the system under test in this process (one process holds the chip),
checks the served path against the plain float32 reference, sends the mix's
warm-up traffic, measures for ``--seconds`` and prints, as the last line of
standard output, one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics``, ``device`` (and ``breakdown`` with ``--trace 1``). With
``--trace 0`` the metrics are the cell's end-to-end metrics; with
``--trace 1`` a profiler trace of a few seconds of the window is taken and
the metrics are the cell's per-layer metrics.

Needs a TPU with the chips the cell asks for: on any other backend it exits
non-zero and prints no result. ``--dry-run`` is for the tests: the tiny model
on the CPU, lengths folded to what it serves, ``"platform": "cpu"`` in the
result, and never a number worth reading.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import logging
import os
import shutil
import sys
import threading
import time

T_PROCESS = time.monotonic()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import client, stats, traffic  # noqa: E402
from benchmark.spec import Spec, SpecError  # noqa: E402


def keep_cache_in_checkout() -> None:
    """The compile cache at ``<checkout>/.jax_cache`` and of no fixed size,
    whatever the environment names: a fixed path inside the checkout, which
    the first run of a cell fills and every later run there reads. A size
    limit makes JAX evict the least recently read programs, and a boot that
    reads its programs in the same order each time then finds none of them
    once they outgrow the limit (the four-chip cell's did, at the chip
    tool's 192 MiB: every run compiled; PERF.md, PR 23). The program takes
    both from the environment, which JAX reads when it is first imported; so
    this comes before that."""
    if "jax" not in sys.modules:
        os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
            ROOT, ".jax_cache")
        os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"


def say(tag: str, obj) -> None:
    """An earlier line of the report: a tag and one JSON object."""
    print(f"{tag} {json.dumps(obj)}", flush=True)


class Poller(threading.Thread):
    """Samples the engine's gauges and step ring while traffic runs. Reads
    the telemetry object directly: no request of its own goes through the
    server under test."""

    def __init__(self, sut, interval_s: float):
        super().__init__(daemon=True, name="bench-poller")
        self.sut, self.interval_s = sut, interval_s
        self.samples = []          # (t, waiting, running, kv_utilization)
        self.steps = {}            # step number -> record
        self.last_waiting = None
        self._halt = threading.Event()

    def run(self) -> None:
        tele = self.sut.telemetry
        while not self._halt.wait(self.interval_s):
            snap = tele.snapshot()
            self.last_waiting = snap.get("waiting")
            self.samples.append((time.monotonic(), snap.get("waiting", 0),
                                 snap.get("running", 0),
                                 snap.get("kv_utilization", 0.0)))
            for rec in tele.recent_steps(256):
                self.steps[rec["step"]] = rec

    def stop(self) -> None:
        self._halt.set()
        self.join(5.0)


class Tracer(threading.Thread):
    """Takes a profiler trace of ``seconds`` of the window from its own
    thread, and marks the traced interval with a ``bench_window`` annotation
    on the profiler's clock."""

    def __init__(self, sut, out_dir: str, start_at: float, seconds: float):
        super().__init__(daemon=True, name="bench-tracer")
        self.sut, self.start_at, self.seconds = sut, start_at, seconds
        self.dir = os.path.join(out_dir, "trace")
        self.before = self.after = None
        self.error = None

    def run(self) -> None:
        import jax

        try:
            time.sleep(max(0.0, self.start_at - time.monotonic()))
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            try:
                self.before = self.sut.counters()
                with jax.profiler.TraceAnnotation("bench_window"):
                    time.sleep(self.seconds)
                self.after = self.sut.counters()
            finally:
                jax.profiler.stop_trace()
        except Exception as e:   # the run reports it and fails
            self.error = f"{type(e).__name__}: {e}"

    def reduced(self):
        import glob

        from benchmark import trace

        files = sorted(glob.glob(os.path.join(
            self.dir, "plugins", "profile", "*", "*.xplane.pb")))
        if not files:
            raise RuntimeError(f"no trace was written under {self.dir}")
        red = trace.Reduced(trace.load_xplane(files[-1]))
        # tens of megabytes a chip and a run: reduced, it is not kept
        shutil.rmtree(self.dir, ignore_errors=True)
        return red


def one_run(spec: Spec, args, seed: int, sut, t_setup0: float,
            setup_extra: dict, mix_override: dict = None, info: dict = None
            ) -> dict:
    """Warm-up traffic, the window, and the result line's object.
    ``mix_override`` and ``info`` serve ``sweep.py``: a changed rate, and
    the records behind the numbers."""
    w, cfg, mix = spec.cell(args.workload)
    mix = {**mix, **(mix_override or {})}
    h = spec.harness
    dry = h["dry_run"] if args.dry_run else None
    if dry:
        mix = {**mix, "warmup_s": min(mix["warmup_s"], dry["warmup_s"])}
    plans = traffic.schedule(mix, seed, args.seconds,
                             dry["clamp"] if dry else None)
    out_dir = sut.out_dir
    poller = Poller(sut, float(h["poll_interval_s"]))
    t0_box = {}
    flog = client.FailureLog(
        os.path.join(out_dir, "failures.jsonl"), args.workload, seed,
        lambda: poller.last_waiting, lambda: t0_box["t0"])
    gen = client.LoadGenerator(sut.host, sut.port,
                               float(h["client_timeout_s"]), flog)
    t_start = time.monotonic() + 0.05
    t0 = t0_box["t0"] = t_start + float(mix["warmup_s"])
    t1 = t0 + float(args.seconds)
    tracer = None
    if args.trace:
        tracer = Tracer(
            sut, out_dir,
            t0 + float((dry or h)["trace_start_s"]),
            min(float((dry or h)["trace_seconds"]), args.seconds / 2))
        tracer.start()
    poller.start()
    marks = {}

    async def drive():
        async def mark():
            await asyncio.sleep(max(0.0, t0 - time.monotonic()))
            marks["before"] = sut.counters()
            await asyncio.sleep(max(0.0, t1 - time.monotonic()))
            marks["after"] = sut.counters()

        marker = asyncio.get_running_loop().create_task(mark())
        if mix["loop"] == "open":
            await asyncio.sleep(max(0.0, t_start - time.monotonic()))
            await gen.open_loop(plans, t0, t1)
        else:
            await gen.closed_loop(plans, int(mix["clients"]), t_start, t0, t1)
        await marker

    asyncio.run(drive())
    poller.stop()
    if tracer is not None:
        tracer.join(120.0)
    setup_s = t0 - t_setup0
    records = gen.records
    if info is not None:
        info.update(records=records, t0=t0, t1=t1, samples=poller.samples)
    summ = stats.summary(records, t0, t1)
    timeout_s = float(h["client_timeout_s"])
    compile_in_window = sut.compile_s_between(t0, t1)
    complete = [r for r in stats.attempted(records) if r.failure is None]
    accounted = all(stats.accounts_for_its_tokens(r) for r in complete)
    correct = bool(setup_extra["reference"]["passed"] and accounted
                   and complete
                   and compile_in_window <= float(h["post_ready_compile_s"]))
    say("run", {"workload": args.workload, "seed": seed, **summ,
                "tokens_accounted_for": accounted,
                "xla_compile_s_in_window": compile_in_window,
                "waiting_max": max((s[1] for s in poller.samples
                                    if t0 <= s[0] < t1), default=0),
                "setup_split": {**sut.split, **{
                    k: v for k, v in setup_extra.items() if k != "reference"},
                    "warmup_traffic_s": float(mix["warmup_s"])},
                "reference": setup_extra["reference"],
                "cache_entries_added": sut.cache_entries_added()})

    devs = sut.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": int(cfg["chips"]) if not args.dry_run else len(devs),
              "memory_peak_bytes": sut.memory_peak_bytes()}
    result = {"correct": correct, "attempted": summ["attempted"],
              "failed": summ["failed"], "metrics": {}, "device": device}
    if not args.trace:
        for name in spec.cell_end_to_end(args.workload):
            value = (setup_s if name == "setup_s" else stats.client_metric(
                name, records, t0, t1, timeout_s))
            if value is not None:
                result["metrics"][name] = {
                    "value": value, "unit": spec.metric_entry(name)["unit"]}
        return result

    if tracer.error:
        raise RuntimeError(f"the profiler failed: {tracer.error}")
    red = None if args.dry_run else tracer.reduced()
    ctx = {"spec": spec, "workload": w, "config": cfg, "mix": mix,
           "records": records, "t0": t0, "t1": t1, "timeout_s": timeout_s,
           "before": marks["before"], "after": marks["after"],
           "trace_before": tracer.before, "trace_after": tracer.after,
           "samples": [s for s in poller.samples if t0 <= s[0] < t1],
           "steps": [poller.steps[k] for k in sorted(poller.steps)],
           "wall0": time.time() - (time.monotonic() - t0),
           "wall1": time.time() - (time.monotonic() - t1),
           "trace": red, "sut": sut, "setup_s": setup_s,
           "attempted": summ["attempted"],
           "peak": None if args.dry_run else spec.peak(devs[0].device_kind)}
    for name in spec.cell_layer_metrics(args.workload):
        mf = spec.layer_metric(name)
        try:
            value = spec.reader(mf["reader"]["kind"]).read(ctx, mf["reader"])
        except Exception as e:   # one reader's fault costs one metric
            print(f"benchmark: the reader of {name} failed and the metric is "
                  f"left out: {type(e).__name__}: {e}", file=sys.stderr)
            value = None
        if value is not None:
            result["metrics"][name] = {"value": float(value),
                                       "unit": mf["unit"]}
    if red is not None:
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        result["breakdown"] = red.breakdown()
    return result


def shape_walk(spec: Spec, sut, cfg: dict, seed: int, dry: dict) -> int:
    """Set-up: one streamed request through every continuation rung and the
    batched prefill sizes, so that whatever the program compiles on first
    use (it warms its step executables itself, not every eager helper) is
    compiled before the window and not inside it, whichever requests the
    seed puts first. Returns how many requests it sent."""
    import random

    walk = cfg["shape_walk"]
    rng = random.Random(int(seed) ^ 0xA11)
    gen = client.LoadGenerator(sut.host, sut.port,
                               float(spec.harness["client_timeout_s"]),
                               lambda rec: None)

    def plan(i, n_prompt):
        n_out = int(walk["new_tokens"])
        if dry:
            n_prompt, n_out = traffic.clamp_for_dry_run(n_prompt, n_out,
                                                        dry["clamp"])
        return traffic.Planned(i, None, n_prompt, n_out, traffic.prompt_text(
            n_prompt, f"walk{seed}w{i}", rng))

    async def go():
        i = 0
        for n in walk["one_by_one"]:
            await gen._one(plan(i, n), time.monotonic(), False)
            i += 1
        for k in walk["together"]:
            await asyncio.gather(*[
                gen._one(plan(i + j, int(walk["together_tokens"])),
                         time.monotonic(), False) for j in range(k)])
            i += k

    asyncio.run(go())
    bad = [r.failure for r in gen.records if r.failure is not None]
    if bad:
        raise RuntimeError(f"the shape walk failed: {bad[:3]}")
    return len(gen.records)


def reference_check(spec: Spec, sut, cfg: dict, seed: int, dry_run: bool,
                    variant: str = "") -> dict:
    """The served path against the plain reference, on the configuration's
    prompts (the dry run's own at the tiny size)."""
    from benchmark.reference import check

    dry = spec.harness["dry_run"] if dry_run else None
    lengths = (dry["reference_prompts"] if dry
               else cfg["reference"]["prompt_tokens"])
    n_new = 4 if dry else int(cfg["reference"]["new_tokens"])
    # the published config's keys are the configuration file's own
    model = _tiny_model(sut) if dry else cfg
    return check.run(sut.generate, sut.params, model, lengths, n_new, seed,
                     pad_to=max(lengths) + n_new, variant=variant)


def set_up(spec: Spec, sut, cfg: dict, seed: int, dry_run: bool) -> dict:
    """What set-up holds after the server is ready: the reference check and
    the shape walk. Returns what ``one_run`` reports of them."""
    t_ref = time.monotonic()
    ref = reference_check(spec, sut, cfg, seed, dry_run)
    say("reference", ref)
    t_walk = time.monotonic()
    n_walk = shape_walk(spec, sut, cfg, seed,
                        spec.harness["dry_run"] if dry_run else None)
    return {"reference": ref, "reference_check_s": t_walk - t_ref,
            "shape_walk_s": time.monotonic() - t_walk,
            "shape_walk_requests": n_walk,
            "xla_compiles_after_ready_before_window": [
                n for t, n, _ in sut.compiles if t > sut.t_ready]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dry-run", action="store_true",
                    help="CPU, tiny model: control flow only, for the tests")
    ap.add_argument("--out", default=None,
                    help="directory for what the run writes (failures.jsonl, "
                         "the trace, the engine's config)")
    args = ap.parse_args(argv)
    logging.basicConfig(
        level="WARNING", stream=sys.stderr,
        format="%(asctime)s %(name)s %(levelname)s %(message)s")
    keep_cache_in_checkout()
    try:
        spec = Spec(ROOT)
        problems = spec.problems()
        if problems:
            raise SpecError("; ".join(problems))
        w, cfg, mix = spec.cell(args.workload)
    except SpecError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    try:
        from benchmark.server import NoAccelerator, SystemUnderTest
        import scalable_hw_agnostic_inference_tpu  # noqa: F401
    except ImportError as e:
        print(f"benchmark: the system under test is not in this checkout: "
              f"{e}", file=sys.stderr)
        return 2

    out_dir = os.path.join(ROOT, args.out or os.path.join(
        spec.harness["out_dir"], args.workload))
    sut = SystemUnderTest(cfg, spec.harness, args.seed, out_dir, args.dry_run)
    try:
        devs = sut.devices()
    except NoAccelerator as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 3
    say("device", {"platform": devs[0].platform, "kind": devs[0].device_kind,
                   "count": len(devs), "dry_run": args.dry_run})
    try:
        sut.start()
        extra = set_up(spec, sut, cfg, args.seed, args.dry_run)
        result = one_run(spec, args, args.seed, sut, T_PROCESS, extra)
    finally:
        sut.stop()
    print(json.dumps(result), flush=True)
    return 0


def _tiny_model(sut) -> dict:
    """The dry run's model, in the published config's keys."""
    m = sut.service._engine.cfg
    return {"num_hidden_layers": m.n_layers, "num_attention_heads": m.n_heads,
            "num_key_value_heads": m.n_kv_heads, "rms_norm_eps": m.rms_eps,
            "rope_theta": m.rope_theta}


if __name__ == "__main__":
    sys.exit(main())
