#!/usr/bin/env python3
"""Start the engine-backed server on the chip and prove that it answers.

    python chip_smoke.py             # one TPU chip: Mistral-7B geometry, int8
    python chip_smoke.py --tp 4      # one four-chip host: bf16, tensor parallel
    python chip_smoke.py --dry-run   # CPU, MODEL_ID=tiny, kernels interpreted

Two phases in ONE process (a chip belongs to one process at a time):

1. kernels — every Pallas kernel in ``ops/pallas/`` compiled for the chip at
   the shapes the engine below dispatches, compared on seeded inputs with its
   XLA oracle (``ops.kernel_check``);
2. server — the ``vllm`` unit booted by the same ``serve.__main__.boot`` the
   pod entrypoint runs, at the full width and depth of Mistral-7B with seeded
   random weights, served over loopback HTTP on a background thread: it warms
   its closed executable set, turns ready, and answers ``/generate`` (one
   prompt per prefill bucket, a repeat, one prompt past the largest bucket,
   four in flight together) and ``/v1/completions`` (with logprobs, and
   streamed).

Any failed check ends the run with a non-zero exit code and no result line.
Without ``--dry-run`` the run needs a TPU: on any other backend it exits
non-zero before the first phase. The last line of standard output is the
result, ``{"ok": true, "device": {...}}`` with the device as JAX reports it;
a dry run adds ``"dry_run": true`` and carries ``"platform": "cpu"``.

Nothing outside the checkout is read: weights come from a seed, the artifact
root and the ``vllm_config`` are written to a fresh temporary directory, and
the compile cache is where ``core.aot.enable_persistent_cache`` puts it.
"""

from __future__ import annotations

import argparse
import json
import logging
import math
import os
import sys
import tempfile
import threading
import time
import urllib.error
import urllib.request

MODEL_ID = "mistral-7b-geometry"
NEW_TOKENS = 32
#: the ``vllm_config`` the smoke writes: two prefill buckets x three prefill
#: batch sizes, three decode batch buckets, and a ``max_model_len`` of four
#: times the largest bucket so the continuation ladder has three rungs —
#: twelve executables, small enough to compile cold inside the time limit
ENGINE = {"max_model_len": 2048, "max_num_seqs": 4, "block_size": 16,
          "context_encoding_buckets": [128, 512], "max_prefill_batch": 4,
          "max_new_tokens": 64}
READY_TIMEOUT_S = 1000.0
#: XLA compile seconds allowed after ready, over all requests. First-use
#: scalar helpers cost about 0.1 s each on the chip (0.7 s for seven,
#: measured); one model-sized executable costs five seconds or more
POST_READY_COMPILE_S = 2.0


def check(ok: bool, what: str) -> None:
    if not ok:
        sys.exit(f"chip_smoke FAILED: {what}")


# -- phase 1: kernels -------------------------------------------------------

def kernel_cases(dry_run: bool, tp: int):
    from scalable_hw_agnostic_inference_tpu.models.llama import LlamaConfig
    from scalable_hw_agnostic_inference_tpu.ops import kernel_check

    if dry_run:
        # the same case builders at a size the Pallas interpreter finishes
        return kernel_check.engine_cases(
            4, 2, 64, block_size=8, buckets=(16,), max_model_len=32,
            max_num_seqs=4, max_prefill_batch=2)
    m = LlamaConfig.mistral_7b()
    return kernel_check.engine_cases(
        m.n_heads, m.n_kv_heads, m.head_dim, tp=tp,
        block_size=ENGINE["block_size"],
        buckets=ENGINE["context_encoding_buckets"],
        max_model_len=ENGINE["max_model_len"],
        max_num_seqs=ENGINE["max_num_seqs"],
        max_prefill_batch=ENGINE["max_prefill_batch"])


def kernel_phase(cases, interpret: bool) -> dict:
    """Max-abs error of every case against its oracle; all are printed
    before any fails the run."""
    errs = {}
    for case in cases:
        errs[case.name] = err = case.max_abs_err(interpret=interpret)
        print(f"kernel {case.name}: max_abs_err {err:.4g} (tol "
              f"{case.tol:.4g}) {'ok' if err <= case.tol else 'FAIL'}",
              flush=True)
    bad = [c.name for c in cases if not errs[c.name] <= c.tol]
    check(not bad, f"kernels disagree with their XLA oracle: {bad}")
    return errs


# -- phase 2: server --------------------------------------------------------

def http(base: str, method: str, path: str, body=None, timeout: float = 300.0):
    req = urllib.request.Request(
        base + path, method=method,
        data=None if body is None else json.dumps(body).encode(),
        headers={"content-type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, r.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


def call(base: str, method: str, path: str, body=None) -> dict:
    status, raw = http(base, method, path, body)
    check(status == 200, f"{method} {path} -> {status}: {raw[:500]!r}")
    return json.loads(raw)


def wait_ready(base: str) -> None:
    """Poll ``/readiness``. A 500 is a failed load: the server keeps the
    process alive on it, so waiting out the timeout would hide the error."""
    deadline = time.monotonic() + READY_TIMEOUT_S
    while True:
        status, raw = http(base, "GET", "/readiness", timeout=30.0)
        if status == 200:
            return
        check(status == 503, f"/readiness -> {status}: {raw[:2000]!r}")
        check(time.monotonic() < deadline,
              f"not ready after {READY_TIMEOUT_S:.0f}s")
        time.sleep(1.0)


def prompt_of(n_tokens: int, tag: int) -> str:
    """ASCII text the byte tokenizer turns into exactly ``n_tokens`` ids
    (BOS + one id per byte)."""
    words = f"request {tag} asks the quick brown fox about lazy dogs; "
    return (words * (n_tokens // len(words) + 1))[:n_tokens - 1]


def generate(base: str, prompt: str) -> dict:
    """Greedy ``/generate`` of NEW_TOKENS tokens; checks the answer's shape
    and returns it with the sampled ids under ``"ids"``."""
    out = call(base, "POST", "/generate", {
        "prompt": prompt, "temperature": 0.0, "max_new_tokens": NEW_TOKENS,
        "logprobs": 1})
    check(out["n_tokens"] == NEW_TOKENS and out["stop_reason"] == "length",
          f"/generate returned {out['n_tokens']} tokens "
          f"({out['stop_reason']}), wanted {NEW_TOKENS}")
    out["ids"] = [e["token"] for e in out["logprobs"]]
    check(len(out["ids"]) == NEW_TOKENS, "logprobs do not cover the tokens")
    check(all(math.isfinite(e["logprob"]) for e in out["logprobs"]),
          "non-finite logprob from /generate")
    return out


def request_phase(base: str, ecfg) -> dict:
    buckets = sorted(ecfg.context_encoding_buckets)
    # one prompt inside each prefill bucket (single prefill, batch-1 decode)
    singles = [generate(base, prompt_of(b - 8, i))
               for i, b in enumerate(buckets)]
    for b, out in zip(buckets, singles):
        check(out["n_prompt"] == b - 8,
              f"bucket {b}: n_prompt {out['n_prompt']}")
    # greedy decoding is a function of the prompt
    again = generate(base, prompt_of(buckets[0] - 8, 0))
    check(again["ids"] == singles[0]["ids"],
          "the same greedy prompt gave different tokens on a repeat")
    # past the largest bucket: chunked through every continuation rung
    n_long = ecfg.max_model_len - NEW_TOKENS - 8
    long_out = generate(base, prompt_of(n_long, 90))
    check(long_out["n_prompt"] == n_long,
          f"long prompt: n_prompt {long_out['n_prompt']} != {n_long}")
    # max_num_seqs in flight together: batched prefill, widest decode batch
    n_conc = ecfg.max_num_seqs
    outs = [None] * n_conc

    def one(i: int) -> None:
        outs[i] = generate(base, prompt_of(buckets[0] - 8 - i, 10 + i))

    threads = [threading.Thread(target=one, args=(i,)) for i in range(n_conc)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(600.0)
    check(all(o is not None for o in outs),
          "a concurrent /generate did not come back")
    # zero or broken weights answer every prompt with one token
    ids = {t for o in singles + [long_out] + outs for t in o["ids"]}
    check(len(ids) > 1, f"every generated token is {ids}")
    steps = call(base, "GET", "/debug/flight?requests=0")["engine_steps"]
    widest = max(s["running"] for s in steps)
    check(widest == n_conc,
          f"decode never ran {n_conc} rows together (widest {widest})")
    # the OpenAI surface: logprobs asked for, then streamed
    comp = call(base, "POST", "/v1/completions", {
        "prompt": prompt_of(40, 20), "temperature": 0.0,
        "max_tokens": NEW_TOKENS, "logprobs": 2})
    lp = comp["choices"][0]["logprobs"]
    check(comp["usage"]["completion_tokens"] == NEW_TOKENS
          and len(lp["token_logprobs"]) == NEW_TOKENS,
          f"/v1/completions usage {comp['usage']}")
    check(all(math.isfinite(x) for x in lp["token_logprobs"])
          and all(math.isfinite(x) for top in lp["top_logprobs"]
                  for x in top.values()),
          "non-finite logprob from /v1/completions")
    status, raw = http(base, "POST", "/v1/completions", {
        "prompt": prompt_of(40, 21), "temperature": 0.0,
        "max_tokens": NEW_TOKENS, "stream": True})
    check(status == 200, f"streamed /v1/completions -> {status}")
    events = [ln[len("data: "):] for ln in raw.decode().splitlines()
              if ln.startswith("data: ")]
    check(len(events) >= 2 and events[-1] == "[DONE]",
          f"stream did not end with [DONE]: {events[-3:]}")
    last = json.loads(events[-2])
    check("error" not in last
          and last["choices"][0]["finish_reason"] == "length",
          f"stream ended with {events[-2]}")
    return {"requests": len(singles) + 2 + n_conc + 2,
            "widest_decode_batch": widest}


def check_spread(engine, tp: int) -> dict:
    """Tensor parallelism is real: every leaf the TP rules split, and the KV
    pool, live in ``tp`` pieces on ``tp`` devices, and the devices hold
    about the same number of bytes."""
    import jax

    devs = jax.devices()
    check(len(devs) == tp, f"{len(devs)} devices visible, wanted {tp}")
    want = engine.shardings
    n_split = 0
    for (path, leaf), sh in zip(
            jax.tree_util.tree_flatten_with_path(engine.params)[0],
            jax.tree.leaves(want.params)):
        name = jax.tree_util.keystr(path)
        check(leaf.sharding.is_equivalent_to(sh, leaf.ndim),
              f"{name}: sharded {leaf.sharding}, rules say {sh}")
        if not sh.is_fully_replicated:
            n_split += 1
            shards = leaf.addressable_shards
            check(len({s.device for s in shards}) == tp
                  and all(s.data.size * tp == leaf.size for s in shards),
                  f"{name}: not split {tp} ways")
    check(n_split > 0, "the TP rules split no parameter leaf")
    for layer in engine.cache.kv:
        for name, pool in layer.items():
            shards = pool.addressable_shards
            check(len({s.device for s in shards}) == tp
                  and all(s.data.size * tp == pool.size for s in shards),
                  f"KV pool {name}: not split {tp} ways")
    in_use = [d.memory_stats()["bytes_in_use"] for d in devs]
    check(max(in_use) <= 1.5 * min(in_use),
          f"per-device bytes_in_use unbalanced: {in_use}")
    return {"tp_split_leaves": n_split, "bytes_in_use": in_use}


def server_phase(service, cfg, tp: int) -> dict:
    import jax

    from scalable_hw_agnostic_inference_tpu.serve.app import create_app
    from scalable_hw_agnostic_inference_tpu.serve.httpd import Server
    from scalable_hw_agnostic_inference_tpu.serve.metrics import (
        MetricsPublisher,
    )

    compiles = []   # (function, seconds) of every XLA compile, in order
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, fun_name="?", **kw:
        compiles.append((fun_name, secs))
        if event == "/jax/core/compile/backend_compile_duration" else None)
    # the pod's per-request metric lines go to stderr: stdout is the report
    app = create_app(cfg, service, publisher=MetricsPublisher(
        cfg.app, cfg.nodepool, cfg.pod_name, stream=sys.stderr))
    server = Server(app, host="127.0.0.1", port=0)
    t0 = time.monotonic()
    host, port = server.start_background()   # kicks off load + warm
    base = f"http://{host}:{port}"
    stopped = threading.Event()
    try:
        wait_ready(base)
        setup_s = time.monotonic() - t0
        n_setup_compiles = len(compiles)
        root = call(base, "GET", "/")
        dev = jax.devices()[0]
        check((root["device"], root["platform"], root["device_kind"],
               root["n_devices"]) == (cfg.device, dev.platform,
                                      dev.device_kind, len(jax.devices())),
              f"GET / does not show the live backend: {root}")
        report = {"setup_s": round(setup_s, 1)}
        if tp > 1:
            report.update(check_spread(service._engine, tp))
        t1 = time.monotonic()
        report.update(request_phase(base, service.ecfg))
        report["request_s"] = round(time.monotonic() - t1, 1)
        stats = call(base, "GET", "/stats")
        built = stats["service"]["executables"]
        eng = stats["engine"]
        check(built == eng["warmed_executables"] and eng["recompiles"] == 0,
              f"executables built after ready: {built} built, "
              f"{eng['warmed_executables']} warmed, "
              f"{eng['recompiles']} recompiles")
        check(eng["steps"] > 0, "the engine took no step")
        # the engine counts the executables it builds; XLA counts every
        # program anything compiled, the sampler and eager helpers included
        late = compiles[n_setup_compiles:]
        late_s = sum(secs for _, secs in late)
        check(late_s <= POST_READY_COMPILE_S,
              f"{late_s:.1f}s of XLA compiles after ready: {late}")
        report.update(
            executables=built, engine_steps=eng["steps"],
            xla_compiles_setup=n_setup_compiles,
            xla_compile_s_setup=round(
                sum(secs for _, secs in compiles[:n_setup_compiles]), 1),
            xla_compiles_after_ready=sorted(name for name, _ in late),
            xla_compile_s_after_ready=round(late_s, 2))
        return report
    finally:
        # the SIGTERM path: drain the engine loop, then stop the server
        app.state["begin_drain"](
            on_done=lambda: (server.request_shutdown(), stopped.set()))
        stopped.wait(60.0)


def n_cache_entries(cache_dir: str) -> int:
    # JAX creates the directory of JAX_COMPILATION_CACHE_DIR on first write
    return len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--dry-run", action="store_true",
                    help="CPU, MODEL_ID=tiny, kernels in interpret mode")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor_parallel_size (bf16 weights when > 1)")
    args = ap.parse_args(argv)
    dry, tp = args.dry_run, args.tp
    logging.basicConfig(
        level="INFO", stream=sys.stderr,
        format="%(asctime)s %(name)s %(levelname)s %(message)s")

    import jax
    import yaml

    from scalable_hw_agnostic_inference_tpu.core.device import apply_platform
    from scalable_hw_agnostic_inference_tpu.serve.__main__ import boot
    from scalable_hw_agnostic_inference_tpu.utils.env import ServeConfig

    device = "cpu" if dry else "tpu"
    apply_platform(device)   # a dry run stays off a chip that is there
    devs = jax.devices()
    print(f"chip_smoke: jax {jax.__version__} platform={devs[0].platform} "
          f"device_kind={devs[0].device_kind!r} n_devices={len(devs)}"
          f"{' DRY RUN' if dry else ''}", flush=True)

    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        quant = "" if tp > 1 else "int8"
        vllm_config = os.path.join(tmp, "vllm_config.yaml")
        with open(vllm_config, "w") as f:
            yaml.safe_dump({**ENGINE, "model": "tiny" if dry else MODEL_ID,
                            "tensor_parallel_size": tp,
                            "quantization": quant or None}, f)
        cfg = ServeConfig(
            app="chip-smoke", device=device,
            model_id="tiny" if dry else MODEL_ID, quantization=quant,
            vllm_config=vllm_config, max_new_tokens=ENGINE["max_new_tokens"],
            artifact_root=os.path.join(tmp, "artifacts"))
        # boot() refuses a backend that is not the DEVICE tier asked for
        service, _ = boot("vllm", cfg)
        cache_dir = jax.config.jax_compilation_cache_dir
        n_cache_before = n_cache_entries(cache_dir)
        print(f"compile cache {cache_dir}: {n_cache_before} entries",
              flush=True)

        t0 = time.monotonic()
        errs = kernel_phase(kernel_cases(dry, tp), interpret=dry)
        kernel_s = time.monotonic() - t0
        report = server_phase(service, cfg, tp)

    peaks = [d.memory_stats() for d in devs] if not dry else []
    report.update(
        kernel_s=round(kernel_s, 1),
        kernel_max_abs_err={k: float(f"{v:.4g}") for k, v in errs.items()},
        cache_dir=cache_dir, cache_entries_before=n_cache_before,
        cache_entries_after=n_cache_entries(cache_dir),
        peak_bytes_in_use=[m["peak_bytes_in_use"] for m in peaks],
        bytes_limit=[m["bytes_limit"] for m in peaks],
        model_id=cfg.model_id, quantization=quant, tensor_parallel_size=tp)
    print("report " + json.dumps(report), flush=True)
    result = {"ok": True,
              "device": {"platform": devs[0].platform,
                         "kind": devs[0].device_kind, "count": len(devs)}}
    if dry:
        result["dry_run"] = True
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
