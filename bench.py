"""Ad-hoc benchmark modes: one process, one JSON line on success.

Headline (default): SD2.1 512x512 txt2img on a single chip — real UNet/VAE
geometry (random weights; throughput is weight-value-independent), bf16, the
whole 25-step CFG denoise loop as one jitted scan. ``vs_baseline`` compares
single-stream images/sec against the reference's inf2.xlarge unit at its
published breaking point: latency 0.67 s/img => 1.49 img/s (BASELINE.md,
reference ``README.md:261``) — i.e. single-stream latency here vs the
reference's p50 *at* its breaking point, the comparison BASELINE.md records.

``python bench.py llama`` benches the causal-LM decode path instead
(Llama-3.2-1B geometry tokens/sec); the other modes are listed in
``UNITS_BY_BENCH``.

A measurement needs the chip: on a CPU backend the script exits non-zero
and prints no metric line. ``--cpu`` is the explicit exception — tiny shapes
on the CPU platform, a smoke of the code path whose numbers are stamped
``platform: cpu`` and are not device measurements. A failure is a failure:
the exception propagates and the exit code is non-zero.
"""

from __future__ import annotations

import json
import os
import sys
import time

import jax
import jax.numpy as jnp

# inf2.xlarge SD2.1 breaking point: 0.67 s/img p50 (reference README.md:261)
SD_BASELINE_IMG_S = 1.0 / 0.67
#: unit of each mode's headline value
UNITS_BY_BENCH = {"llama": "tokens/sec", "t5": "sequences/sec",
                  "mllama": "tokens/sec", "llama_spec": "tokens/sec",
                  "vllm": "tokens/sec", "kvtier": "x", "qos": "x",
                  "disagg": "x", "migrate": "ms", "kvfabric": "x",
                  "scaler": "s", "hedge": "x",
                  "sd": "images/sec", "sd8": "images/sec",
                  "flux": "images/sec"}
# $/hr: v5e-1 on-demand (us-central, 1 chip) vs the reference's inf2.xlarge
# (reference README.md:192). The north star is throughput per DOLLAR, so
# every bench line carries the cost basis it was computed with.
V5E_COST_HR = 1.20
INF2_COST_HR = 0.7582


_ROOT = os.path.dirname(os.path.abspath(__file__))


def _pctl(xs, q):
    """Nearest-rank percentile over a small sample (ONE definition —
    bench_qos and bench_disagg must report p99 with identical math)."""
    xs = sorted(xs)
    return xs[min(len(xs) - 1, int(q * (len(xs) - 1)))]



def _which_from_argv(argv) -> str:
    """THE argv->bench-key dispatch."""
    if "llama_spec" in argv:  # before the llama prefix match below
        return "llama_spec"
    if any(a.startswith("llama") for a in argv):
        return "llama"
    for k in ("vllm", "kvtier", "qos", "disagg", "migrate", "kvfabric",
              "scaler", "hedge", "flux", "t5", "mllama", "sd8"):
        if k in argv:
            return k
    return "sd"


def _published(key: str):
    """Self-baseline anchor from BASELINE.json.published (repo-root path —
    cwd-independent), or None before the first promoted on-chip run."""
    try:
        with open(os.path.join(_ROOT, "BASELINE.json")) as f:
            return json.load(f)["published"].get(key)
    except Exception:
        return None


def _phases_of(fins) -> dict:
    """Per-phase medians (seconds) across a batch of engine ``Finished``
    results — the queue/prefill/decode split from the obs timeline, attached
    to engine bench lines so a BENCH_*.json regression says WHERE the time
    went (queue wait vs prefill vs decode), not just that tok/s moved."""
    import statistics

    out = {}
    for k in ("queue_s", "prefill_s", "decode_s", "total_s"):
        vals = [f.timing[k] for f in fins
                if f.timing is not None and k in f.timing]
        if vals:
            out[k.replace("_s", "_s_p50")] = round(statistics.median(vals), 4)
    return out


def _dollars(out: dict, *, inf2_value: float | None = None) -> dict:
    """Attach the cost basis + work-per-dollar fields to a bench line.

    ``per_dollar`` is work units per dollar of chip time; when the reference
    publishes a comparable inf2 number, ``per_dollar_vs_inf2`` is the
    throughput/$ ratio (the BASELINE.md north star: >= 2.0).
    """
    out["chip_cost_per_hr"] = V5E_COST_HR
    out["per_dollar"] = round(out["value"] * 3600.0 / V5E_COST_HR, 2)
    if inf2_value is not None:
        out["per_dollar_vs_inf2"] = round(
            (out["value"] / V5E_COST_HR) / (inf2_value / INF2_COST_HR), 3)
    return out


def bench_sd8(tiny: bool) -> dict:
    """Batch-8 flash-attention throughput bench — the sd21-tpub8 serving
    tier's configuration (deploy/gen_units.py: SD_BATCH_MAX=8 +
    SHAI_ATTN_IMPL=pallas), driven through the coalescer's own
    txt2img_batch executable. This is the on-chip validation target for
    PERF_MODEL.md's headline projection (batch-8 + flash is the modeled
    path past 2x throughput/$ vs inf2)."""
    return bench_sd(tiny, batch=8, attn="pallas")


def bench_sd(tiny: bool, batch: int = 1, attn: str = "") -> dict:
    from scalable_hw_agnostic_inference_tpu.core.aot import (
        host_init,
        to_default_device,
    )
    from scalable_hw_agnostic_inference_tpu.models import sd as sd_mod

    if attn:
        # trace-time dispatch override (ops.attention): must be set before
        # the first pipeline build
        os.environ["SHAI_ATTN_IMPL"] = attn
    if tiny:
        variant, size, steps, seq = sd_mod.SDVariant.tiny(), 16, 2, 8
        attn = ""  # pallas kernels need a real TPU; tiny tier is CPU
        os.environ.pop("SHAI_ATTN_IMPL", None)
    else:
        variant, size, steps, seq = sd_mod.SDVariant.sd21_base(), 512, 25, 77

    unet = sd_mod.UNet2DCondition(variant.unet)
    f = 2 ** (len(variant.vae.block_out) - 1)
    lat = size // f
    from scalable_hw_agnostic_inference_tpu.models.convert import cast_f32_to_bf16

    unet_params = host_init(
        unet.init, lambda: jax.random.PRNGKey(0),
        lambda: jnp.zeros((1, lat, lat, variant.unet.in_channels)),
        lambda: jnp.zeros((1,), jnp.int32),
        lambda: jnp.zeros((1, seq, variant.unet.cross_attention_dim)),
    )
    unet_params = to_default_device(cast_f32_to_bf16(unet_params))
    vae = sd_mod.AutoencoderKL(variant.vae)
    vae_params = to_default_device(host_init(
        vae.init, lambda: jax.random.PRNGKey(1),
        lambda: jnp.zeros((1, lat, lat, variant.vae.latent_channels))))
    rng = jax.random.PRNGKey(0)

    D = variant.unet.cross_attention_dim

    @jax.jit  # one dispatch for the stub conditioning (not benched)
    def text_encode(ids):  # conditioning cost is negligible; bench unet+vae
        return jax.nn.one_hot(ids % D, D, dtype=jnp.bfloat16)

    pipe = sd_mod.StableDiffusion(variant, unet_params, vae_params, text_encode)
    ids = jnp.zeros((1, seq), jnp.int32)

    if batch > 1:
        # the coalescer's own latents-as-argument executable, exactly as the
        # SD_BATCH_MAX serving tier runs it
        bids = jnp.zeros((batch, seq), jnp.int32)
        lats = jnp.concatenate(
            [pipe.init_latents(i, lat, lat, steps) for i in range(batch)])

        def run_batch():
            return pipe.txt2img_batch(bids, bids, lats, height=size,
                                      width=size, steps=steps)

        img = run_batch()  # warm (compiles the ('batch', B, ...) pipeline)
        runs = 3
        t0 = time.perf_counter()
        for _ in range(runs):
            img = run_batch()
        dt = (time.perf_counter() - t0) / runs
        assert img.shape[0] == batch and img.shape[1] == size
        label = f" b{batch}" + (f"-{attn}" if attn else "")
        return _dollars({
            "metric": f"sd21-{size}px {steps}-step{label} txt2img img/s "
                      f"({jax.devices()[0].platform})",
            "value": round(batch / dt, 4),
            "unit": "images/sec",
            "vs_baseline": round((batch / dt) / SD_BASELINE_IMG_S, 3),
        }, inf2_value=SD_BASELINE_IMG_S)

    def run(key):
        return pipe.txt2img(ids, ids, rng=key, height=size, width=size,
                            steps=steps)

    img = run(rng)  # warm: compiles the full pipeline
    runs = 3
    t0 = time.perf_counter()
    for i in range(runs):
        img = run(jax.random.PRNGKey(i))
    dt = (time.perf_counter() - t0) / runs
    assert img.shape[1] == size
    return _dollars({
        "metric": f"sd21-{size}px {steps}-step txt2img img/s "
                  f"({jax.devices()[0].platform})",
        "value": round(1.0 / dt, 4),
        "unit": "images/sec",
        "vs_baseline": round((1.0 / dt) / SD_BASELINE_IMG_S, 3),
    }, inf2_value=SD_BASELINE_IMG_S)


def bench_llama(tiny: bool) -> dict:
    from scalable_hw_agnostic_inference_tpu.models.generate import make_generate
    from scalable_hw_agnostic_inference_tpu.models.llama import (
        LlamaConfig,
        LlamaForCausalLM,
    )

    quant = "int8" in sys.argv
    if tiny:
        cfg, batch, prompt, new = LlamaConfig.tiny(), 2, 32, 16
        name = "tiny"
    elif "llama3b" in sys.argv:
        # the largest Llama that fits one v5e chip in bf16 with headroom
        cfg = LlamaConfig.llama32_3b()
        batch, prompt, new = 8, 128, 128
        name = "llama3.2-3b-geometry"
    else:
        cfg = LlamaConfig.llama32_1b()
        batch, prompt, new = 8, 128, 128
        name = "llama3.2-1b-geometry"

    from scalable_hw_agnostic_inference_tpu.core.aot import (
        host_init,
        to_default_device,
    )
    from scalable_hw_agnostic_inference_tpu.models.convert import cast_f32_to_bf16

    # init the float model on CPU; the int8 variant quantizes host-side
    # (the serving boot path: ops.quant.quantize_params_tree) and runs the
    # same geometry through QuantDense weights
    float_model = LlamaForCausalLM(cfg, dtype=jnp.bfloat16)
    params = host_init(float_model.init, lambda: jax.random.PRNGKey(0),
                       lambda: jnp.zeros((1, 8), jnp.int32))
    params = cast_f32_to_bf16(params)
    if quant:
        from scalable_hw_agnostic_inference_tpu.ops.quant import (
            quantize_params_tree,
        )

        params = quantize_params_tree(params)
        name += "-int8"
    params = to_default_device(params)
    rng = jax.random.PRNGKey(0)
    model = LlamaForCausalLM(cfg, dtype=jnp.bfloat16, quant=quant)
    gen = make_generate(model, cfg, prompt_bucket=prompt, max_new_tokens=new,
                        eos_id=-1)
    ids = jax.random.randint(rng, (batch, prompt), 3, cfg.vocab_size, jnp.int32)
    plen = jnp.full((batch,), prompt, jnp.int32)
    out = gen(params, ids, plen, rng, 1.0, 0, 1.0)
    out.tokens.block_until_ready()
    runs = 3
    t0 = time.perf_counter()
    for i in range(runs):
        out = gen(params, ids, plen, jax.random.fold_in(rng, i), 1.0, 0, 1.0)
    out.tokens.block_until_ready()
    dt = (time.perf_counter() - t0) / runs
    toks = batch * new / dt
    key = {"llama3.2-1b-geometry": "llama1b_decode_tok_s",
           "llama3.2-3b-geometry": "llama3b_decode_tok_s",
           "llama3.2-1b-geometry-int8": "llama1b_int8_decode_tok_s",
           "llama3.2-3b-geometry-int8": "llama3b_int8_decode_tok_s"}.get(name)
    base = _published(key)
    return _dollars({
        "metric": f"{name} decode tok/s (bs={batch}, "
                  f"{jax.devices()[0].platform})",
        "value": round(toks, 2),
        "unit": "tokens/sec",
        "vs_baseline": round(toks / base, 3) if base else 1.0,
    })


def bench_llama_spec(tiny: bool) -> dict:
    """Speculative decoding tokens/sec through the paged engine: prompt-
    lookup ([ngram]) drafting with num_speculative_tokens=4, verified by the
    multi-token executable (engine/runner.py make_verify) — the PR-1
    tentpole's measured number. The workload is repetitive prompts (the
    regime prompt lookup targets: extraction/summarization-style requests
    whose output quotes the input); the line carries the realized
    acceptance_rate and tokens_per_verify so the perf model's
    acceptance-dependent projection (perf/model.py spec_decode_model) can be
    checked against an on-chip measurement, not just the roofline.
    """
    import numpy as np

    from scalable_hw_agnostic_inference_tpu.engine import EngineConfig
    from scalable_hw_agnostic_inference_tpu.engine.engine import (
        LLMEngine,
        SamplingParams,
    )
    from scalable_hw_agnostic_inference_tpu.models import llama as llama_mod

    if tiny:
        cfg = llama_mod.LlamaConfig.tiny()
        ecfg = EngineConfig(max_model_len=128, max_num_seqs=2, block_size=8,
                            context_encoding_buckets=(32,),
                            max_new_tokens=32,
                            speculative_model="[ngram]",
                            num_speculative_tokens=4)
        batch, prompt_len, new = 2, 24, 24
        name = "llama-tiny-spec"
    else:
        cfg = llama_mod.LlamaConfig.llama32_1b()
        ecfg = EngineConfig(max_model_len=1024, max_num_seqs=4,
                            block_size=16, context_encoding_buckets=(128,),
                            max_new_tokens=128,
                            speculative_model="[ngram]",
                            num_speculative_tokens=4)
        batch, prompt_len, new = 4, 128, 128
        name = "llama3.2-1b-geometry-spec"

    params = llama_mod.geometry_params(cfg, quant=False)
    eng = LLMEngine(cfg, params, ecfg)
    rng = np.random.default_rng(0)
    base = rng.integers(3, cfg.vocab_size, 16).tolist()
    prompt = (base * ((prompt_len // 16) + 1))[:prompt_len]
    sp = SamplingParams(temperature=0.0, max_new_tokens=new)

    def run():
        for _ in range(batch):
            eng.add_request(prompt, sp)
        fins = []
        while eng.has_work:
            fins += eng.step()
        assert len(fins) == batch
        assert all(len(f.token_ids) == new for f in fins)
        return fins

    run()   # warm: prefill + decode + verify executables
    runs = 3
    fins = []
    t0 = time.perf_counter()
    for _ in range(runs):
        fins = run()
    dt = (time.perf_counter() - t0) / runs
    val = round(batch * new / dt, 2)
    base_v = _published("llama_spec_tps")
    out = _dollars({
        "metric": f"{name} spec-decode tok/s (bs={batch}, k=4, ngram, "
                  f"{jax.devices()[0].platform})",
        "value": val,
        "unit": "tokens/sec",
        "vs_baseline": round(val / base_v, 3) if base_v else 1.0,
    })
    out["acceptance_rate"] = round(eng.spec.acceptance_rate, 4)
    out["tokens_per_verify"] = round(eng.spec.tokens_per_verify, 4)
    out["spec_fallback_steps"] = eng.spec.fallback_steps
    out["phases"] = _phases_of(fins)  # last measured batch, warm steady-state
    return out


def bench_vllm(tiny: bool) -> dict:
    """Continuous-batching engine decode tok/s, async pipeline ON vs OFF.

    The PR-6 tentpole's measured number: the same paged-engine decode
    workload run twice — ``SHAI_ASYNC_DECODE=1`` (device-resident batch
    state + one-step-lookahead dispatch) and ``=0`` (the lock-step
    reference oracle) — in one line, so a BENCH_*.json row shows both the
    absolute tok/s and the realized pipelining speedup. The per-mode
    ``step_gap_mean_ms`` (obs.steploop ``shai_engine_step_gap_seconds``)
    says WHERE the win came from: the async path's inter-step host gap
    collapses to ~0 while lock-step pays marshal+readback every step.

    Tracing overhead note (PR 18, fleet tracing): this bench drives the
    engine directly, and the engine hot path holds NO tracing calls —
    trace attribution rides plain dict stamps on the request
    (``Request.obs_extra``), spans are grafted by the serving layer
    after the fact, and with ``SHAI_TRACE=0`` every serving-layer seam
    is the shared no-op. Measured on this cpu-tiny geometry (bs=4):
    2089.7 tok/s tracing-on vs 2217.0 tok/s tracing-off — a gap within
    this config's run-to-run variance, consistent with the
    no-engine-cost design (the deviceless overhead-guard test in
    tests/test_trace_fleet.py pins the no-op contract itself).
    """
    import os

    import numpy as np

    from scalable_hw_agnostic_inference_tpu.engine import EngineConfig
    from scalable_hw_agnostic_inference_tpu.engine.engine import (
        LLMEngine,
        SamplingParams,
    )
    from scalable_hw_agnostic_inference_tpu.models import llama as llama_mod

    if tiny:
        cfg = llama_mod.LlamaConfig.tiny()
        ecfg = EngineConfig(max_model_len=128, max_num_seqs=4, block_size=8,
                            context_encoding_buckets=(32,),
                            max_new_tokens=48)
        batch, prompt_len, new = 4, 24, 48
        name = "vllm-tiny"
    else:
        cfg = llama_mod.LlamaConfig.llama32_1b()
        ecfg = EngineConfig(max_model_len=1024, max_num_seqs=8,
                            block_size=16, context_encoding_buckets=(128,),
                            max_new_tokens=128)
        batch, prompt_len, new = 8, 128, 128
        name = "vllm-1b-geometry"

    params = llama_mod.geometry_params(cfg, quant=False)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(3, cfg.vocab_size, prompt_len).tolist()
               for _ in range(batch)]
    sp = SamplingParams(temperature=0.0, max_new_tokens=new)

    def measure(async_on: bool):
        os.environ["SHAI_ASYNC_DECODE"] = "1" if async_on else "0"
        try:
            eng = LLMEngine(cfg, params, ecfg)
        finally:
            os.environ.pop("SHAI_ASYNC_DECODE", None)

        def run():
            fins = eng.generate(prompts, sp)
            assert len(fins) == batch
            assert all(len(f.token_ids) == new for f in fins)
            return fins

        run()   # warm: prefill + decode executables
        runs = 3
        fins = []
        t0 = time.perf_counter()
        for _ in range(runs):
            fins = run()
        dt = (time.perf_counter() - t0) / runs
        gap = eng.obs.step_gap.snapshot()
        return {
            "tok_s": round(batch * new / dt, 2),
            "step_gap_mean_ms": (round(gap["sum"] / gap["count"] * 1e3, 4)
                                 if gap["count"] else 0.0),
            "pipeline_flushes": eng.obs.pipeline_flushes,
            "phases": _phases_of(fins),
        }

    on = measure(True)
    off = measure(False)
    base = _published("vllm_decode_tok_s")
    out = _dollars({
        "metric": f"{name} engine decode tok/s (bs={batch}, "
                  f"SHAI_ASYNC_DECODE on vs off, "
                  f"{jax.devices()[0].platform})",
        "value": on["tok_s"],
        "unit": "tokens/sec",
        "vs_baseline": round(on["tok_s"] / base, 3) if base else 1.0,
    })
    out["async"] = on
    out["lockstep"] = off
    out["async_speedup"] = (round(on["tok_s"] / off["tok_s"], 3)
                            if off["tok_s"] else 0.0)
    out["phases"] = on["phases"]
    return out


def bench_kvtier(tiny: bool) -> dict:
    """KV-tier warm-hit TTFT: prompt replay after eviction pressure.

    The PR-10 tentpole's measured number. One engine with the host tier ON
    (``SHAI_KVTIER=1``, synchronous copies so the measurement is
    deterministic) and a pool small enough that filler prompts evict the
    probe prompt's prefix blocks — demoting them to the host tier. Each
    round then measures (a) a COLD same-length prompt (full prefill) and
    (b) the probe REPLAY, whose prefix swaps back in via the tier's
    scatter-write restore instead of re-running prefill. ``value`` is the
    cold/warm TTFT ratio (>1 = the tier is saving prefill work); the line
    carries the tier's own counters so a regression says whether the hit
    path or the copy path moved.
    """
    import os
    import statistics

    import numpy as np

    from scalable_hw_agnostic_inference_tpu.engine import EngineConfig
    from scalable_hw_agnostic_inference_tpu.engine.engine import (
        LLMEngine,
        SamplingParams,
    )
    from scalable_hw_agnostic_inference_tpu.models import llama as llama_mod

    if tiny:
        cfg = llama_mod.LlamaConfig.tiny()
        ecfg = EngineConfig(max_model_len=256, max_num_seqs=1, block_size=8,
                            num_blocks=26,
                            context_encoding_buckets=(32, 64, 128),
                            max_new_tokens=16, enable_prefix_caching=True)
        prompt_len, new = 120, 8
        name = "kvtier-tiny"
    else:
        cfg = llama_mod.LlamaConfig.llama32_1b()
        ecfg = EngineConfig(max_model_len=1024, max_num_seqs=2,
                            block_size=16, num_blocks=72,
                            context_encoding_buckets=(128, 256, 512),
                            max_new_tokens=16, enable_prefix_caching=True)
        prompt_len, new = 480, 8
        name = "kvtier-1b-geometry"

    params = llama_mod.geometry_params(cfg, quant=False)
    rng = np.random.default_rng(7)
    probe = rng.integers(3, cfg.vocab_size, prompt_len).tolist()
    fillers = [rng.integers(3, cfg.vocab_size, prompt_len).tolist()
               for _ in range(3)]
    sp = SamplingParams(temperature=0.0, max_new_tokens=new)

    os.environ["SHAI_KVTIER"] = "1"
    os.environ["SHAI_KVTIER_ASYNC"] = "0"  # deterministic copy timing
    try:
        eng = LLMEngine(cfg, params, ecfg)
    finally:
        os.environ.pop("SHAI_KVTIER", None)
        os.environ.pop("SHAI_KVTIER_ASYNC", None)
    assert eng.cache.tier is not None

    def ttft_of(prompt):
        [fin] = eng.generate([list(prompt)], sp)
        return fin.timing["prefill_s"]

    # warm every executable on the path (prefill buckets, cont chunks,
    # decode, tier movers) before timing anything
    ttft_of(probe)
    for f in fillers:
        ttft_of(f)
    ttft_of(probe)

    colds, warms = [], []
    for r in range(3):
        for f in fillers:  # eviction pressure: the probe's blocks demote
            ttft_of(f)
        cold = list(probe)
        cold[0] = int(cold[0]) % (cfg.vocab_size - 4) + 3 + r + 1
        colds.append(ttft_of(cold))      # same length, cold first block
        warms.append(ttft_of(probe))     # host-tier restore path
    cold_p50 = statistics.median(colds)
    warm_p50 = statistics.median(warms)
    snap = eng.cache.tier.snapshot()
    base = _published("kvtier_warm_ttft_speedup")
    val = round(cold_p50 / warm_p50, 3) if warm_p50 else 0.0
    return {
        "metric": f"{name} warm-host-tier TTFT speedup (prompt "
                  f"{prompt_len}, replay after eviction, "
                  f"{jax.devices()[0].platform})",
        "value": val,
        "unit": "x",
        "vs_baseline": round(val / base, 3) if base else 1.0,
        "cold_ttft_ms": round(cold_p50 * 1e3, 3),
        "warm_ttft_ms": round(warm_p50 * 1e3, 3),
        "tier": {k: snap[k] for k in ("hits", "misses", "stores",
                                      "restored", "evictions", "errors")},
    }


def bench_qos(tiny: bool) -> dict:
    """Multi-tenant QoS A/B: high-priority tenant p99 TTFT under a
    low-priority flood, ``SHAI_QOS=1`` (weighted-fair dequeue + priority
    preemption) vs ``=0`` (FIFO).

    One engine per mode runs identical seeded rounds: the flood tenant
    parks a burst of low-priority requests in the queue, then the vip
    tenant submits ONE high-priority request; the measurement is the vip
    request's realized TTFT (t_first - t_submit from the obs timeline).
    ``value`` is ``qos_flood_p99_ratio`` = FIFO flooded p99 / QoS flooded
    p99 — how many × of the flood-induced TTFT inflation the class-aware
    dequeue removes (>1 = QoS is protecting the high class). The line
    carries both modes' p50/p99 plus the no-flood baseline so a
    regression says whether QoS got worse or the flood got cheaper.
    """
    import os
    import statistics

    import numpy as np

    from scalable_hw_agnostic_inference_tpu.engine import EngineConfig
    from scalable_hw_agnostic_inference_tpu.engine.engine import (
        LLMEngine,
        SamplingParams,
    )
    from scalable_hw_agnostic_inference_tpu.models import llama as llama_mod

    if tiny:
        cfg = llama_mod.LlamaConfig.tiny()
        ecfg = EngineConfig(max_model_len=128, max_num_seqs=2, block_size=8,
                            context_encoding_buckets=(32,),
                            max_new_tokens=24)
        n_flood, flood_new, vip_new, rounds = 6, 16, 4, 6
        prompt_len = 20
        name = "qos-tiny"
    else:
        cfg = llama_mod.LlamaConfig.llama32_1b()
        ecfg = EngineConfig(max_model_len=1024, max_num_seqs=4,
                            block_size=16, context_encoding_buckets=(128,),
                            max_new_tokens=96)
        n_flood, flood_new, vip_new, rounds = 12, 64, 16, 5
        prompt_len = 100
        name = "qos-1b-geometry"

    params = llama_mod.geometry_params(cfg, quant=False)

    def measure(qos_on: bool):
        os.environ["SHAI_QOS"] = "1" if qos_on else "0"
        try:
            eng = LLMEngine(cfg, params, ecfg)
        finally:
            os.environ.pop("SHAI_QOS", None)
        rng = np.random.default_rng(17)  # same schedule both modes
        sp_flood = SamplingParams(temperature=0.0,
                                  max_new_tokens=flood_new)
        sp_vip = SamplingParams(temperature=0.0, max_new_tokens=vip_new)

        def prompt():
            return rng.integers(3, cfg.vocab_size, prompt_len).tolist()

        def drain(ids):
            done = {}
            while set(ids) - set(done):
                for f in eng.step():
                    done[f.req_id] = f
            return done

        drain([eng.add_request(prompt(), sp_vip)])  # warm the ladder
        # no-flood baseline: the vip tenant alone
        base = []
        for _ in range(rounds):
            rid = eng.add_request(prompt(), sp_vip, priority=0,
                                  tenant="vip")
            fin = drain([rid])[rid]
            base.append(fin.timing["t_first"] - fin.timing["t_submit"])
        # flooded rounds: the flood queues first, vip arrives last
        vip = []
        for _ in range(rounds):
            flood = [eng.add_request(prompt(), sp_flood, priority=2,
                                     tenant="flood")
                     for _ in range(n_flood)]
            eng.step()  # the flood takes the slots/queue
            rid = eng.add_request(prompt(), sp_vip, priority=0,
                                  tenant="vip")
            done = drain(flood + [rid])
            fin = done[rid]
            vip.append(fin.timing["t_first"] - fin.timing["t_submit"])

        return {
            "vip_ttft_p50_ms": round(statistics.median(vip) * 1e3, 2),
            "vip_ttft_p99_ms": round(_pctl(vip, 0.99) * 1e3, 2),
            "vip_ttft_noflood_p50_ms": round(
                statistics.median(base) * 1e3, 2),
            "preemptions": eng.obs.preemptions,
        }

    on = measure(True)
    off = measure(False)
    base = _published("qos_flood_p99_ratio")
    val = (round(off["vip_ttft_p99_ms"] / on["vip_ttft_p99_ms"], 3)
           if on["vip_ttft_p99_ms"] else 0.0)
    return {
        "metric": f"{name} high-priority p99 TTFT under low-priority "
                  f"flood, FIFO/QoS ratio ({n_flood}-deep flood, "
                  f"{jax.devices()[0].platform})",
        "value": val,
        "unit": "x",
        "vs_baseline": round(val / base, 3) if base else 1.0,
        "qos": on,
        "fifo": off,
    }


def bench_disagg(tiny: bool) -> dict:
    """Disaggregated prefill/decode A/B: a two-engine prefill/decode split
    (warm KV shipped through the kvnet frame codec, the in-process stand-in
    for the socket hop) vs one monolithic engine, under a mixed-length
    prompt load.

    Each round submits a fresh batch of mixed-length prompts concurrently.
    The monolithic engine pays every prompt's full prefill inline with its
    decoding batch; the decode engine receives each round's KV runs the
    way a handoff delivers them — prefill engine (role=prefill) finishes
    the prompt, its tier's run crosses ``encode_frames``/``decode_frames``
    byte-exact into the decode engine's host tier — and admits via the
    tier restore. ``value`` is ``disagg_ttft_ratio`` = mono TTFT p50 /
    disagg TTFT p50 on the decode side (>1 = the split is buying TTFT);
    the line carries p50/p99 TTFT + TPOT p50 for both modes so a
    regression says whether the restore path or the decode pace moved.
    Network latency is NOT modeled — the line measures the compute-side
    win of restoring vs re-prefilling, the same quantity the live socket
    test exercises end-to-end.
    """
    import os
    import statistics

    import numpy as np

    from scalable_hw_agnostic_inference_tpu.engine import EngineConfig
    from scalable_hw_agnostic_inference_tpu.engine.engine import (
        LLMEngine,
        SamplingParams,
    )
    from scalable_hw_agnostic_inference_tpu.kvnet import frames
    from scalable_hw_agnostic_inference_tpu.models import llama as llama_mod

    # the load is LONG mixed-length prompts — past the largest prefill
    # bucket, so the monolithic pod pays the chunked-prefill ladder
    # serially inside its decoding batch (THE TTFT/TPOT interference the
    # split exists to remove), while the decode pod restores the banked
    # run and computes only the tail chunk
    if tiny:
        cfg = llama_mod.LlamaConfig.tiny()
        kw = dict(max_model_len=256, max_num_seqs=4, block_size=8,
                  context_encoding_buckets=(32, 64, 128),
                  max_new_tokens=16, enable_prefix_caching=True)
        lens, new, rounds = (240, 192, 160, 232), 8, 3
        name = "disagg-tiny"
    else:
        cfg = llama_mod.LlamaConfig.llama32_1b()
        kw = dict(max_model_len=1024, max_num_seqs=4, block_size=16,
                  context_encoding_buckets=(128, 256, 512),
                  max_new_tokens=32, enable_prefix_caching=True)
        lens, new, rounds = (960, 832, 704, 928), 16, 3
        name = "disagg-1b-geometry"

    params = llama_mod.geometry_params(cfg, quant=False)
    sp = SamplingParams(temperature=0.0, max_new_tokens=new)
    sp1 = SamplingParams(temperature=0.0, max_new_tokens=1)

    def build(role: str, tier: bool) -> LLMEngine:
        os.environ["SHAI_KVTIER"] = "1" if tier else "0"
        os.environ["SHAI_KVTIER_ASYNC"] = "0"  # deterministic copies
        try:
            return LLMEngine(cfg, params, EngineConfig(role=role, **kw))
        finally:
            os.environ.pop("SHAI_KVTIER", None)
            os.environ.pop("SHAI_KVTIER_ASYNC", None)

    def prompts_for(round_i: int):
        rng = np.random.default_rng(31 + round_i)  # fresh every round:
        return [rng.integers(3, cfg.vocab_size, n).tolist()  # no device-
                for n in lens]                               # cache reuse

    def run_batch(eng, batch, params_):
        ids = [eng.add_request(list(p), params_) for p in batch]
        done = {}
        while set(ids) - set(done):
            for f in eng.step():
                done[f.req_id] = f
        eng.finish_pending()
        return [done[i] for i in ids]

    def ttfts(fins):
        return [f.timing["t_first"] - f.timing["t_submit"] for f in fins]

    def tpots(fins):
        return [f.timing["decode_s"] / max(1, len(f.token_ids) - 1)
                for f in fins if f.timing and "decode_s" in f.timing]

    def ship(pre: LLMEngine, dec: LLMEngine, batch) -> int:
        """The handoff wire, in-process: prefill tier run -> frames ->
        decode tier (byte-exact, same as GET /kv/blocks)."""
        moved = 0
        for p in batch:
            hashes = pre.cache.prefix_hashes(list(p))
            run = pre.cache.tier.get_run(hashes)
            if not run:
                continue
            entries = frames.decode_frames(frames.encode_frames(run))
            n_arr = len(entries[0]) - 1
            stacked = [np.stack([e[1 + ai] for e in entries], axis=1)
                       for ai in range(n_arr)]
            dec.cache.tier.store_batch([e[0] for e in entries], *stacked,
                                       len(entries))
            moved += len(entries)
        return moved

    # monolithic oracle: full prefill inline with the decode batch
    mono = LLMEngine(cfg, params, EngineConfig(**kw))
    run_batch(mono, prompts_for(99), sp)  # warm every executable
    mono_fins = []
    for r in range(rounds):
        mono_fins += run_batch(mono, prompts_for(r), sp)

    # split: prefill engine banks KV, decode engine restores + generates
    pre = build("prefill", tier=True)
    dec = build("decode", tier=True)
    warm = prompts_for(99)
    run_batch(pre, warm, sp1)
    ship(pre, dec, warm)
    run_batch(dec, warm, sp)              # warm incl. the restore movers
    dec_fins, shipped = [], 0
    for r in range(rounds):
        batch = prompts_for(r)
        run_batch(pre, batch, sp1)        # the prefill tier's work
        shipped += ship(pre, dec, batch)  # the wire
        dec_fins += run_batch(dec, batch, sp)  # the decode tier's TTFT

    mono_ttft, dec_ttft = ttfts(mono_fins), ttfts(dec_fins)
    val = (round(statistics.median(mono_ttft)
                 / statistics.median(dec_ttft), 3)
           if statistics.median(dec_ttft) else 0.0)
    base = _published("disagg_ttft_ratio")
    snap = dec.cache.tier.snapshot()
    return {
        "metric": f"{name} decode-pod TTFT vs monolithic under mixed "
                  f"prompt load, p50 ratio (batch {len(lens)}, "
                  f"{jax.devices()[0].platform})",
        "value": val,
        "unit": "x",
        "vs_baseline": round(val / base, 3) if base else 1.0,
        "mono_ttft_p50_ms": round(statistics.median(mono_ttft) * 1e3, 3),
        "mono_ttft_p99_ms": round(_pctl(mono_ttft, 0.99) * 1e3, 3),
        "disagg_ttft_p50_ms": round(statistics.median(dec_ttft) * 1e3, 3),
        "disagg_ttft_p99_ms": round(_pctl(dec_ttft, 0.99) * 1e3, 3),
        "mono_tpot_p50_ms": round(
            statistics.median(tpots(mono_fins)) * 1e3, 3),
        "disagg_tpot_p50_ms": round(
            statistics.median(tpots(dec_fins)) * 1e3, 3),
        "blocks_shipped": shipped,
        "decode_tier": {k: snap[k] for k in ("stores", "restored",
                                             "evictions", "errors")},
    }


def bench_kvfabric(tiny: bool) -> dict:
    """KV fabric A/B: peer-probe admission vs cold recompute under a
    shared-system-prompt workload.

    Pod A (role=prefill, host tier on) prefills each round's prompts and
    banks their KV runs; pod B runs the same round twice as two fresh
    engines — fabric OFF (every round's new system prefix is a full
    prefill) and fabric ON with a pushed-down holder slice naming pod A
    (the probe rung pulls the run over the kvnet wire — an
    ``httpx.MockTransport`` wired to pod A's tier through the REAL
    ``KvNetClient`` fetch/validate/publish path — and ordinary warm
    admission restores it). ``value`` is ``kvfabric_warm_ttft_ratio`` =
    fabric-off TTFT p50 / fabric-on TTFT p50 (>1 = the fabric is buying
    TTFT). Greedy decode on both sides; the line asserts token-exactness
    in-line and REQUIRES zero transport errors — a ratio produced by a
    degraded run is a lie, not a measurement. Network latency is NOT
    modeled (same caveat as bench_disagg): this is the compute-side win
    of restoring vs re-prefilling; the live two-pod socket test covers
    the wire end-to-end."""
    import os
    import statistics

    import httpx
    import numpy as np

    from scalable_hw_agnostic_inference_tpu.engine import EngineConfig
    from scalable_hw_agnostic_inference_tpu.engine.engine import (
        LLMEngine,
        SamplingParams,
    )
    from scalable_hw_agnostic_inference_tpu.kvnet import frames
    from scalable_hw_agnostic_inference_tpu.kvnet.client import KvNetClient
    from scalable_hw_agnostic_inference_tpu.kvnet.directory import (
        FabricProbe,
    )
    from scalable_hw_agnostic_inference_tpu.models import llama as llama_mod

    if tiny:
        cfg = llama_mod.LlamaConfig.tiny()
        kw = dict(max_model_len=768, max_num_seqs=4, block_size=8,
                  context_encoding_buckets=(32, 64, 128, 256),
                  max_new_tokens=16, enable_prefix_caching=True)
        n_prefix, n_tail, batch, new, rounds = 576, 24, 4, 8, 3
        name = "kvfabric-tiny"
    else:
        cfg = llama_mod.LlamaConfig.llama32_1b()
        kw = dict(max_model_len=1024, max_num_seqs=4, block_size=16,
                  context_encoding_buckets=(128, 256, 512),
                  max_new_tokens=32, enable_prefix_caching=True)
        n_prefix, n_tail, batch, new, rounds = 768, 64, 4, 16, 3
        name = "kvfabric-1b-geometry"

    params = llama_mod.geometry_params(cfg, quant=False)
    sp = SamplingParams(temperature=0.0, max_new_tokens=new)
    sp1 = SamplingParams(temperature=0.0, max_new_tokens=1)
    peer = "http://pod-a"

    def build(role: str = "both") -> LLMEngine:
        os.environ["SHAI_KVTIER"] = "1"
        os.environ["SHAI_KVTIER_ASYNC"] = "0"  # deterministic copies
        try:
            return LLMEngine(cfg, params, EngineConfig(role=role, **kw))
        finally:
            os.environ.pop("SHAI_KVTIER", None)
            os.environ.pop("SHAI_KVTIER_ASYNC", None)

    def prompts_for(round_i: int):
        # ONE shared system prefix per round (fresh each round: no
        # device-cache reuse across rounds), distinct per-request tails
        rng = np.random.default_rng(47 + round_i)
        prefix = rng.integers(3, cfg.vocab_size, n_prefix).tolist()
        return [prefix + rng.integers(3, cfg.vocab_size, n_tail).tolist()
                for _ in range(batch)]

    def run_batch(eng, prompts, params_, holders=None):
        ids = [eng.add_request(list(p), params_, kv_holders=holders)
               for p in prompts]
        done = {}
        while set(ids) - set(done):
            for f in eng.step():
                done[f.req_id] = f
        eng.finish_pending()
        return [done[i] for i in ids]

    def ttfts(fins):
        return [f.timing["t_first"] - f.timing["t_submit"] for f in fins]

    # pod A: banks every round's runs in its host tier (the holder)
    pod_a = build("prefill")
    run_batch(pod_a, prompts_for(99), sp1)          # warm executables
    tier_a = pod_a.cache.tier

    def handler(request: "httpx.Request") -> "httpx.Response":
        # pod A's /kv/blocks, served in-process: same frames, same
        # leading-run contract the socket endpoint implements
        if request.url.path == "/kv/blocks":
            hs = [int(h) for h in
                  (request.url.params.get("hashes") or "").split(",") if h]
            run = tier_a.get_run(hs)
            return httpx.Response(200, content=frames.encode_frames(run))
        return httpx.Response(404)

    def arm(eng: LLMEngine) -> FabricProbe:
        fab = FabricProbe(
            eng.cache.tier, kvnet_stats=eng.obs.kvnet, peers=[],
            client=KvNetClient(eng.cache.tier, eng.obs.kvnet,
                               transport=httpx.MockTransport(handler)))
        eng._kvfabric = fab
        eng.obs.kvfabric = fab.stats
        return fab

    b_off = build()
    b_on = build()
    fab = arm(b_on)
    # warm both B engines' executables on an unrelated round (and pod A
    # banks it so the fabric-on warm-up walks the full probe+restore
    # path — the restore movers compile OUTSIDE the measured rounds)
    warm = prompts_for(98)
    run_batch(pod_a, warm, sp1)
    run_batch(b_off, warm, sp)
    run_batch(b_on, warm, sp, holders=[peer])

    off_fins, on_fins = [], []
    for r in range(rounds):
        prompts = prompts_for(r)
        run_batch(pod_a, prompts, sp1)              # the holder's banking
        off_fins += run_batch(b_off, prompts, sp)   # cold: full prefill
        on_fins += run_batch(b_on, prompts, sp,     # warm: probe+restore
                             holders=[peer])

    # token-exactness is part of the measurement's validity, not a
    # separate test: greedy fabric-on output must equal fabric-off
    for fo, fn in zip(off_fins, on_fins):
        assert list(fo.token_ids) == list(fn.token_ids), \
            "kvfabric changed greedy tokens — the ratio is invalid"
    kv_errors = int(b_on.obs.kvnet.snapshot()["errors"])
    assert kv_errors == 0, f"kvfabric bench saw {kv_errors} kvnet errors"
    fsnap = fab.stats.snapshot()
    assert fsnap["remote_hits"] > 0, "fabric probe never landed a run"

    off_ttft, on_ttft = ttfts(off_fins), ttfts(on_fins)
    val = (round(statistics.median(off_ttft)
                 / statistics.median(on_ttft), 3)
           if statistics.median(on_ttft) else 0.0)
    base = _published("kvfabric_warm_ttft_ratio")
    return {
        "metric": f"{name} shared-system-prompt TTFT, fabric-off vs "
                  f"fabric-on p50 ratio (batch {batch}, "
                  f"{jax.devices()[0].platform})",
        "value": val,
        "unit": "x",
        "vs_baseline": round(val / base, 3) if base else 1.0,
        "off_ttft_p50_ms": round(statistics.median(off_ttft) * 1e3, 3),
        "off_ttft_p99_ms": round(_pctl(off_ttft, 0.99) * 1e3, 3),
        "on_ttft_p50_ms": round(statistics.median(on_ttft) * 1e3, 3),
        "on_ttft_p99_ms": round(_pctl(on_ttft, 0.99) * 1e3, 3),
        "errors": kv_errors,
        "kvfabric": {k: fsnap[k] for k in ("probes", "remote_hits",
                                           "remote_misses",
                                           "stale_holders")},
    }


def bench_migrate(tiny: bool) -> dict:
    """Live migration A/B: drain-with-migration vs drain-with-recompute
    under a mid-decode drain cut (the in-process stand-in for a
    mid-stream SIGTERM — the engines' migrate/resume path IS the one the
    socket drain drives).

    Each round decodes a batch on pod A, cuts it mid-decode (the drain's
    migrate sweep: ``migrate_out`` snapshots + banks KV), and resumes
    every request on pod B. The **migrate** arm ships the banked KV run
    through the MIGRATE envelope codec (byte-exact, same as
    ``POST /kv/migrate``) so B restores instead of re-prefilling; the
    **recompute** arm ships the manifest only (the drain-without-
    migration world: the replay pays full prefill over prompt+generated).
    ``value`` is ``migrate_resume_p50_ms`` — the migrated arm's p50
    added latency from the drain CUT to each resumed request's next
    token (snapshot + envelope + publish + restore-vs-reprefill: the
    whole stall a client sees; the decode tail past it is identical in
    both arms) — and the line carries the recompute arm's p50, the
    recompute/migrate ratio (>1 = migration is buying resume latency),
    and the REQUIRED ``errors`` count (0: every cut request completes,
    token-exact vs an uninterrupted oracle — the ladder's no-failure
    contract, measured).
    """
    import os
    import statistics
    import time as _time

    import numpy as np

    from scalable_hw_agnostic_inference_tpu.engine import EngineConfig
    from scalable_hw_agnostic_inference_tpu.engine.engine import (
        LLMEngine,
        SamplingParams,
    )
    from scalable_hw_agnostic_inference_tpu.kvnet import migrate as migmod
    from scalable_hw_agnostic_inference_tpu.kvnet.client import publish_run
    from scalable_hw_agnostic_inference_tpu.models import llama as llama_mod

    if tiny:
        cfg = llama_mod.LlamaConfig.tiny()
        kw = dict(max_model_len=256, max_num_seqs=4, block_size=8,
                  context_encoding_buckets=(32, 64, 128),
                  max_new_tokens=64, enable_prefix_caching=True)
        # LONG prompts: the resume's cost split is restore-vs-reprefill,
        # so the arm gap is the prompt's prefill cost (the same quantity
        # bench_kvtier's warm-replay line measures)
        lens, new, cut_steps, rounds = (240, 192, 160, 232), 12, 14, 3
        name = "migrate-tiny"
    else:
        cfg = llama_mod.LlamaConfig.llama32_1b()
        kw = dict(max_model_len=1024, max_num_seqs=4, block_size=16,
                  context_encoding_buckets=(128, 256, 512),
                  max_new_tokens=64, enable_prefix_caching=True)
        lens, new, cut_steps, rounds = (960, 832, 704, 928), 24, 18, 3
        name = "migrate-1b-geometry"

    params = llama_mod.geometry_params(cfg, quant=False)
    sp = SamplingParams(temperature=0.0, max_new_tokens=new)

    def build() -> LLMEngine:
        os.environ["SHAI_KVTIER"] = "1"
        os.environ["SHAI_KVTIER_ASYNC"] = "0"  # deterministic copies
        try:
            return LLMEngine(cfg, params, EngineConfig(**kw))
        finally:
            os.environ.pop("SHAI_KVTIER", None)
            os.environ.pop("SHAI_KVTIER_ASYNC", None)

    def prompts_for(round_i: int):
        rng = np.random.default_rng(47 + round_i)
        return [rng.integers(3, cfg.vocab_size, n).tolist() for n in lens]

    def run_batch(eng, batch, params_):
        ids = [eng.add_request(list(p), params_) for p in batch]
        done = {}
        while set(ids) - set(done):
            for f in eng.step():
                done[f.req_id] = f
        eng.finish_pending()
        return [done[i] for i in ids]

    def drain_to_done(eng, done):
        while eng.has_work:
            for f in eng.step():
                done[f.req_id] = (f, _time.monotonic())
        eng.finish_pending()

    # the uninterrupted oracle outputs, per round (token-exactness is an
    # ACCEPTANCE condition of this line, not just a latency number)
    oracle = build()
    run_batch(oracle, prompts_for(99), sp)  # warm every executable
    want = {r: [f.token_ids for f in run_batch(oracle, prompts_for(r), sp)]
            for r in range(rounds)}

    def arm(ship_kv: bool):
        A, B = build(), build()
        run_batch(A, prompts_for(99), sp)   # warm both pods' ladders
        run_batch(B, prompts_for(99), sp)
        lat, shipped, errors = [], 0, 0
        # one UNMEASURED cut+resume cycle first: the resume's warm
        # admission dispatches continuation executables at (start,
        # bucket) keys the plain warm batch never reaches — their
        # first-use compiles are warmup, not resume latency
        for r in [98] + list(range(rounds)):
            measured = r != 98
            batch = prompts_for(r)
            rids = [A.add_request(list(p), sp) for p in batch]
            early = {}
            for _ in range(cut_steps):     # mid-decode: the drain cut
                for f in A.step():
                    early[f.req_id] = f
            t_cut = _time.monotonic()
            resumes = []
            for i, rid in enumerate(rids):
                if rid in early:           # finished before the cut
                    continue
                fin = A.migrate_out(rid)
                if fin is None or fin.stop_reason != "migrated":
                    continue               # pending token completed it
                man = fin.migration
                entries = (A.cache.tier.get_run(man["hashes"])
                           if ship_kv and man["hashes"] else [])
                # the wire: envelope encode/decode, byte-exact
                man2, ent2 = migmod.decode_migration(
                    migmod.encode_migration(man, entries))
                if ent2:
                    shipped += publish_run(
                        B.cache.tier, [int(h) for h in man2["hashes"]],
                        ent2)
                pr = man2["params"]
                sp2 = SamplingParams(
                    temperature=pr["temperature"], top_k=pr["top_k"],
                    top_p=pr["top_p"],
                    max_new_tokens=pr["max_new_tokens"],
                    eos_id=pr["eos_id"])
                rid2 = B.add_request(
                    man2["prompt_ids"], sp2,
                    already_generated=man2["generated"],
                    orig_n_prompt=man2["n_prompt"])
                resumes.append((rid2, i))
            A.finish_pending()
            done = {}
            drain_to_done(B, done)
            if not measured:
                continue
            for rid2, i in resumes:
                if rid2 not in done:
                    errors += 1
                    continue
                fin, t_done = done[rid2]
                del t_done
                if (fin.stop_reason not in ("length", "eos")
                        or fin.token_ids != want[r][i]):
                    errors += 1
                    continue
                # the ADDED latency a client sees: from the drain CUT to
                # the resumed stream's next token. Measured from t_cut,
                # not the resume's submit — the migrate arm's snapshot/
                # envelope/publish cost happens between the two and is
                # part of the migration bill (excluding it would bias
                # the promoted ratio toward migration); the decode tail
                # after t_first is identical in both arms and excluded.
                lat.append(max(0.0, fin.timing["t_first"] - t_cut))
        return lat, shipped, errors

    mig_lat, blocks_shipped, mig_errors = arm(ship_kv=True)
    rec_lat, _, rec_errors = arm(ship_kv=False)
    mig_p50 = statistics.median(mig_lat) * 1e3 if mig_lat else 0.0
    rec_p50 = statistics.median(rec_lat) * 1e3 if rec_lat else 0.0
    base = _published("migrate_resume_p50_ms")
    return {
        "metric": f"{name} resumed-request added latency p50 after a "
                  f"mid-decode drain cut, migrate vs recompute "
                  f"({jax.devices()[0].platform})",
        "value": round(mig_p50, 3),
        "unit": "ms",
        # latency metric: smaller is better, vs_baseline inverts
        "vs_baseline": round(base / mig_p50, 3) if base and mig_p50
        else 1.0,
        "migrate_resume_p50_ms": round(mig_p50, 3),
        "migrate_resume_p99_ms": round(_pctl(mig_lat, 0.99) * 1e3, 3)
        if mig_lat else 0.0,
        "recompute_resume_p50_ms": round(rec_p50, 3),
        "recompute_over_migrate_ratio": round(rec_p50 / mig_p50, 3)
        if mig_p50 else 0.0,
        "resumed_requests": len(mig_lat),
        "blocks_shipped": blocks_shipped,
        "errors": mig_errors + rec_errors,  # MUST be 0: the ladder's
        # no-request-failure contract, measured
    }


def bench_scaler(tiny: bool) -> dict:
    """Autoscaler control-quality line: deviceless, trace-driven.

    Two questions, two traces, one simulator
    (``orchestrate/load_sim.py``):

    * **recovery** (the promoted value): replay the flash-crowd trace
      and measure SLO-recovery time — seconds from spike onset to the
      first sustained run of SLO-compliant ticks. Smaller is better, so
      ``vs_baseline`` inverts like the migrate line.
    * **economics**: replay the diurnal trace twice — scaled fleet vs a
      static fleet sized for PEAK need — and report
      ``pod_hours_ratio`` (scaled/static, < 1 = the controller pays for
      fewer pod-hours). The comparison only counts at equal SLO
      compliance, so both runs' compliance rides the line and the
      scaled run must stay inside the trace's error budget.

    ``errors`` is REQUIRED 0 (every simulated request reaches exactly
    one terminal state), and the control invariants (herd cap, anti-flap
    spacing, migrate-storm cap, recovery window) must hold on both
    traces — a violation fails the bench, not just dents the number.
    The pod capacity/warm-up prices come from PERF_MODEL.json via
    PerfPricer, so the sim's economics share the capacity checker's
    math. ``tiny`` shortens the traces; the control law is identical.
    """
    from scalable_hw_agnostic_inference_tpu.orchestrate import load_sim

    if tiny:
        flash = load_sim.flash_crowd_trace(duration_s=2700.0)
        day = load_sim.diurnal_trace(duration_s=3600.0)
        name = "scaler-tiny"
    else:
        flash = load_sim.flash_crowd_trace()
        day = load_sim.diurnal_trace()
        name = "scaler"

    crowd = load_sim.run_fleet_sim(flash)
    viol = crowd.violations()
    assert not viol, f"flash-crowd invariants violated: {viol}"
    rec = crowd.recovery_s()
    assert rec is not None, "fleet never recovered SLO after the spike"

    dyn = load_sim.run_fleet_sim(day)
    dviol = dyn.violations()
    assert not dviol, f"diurnal invariants violated: {dviol}"
    # the static strawman: a fleet sized for the trace's PEAK need,
    # priced with the SAME capacity math the scaler uses
    sim0 = load_sim.FleetSim(day)
    peak_rps = max(day.rps_fn(i * day.tick_s)
                   for i in range(int(day.duration_s / day.tick_s)))
    peak_need = sim0.scaler.pricer.replicas_for(
        peak_rps, util=sim0.cfg.target_util) or 8
    static = load_sim.run_fleet_sim(day, static_replicas=peak_need)
    ratio = (round(dyn.pod_hours / static.pod_hours, 3)
             if static.pod_hours else 0.0)
    # equal-compliance guard: the cheaper fleet must still hold the SLO
    budget = sim0.budget_frac
    assert dyn.slo_compliance() >= 1.0 - budget, \
        f"scaled diurnal compliance {dyn.slo_compliance():.3f} blew " \
        f"the {budget:.0%} budget — the ratio would be bought with " \
        f"SLO debt"

    errors = crowd.errors + dyn.errors + static.errors
    assert errors == 0, f"{errors} simulated requests failed"
    base = _published("scaler_recovery_s")
    return {
        "metric": f"{name} flash-crowd SLO recovery time "
                  f"(spike {flash.rps_fn(flash.event_at_s):.0f} rps, "
                  f"deviceless sim)",
        "value": round(rec, 1),
        "unit": "s",
        # latency-like metric: smaller is better, vs_baseline inverts
        "vs_baseline": round(base / rec, 3) if base and rec else 1.0,
        "scaler_pod_hours_ratio": ratio,
        "static_peak_replicas": peak_need,
        "scaled_pod_hours": round(dyn.pod_hours, 2),
        "static_pod_hours": round(static.pod_hours, 2),
        "scaled_slo_compliance": round(dyn.slo_compliance(), 4),
        "static_slo_compliance": round(static.slo_compliance(), 4),
        "flips_per_hour": round(crowd.flips_per_hour(), 2),
        "errors": errors,  # MUST be 0: exactly-once terminal contract
    }


def bench_hedge(tiny: bool) -> dict:
    """Request-reliability line: hedged dispatch under the fleet retry
    budget, deviceless and trace-driven (``orchestrate/load_sim.py``).

    One pod of four runs at 20% speed — the classic tail-amplification
    setup: round-robin keeps feeding it, and every request routed there
    waits out its deepening queue. The A/B replays the SAME steady trace
    twice: hedging off (the seed behavior), then hedging on with the
    retry budget funding one tail duplicate per stuck request
    (``retry_pct`` of primary traffic, the cova discipline). The
    promoted value is ``p99_off / p99_on`` — how much tail the hedge
    buys at a bounded (<= 1 + pct) attempt amplification.

    Hard gates, not just numbers: ``errors`` REQUIRED 0 on both runs,
    ``duplicate_executions`` REQUIRED 0 (the loser of every hedge race
    is absorbed by the pod-side idempotency model, never completed
    twice), and every :meth:`SimReport.violations` invariant — including
    the retry-amplification bound — must hold. ``tiny`` shortens the
    trace; the reliability machinery (the REAL ``resilience.hedge``
    classes) is identical.
    """
    from scalable_hw_agnostic_inference_tpu.orchestrate import load_sim

    dur = 600.0 if tiny else 1800.0
    trace = load_sim.SimTrace("slow_pod", dur, lambda t: 4.0, tick_s=15.0)
    kw = dict(static_replicas=4, slow_pods={0: 0.2}, pod_rps=3.0)
    off = load_sim.run_fleet_sim(trace, **kw)
    on = load_sim.run_fleet_sim(trace, hedge=True, retry_pct=0.3, **kw)
    for tag, rep in (("hedge-off", off), ("hedge-on", on)):
        viol = rep.violations()
        assert not viol, f"{tag} invariants violated: {viol}"
    errors = off.errors + on.errors
    assert errors == 0, f"{errors} simulated requests failed"
    dupes = off.double_terminal + on.double_terminal
    assert dupes == 0, f"{dupes} requests executed to completion twice"
    p99_off, p99_on = off.latency_p99(), on.latency_p99()
    assert p99_on > 0, "hedged run completed nothing"
    ratio = round(p99_off / p99_on, 3)
    base = _published("hedge_p99_ratio")
    return {
        "metric": "hedged-dispatch tail rescue (one 5x-slow pod of 4, "
                  "p99 hedge-off/hedge-on, deviceless sim)",
        "value": ratio,
        "unit": "x",
        "vs_baseline": round(ratio / base, 3) if base else 1.0,
        "hedge_p99_ratio": ratio,
        "p99_off_s": round(p99_off, 1),
        "p99_on_s": round(p99_on, 1),
        "hedges_fired": on.hedges,
        "hedges_deduped": on.deduped,
        "attempts": on.attempts,
        "created": on.created,
        "errors": errors,              # MUST be 0
        "duplicate_executions": dupes,  # MUST be 0
    }


def bench_flux(tiny: bool) -> dict:
    """Flux (rectified-flow DiT) txt2img on ONE chip.

    The real flux-schnell is ~12B params — 24 GiB bf16, beyond one v5e chip's
    16 GiB — so this benches a clearly-labeled SCALED geometry (same hidden
    width/heads/patching as flux, depth cut to 6 double + 12 single blocks,
    ~3.8B params) at 256x256, 4 steps, schnell-style (no guidance embedding,
    guidance=0). Self-baselined via BASELINE.json.published like the llama
    benches; the reference's comparable stage is the cova image stage
    (flux-dev 512^2 inf2 TP=8, 5.61 s — ``cova/README.md:98``), recorded in
    BASELINE.md but not directly comparable to a scaled single-chip geometry.
    """
    import dataclasses as _dc

    from scalable_hw_agnostic_inference_tpu.core.aot import (
        host_init,
        to_default_device,
    )
    from scalable_hw_agnostic_inference_tpu.models import flux as flux_mod
    from scalable_hw_agnostic_inference_tpu.models.convert import cast_f32_to_bf16
    from scalable_hw_agnostic_inference_tpu.models.flux_pipeline import FluxPipeline
    from scalable_hw_agnostic_inference_tpu.models.vae import VAEConfig

    if tiny:
        fcfg, vcfg = flux_mod.FluxConfig.tiny(), VAEConfig.tiny()
        size, steps, t5_len = 32, 2, 8
        name = "flux-tiny"
    else:
        fcfg = _dc.replace(flux_mod.FluxConfig.flux_dev(), n_double=6,
                           n_single=12, guidance_embed=False)
        vcfg = VAEConfig(latent_channels=16)
        size, steps, t5_len = 256, 4, 256
        name = "flux-schnell-scaled-4b-geometry"

    model = flux_mod.FluxTransformer(fcfg, dtype=jnp.bfloat16)
    f = 2 ** (len(vcfg.block_out) - 1)
    h = w = size // f
    ids = flux_mod.make_ids(1, t5_len, h, w)  # h,w are LATENT dims
    params = host_init(
        model.init, lambda: jax.random.PRNGKey(0),
        lambda: jnp.zeros((1, (h // 2) * (w // 2), fcfg.in_channels)),
        lambda: jnp.zeros((1, t5_len, fcfg.t5_dim)),
        lambda: jnp.zeros((1, fcfg.clip_dim)),
        lambda: jnp.zeros((1,)),
        lambda: jnp.zeros((1,)),
        lambda: ids,
    )
    params = to_default_device(cast_f32_to_bf16(params))
    from scalable_hw_agnostic_inference_tpu.models.vae import AutoencoderKL

    vae = AutoencoderKL(vcfg)
    vae_params = to_default_device(host_init(
        vae.init, lambda: jax.random.PRNGKey(1),
        lambda: jnp.zeros((1, h, w, vcfg.latent_channels))))

    D_t5, D_clip = fcfg.t5_dim, fcfg.clip_dim

    @jax.jit  # stub conditioning (not benched; cost negligible vs the DiT)
    def t5_encode(tok):
        return jax.nn.one_hot(tok % D_t5, D_t5, dtype=jnp.bfloat16)

    @jax.jit
    def clip_pooled(tok):
        return jax.nn.one_hot(tok[:, 0] % D_clip, D_clip, dtype=jnp.bfloat16)

    pipe = FluxPipeline(fcfg, params, vcfg, vae_params, t5_encode, clip_pooled)
    t5_ids = jnp.zeros((1, t5_len), jnp.int32)
    clip_ids = jnp.zeros((1, 8), jnp.int32)
    rng = jax.random.PRNGKey(0)

    def run(key):
        return pipe.txt2img(t5_ids, clip_ids, rng=key, height=size,
                            width=size, steps=steps, guidance=0.0)

    img = run(rng)  # warm
    runs = 3
    t0 = time.perf_counter()
    for i in range(runs):
        img = run(jax.random.PRNGKey(i))
    dt = (time.perf_counter() - t0) / runs
    assert img.shape[1] == size
    base = _published("flux_scaled_img_s")
    val = round(1.0 / dt, 4)
    return _dollars({
        "metric": f"{name} {size}px {steps}-step txt2img img/s "
                  f"({jax.devices()[0].platform})",
        "value": val,
        "unit": "images/sec",
        "vs_baseline": round(val / base, 3) if base else 1.0,
    })


def bench_t5(tiny: bool) -> dict:
    """T5 embedding throughput on ONE chip (the cova chain's embed stage,
    reference ``t5_model_api.py`` / ``cova/README.md:98``): batched encode +
    mean-pool, sequences/sec. Self-baselined like llama/flux."""
    from scalable_hw_agnostic_inference_tpu.core.aot import (
        host_init,
        to_default_device,
    )
    from scalable_hw_agnostic_inference_tpu.models import t5 as t5_mod
    from scalable_hw_agnostic_inference_tpu.models.convert import cast_f32_to_bf16

    if tiny:
        cfg, batch, seq = t5_mod.T5Config.tiny(), 4, 16
        name = "t5-tiny"
    else:
        cfg, batch, seq = t5_mod.T5Config.t5_v1_1_large(), 32, 128
        name = "t5-v1.1-large-geometry"

    model = t5_mod.T5Encoder(cfg, dtype=jnp.bfloat16)
    params = host_init(
        model.init, lambda: jax.random.PRNGKey(0),
        lambda: jnp.zeros((1, 8), jnp.int32),
        lambda: jnp.ones((1, 8), jnp.int32))
    params = to_default_device(cast_f32_to_bf16(params))

    @jax.jit
    def embed(p, ids, mask):
        return t5_mod.mean_pool(model.apply(p, ids, mask), mask)

    rng = jax.random.PRNGKey(0)
    ids = jax.random.randint(rng, (batch, seq), 3, cfg.vocab_size, jnp.int32)
    mask = jnp.ones((batch, seq), jnp.int32)
    embed(params, ids, mask).block_until_ready()   # warm
    runs = 5
    t0 = time.perf_counter()
    for _ in range(runs):
        out = embed(params, ids, mask)
    out.block_until_ready()
    dt = (time.perf_counter() - t0) / runs
    val = round(batch / dt, 2)
    base = _published("t5_embed_seq_s")
    return _dollars({
        "metric": f"{name} embed seq/s (bs={batch}, len={seq}, "
                  f"{jax.devices()[0].platform})",
        "value": val,
        "unit": "sequences/sec",
        "vs_baseline": round(val / base, 3) if base else 1.0,
    })


def bench_mllama(tiny: bool) -> dict:
    """Mllama (Llama-3.2-Vision) CAPTION-path decode on ONE chip: the paged
    engine with gated cross-attention layers attending a full vision-state
    buffer (4 tiles), int8 weights — the cova caption stage's compute
    (reference ``vllm_model_api_m.py`` / ``cova/README.md:98``). 11B text
    geometry born-int8 device-side (models.llama.geometry_params), so it
    fits the chip at every instant; the HBM budget gate validates on boot.
    Self-baselined; end-to-end tok/s for prompt 128 -> 64 new, bs=1.
    """
    import numpy as np

    from scalable_hw_agnostic_inference_tpu.engine import EngineConfig
    from scalable_hw_agnostic_inference_tpu.engine.engine import (
        LLMEngine,
        SamplingParams,
    )
    from scalable_hw_agnostic_inference_tpu.models import llama as llama_mod

    if tiny:
        cfg = llama_mod.LlamaConfig(
            vocab_size=512, dim=64, n_layers=4, n_heads=4, n_kv_heads=2,
            mlp_dim=128, max_seq_len=256, rope_theta=10000.0,
            tie_embeddings=True, cross_attention_layers=(1, 3))
        Lv, prompt_len, new = 34, 16, 8
        ecfg = EngineConfig(max_model_len=64, max_num_seqs=1, block_size=8,
                            context_encoding_buckets=(16,),
                            max_new_tokens=16)
        quant = False
        name = "mllama-tiny"
    else:
        cfg = llama_mod.LlamaConfig.mllama_11b_text()
        Lv = 4 * (1 + (560 // 14) ** 2)        # 4 tiles x (patches+1)
        prompt_len, new = 128, 64
        ecfg = EngineConfig(
            model="meta-llama/Llama-3.2-11B-Vision-Instruct-geometry",
            max_model_len=1024, max_num_seqs=1, block_size=128,
            context_encoding_buckets=(128,), quantization="int8",
            max_new_tokens=128)
        quant = True
        name = "mllama-11b-int8-geometry"

    params = llama_mod.geometry_params(cfg, quant=quant)
    eng = LLMEngine(cfg, params, ecfg, cross_seq_len=Lv)
    states = np.zeros((Lv, cfg.dim), np.float32)
    rng = np.random.default_rng(0)
    prompt = rng.integers(3, cfg.vocab_size, prompt_len).tolist()

    def run(n_new):
        eng.add_request(prompt,
                        SamplingParams(temperature=0.0, max_new_tokens=n_new),
                        cross_states=states, cross_len=Lv)
        fins = []
        while eng.has_work:
            fins += eng.step()
        assert len(fins) == 1 and len(fins[0].token_ids) == n_new
        return fins

    run(2)   # warm: prefill + decode executables + cross projection
    runs = 3
    fins = []
    t0 = time.perf_counter()
    for _ in range(runs):
        fins = run(new)
    dt = (time.perf_counter() - t0) / runs
    val = round(new / dt, 2)
    base = _published("mllama_caption_tok_s")
    out = _dollars({
        "metric": f"{name} caption tok/s (prompt {prompt_len}, Lv={Lv}, "
                  f"bs=1, {jax.devices()[0].platform})",
        "value": val,
        "unit": "tokens/sec",
        "vs_baseline": round(val / base, 3) if base else 1.0,
    })
    out["phases"] = _phases_of(fins)  # last measured request, warm state
    return out


def main() -> None:
    cpu = "--cpu" in sys.argv
    if cpu:  # JAX_PLATFORMS was read when jax was imported: use the config
        jax.config.update("jax_platforms", "cpu")
    platform = jax.devices()[0].platform
    if platform == "cpu" and not cpu:
        sys.exit("bench.py: JAX found no accelerator (backend is cpu). A "
                 "measurement needs the chip; pass --cpu for the tiny CPU "
                 "smoke of the code path.")
    from scalable_hw_agnostic_inference_tpu.core.aot import (
        enable_persistent_cache,
    )

    enable_persistent_cache()
    out = {"llama": bench_llama, "llama_spec": bench_llama_spec,
           "vllm": bench_vllm, "kvtier": bench_kvtier,
           "qos": bench_qos, "disagg": bench_disagg,
           "migrate": bench_migrate, "kvfabric": bench_kvfabric,
           "scaler": bench_scaler, "hedge": bench_hedge,
           "flux": bench_flux, "t5": bench_t5,
           "mllama": bench_mllama, "sd": bench_sd, "sd8": bench_sd8}[
        _which_from_argv(sys.argv)](cpu)
    # structured platform provenance: is_real() keys off this, never off
    # metric-string formatting
    out["platform"] = platform
    print(json.dumps(out))


if __name__ == "__main__":
    main()
