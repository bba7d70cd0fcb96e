"""Engine-backed vllm service over HTTP: concurrent requests must coalesce
into the running batch (the continuous-batching payoff in serving)."""

import asyncio

import httpx
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from scalable_hw_agnostic_inference_tpu.models.registry import get_model
from scalable_hw_agnostic_inference_tpu.serve.app import create_app
from scalable_hw_agnostic_inference_tpu.utils.env import ServeConfig

from test_serve_http import drive_asgi, make_client, wait_ready


def _char_decode(ids):
    return "".join(chr(i) for i in ids)


def test_sse_assembler_stop_spanning_tokens():
    """A stop sequence split across token boundaries must never leak its
    prefix (OpenAI semantics: nothing at or after the stop is emitted)."""
    from scalable_hw_agnostic_inference_tpu.serve.services import (
        SseTextAssembler,
    )

    asm = SseTextAssembler(_char_decode, ["ab"])
    assert asm.push(ord("x")) == "x"
    assert asm.push(ord("a")) == ""   # held: could begin "ab"
    assert asm.push(ord("b")) == ""   # stop confirmed; "a" never leaked
    assert asm.stopped
    assert asm.finish() == ""

    # the held prefix releases when the next token disambiguates
    asm = SseTextAssembler(_char_decode, ["ab"])
    assert asm.push(ord("x")) == "x"
    assert asm.push(ord("a")) == ""
    assert asm.push(ord("c")) == "ac"
    assert not asm.stopped


def test_sse_assembler_utf8_holdback_flushes_at_end():
    from scalable_hw_agnostic_inference_tpu.serve.services import (
        SseTextAssembler,
    )

    asm = SseTextAssembler(lambda ids: "�" * len(ids), [])
    assert asm.push(1) == ""
    assert asm.push(2) == ""
    assert asm.finish() == "��"   # legit undecodable bytes still arrive


def test_sse_assembler_compacts_on_newline():
    from scalable_hw_agnostic_inference_tpu.serve.services import (
        SseTextAssembler,
    )

    asm = SseTextAssembler(_char_decode, [])
    assert asm.push(ord("q")) == "q"
    assert asm.push(ord("\n")) == "\n"
    assert asm.held == []          # bounded re-decode window reset
    assert asm.push(ord("z")) == "z"


def test_sse_assembler_forced_compaction_preserves_seam_spaces():
    """Long unbroken generations force mid-line compaction; the streamed
    concatenation must still equal the full decode (ADVICE r3: a fresh
    window's sentencepiece-style leading-space normalization used to drop
    the space at the seam — the one-token overlap prevents it)."""
    from scalable_hw_agnostic_inference_tpu.serve.services import (
        SseTextAssembler,
    )

    words = {i: f" w{i}" for i in range(400)}

    def sp_decode(ids):
        # sentencepiece semantics: a word-initial token decodes WITHOUT its
        # leading space at the start of the window
        return "".join(words[i] for i in ids).lstrip(" ")

    asm = SseTextAssembler(sp_decode, [])
    toks = list(range(400))  # > 2x COMPACT_AT, no newlines anywhere
    streamed = "".join(asm.push(t) for t in toks) + asm.finish()
    assert streamed == sp_decode(toks)
    # compaction actually engaged (window stayed bounded)
    assert len(asm.held) <= asm.COMPACT_AT


def make_service(tmp_path=None, **env_over):
    cfg = ServeConfig(app="llm", model_id="tiny", device="cpu",
                      max_new_tokens=8, vllm_config="/nonexistent.yaml",
                      **env_over)
    return cfg, get_model("vllm")(cfg)


@pytest.mark.slow  # tier-1 budget: see scripts/check_tier1_budget.py
@pytest.mark.asyncio
async def test_vllm_service_generate_and_batching():
    cfg, service = make_service()
    assert service.concurrency == service.ecfg.max_num_seqs >= 4
    app = create_app(cfg, service)
    async with make_client(app) as c:
        r = await wait_ready(c, timeout=300.0)
        assert r.status_code == 200, r.text

        r = await c.post("/generate", json={"prompt": "hello world",
                                            "temperature": 0.0,
                                            "max_new_tokens": 6})
        assert r.status_code == 200, r.text
        solo = r.json()
        assert solo["n_tokens"] == 6
        assert solo["stop_reason"] == "length"

        # concurrent fan-in: all requests in flight at once; greedy results
        # must match the solo result (batching must not change outputs)
        payload = {"prompt": "hello world", "temperature": 0.0,
                   "max_new_tokens": 6}
        rs = await asyncio.gather(*[c.post("/generate", json=payload)
                                    for _ in range(4)])
        for r in rs:
            assert r.status_code == 200
            assert r.json()["generated_text"] == solo["generated_text"]

        r = await c.post("/generate", json={"temperature": 0.0})
        assert r.status_code == 400  # missing prompt field


@pytest.mark.slow  # tier-1 budget: see scripts/check_tier1_budget.py
@pytest.mark.asyncio
async def test_vllm_openai_surface_and_stats():
    """OpenAI-compatible routes on the engine unit: /v1/models,
    /v1/completions (usage + stop sequences), /v1/chat/completions
    (template fallback) — plus engine gauges on /stats and /metrics."""
    cfg, service = make_service()
    app = create_app(cfg, service)
    async with make_client(app) as c:
        r = await wait_ready(c, timeout=300.0)
        assert r.status_code == 200, r.text

        r = await c.get("/v1/models")
        assert r.status_code == 200
        assert r.json()["data"][0]["id"] == "tiny"

        r = await c.post("/v1/completions", json={
            "prompt": "hello world", "max_tokens": 6, "temperature": 0.0})
        assert r.status_code == 200, r.text
        body = r.json()
        assert body["object"] == "text_completion"
        assert body["usage"]["completion_tokens"] == 6
        assert body["usage"]["total_tokens"] == (
            body["usage"]["prompt_tokens"] + 6)
        assert body["choices"][0]["finish_reason"] in ("stop", "length")
        full_text = body["choices"][0]["text"]

        # a stop sequence inside the generation truncates + flips the reason
        if len(full_text) > 1:
            r = await c.post("/v1/completions", json={
                "prompt": "hello world", "max_tokens": 6,
                "temperature": 0.0, "stop": [full_text[1]]})
            got = r.json()["choices"][0]
            assert got["text"] == full_text.split(full_text[1])[0]
            assert got["finish_reason"] == "stop"

        r = await c.post("/v1/chat/completions", json={
            "messages": [{"role": "user", "content": "hi there"}],
            "max_tokens": 4, "temperature": 0.0})
        assert r.status_code == 200, r.text
        body = r.json()
        assert body["object"] == "chat.completion"
        assert body["choices"][0]["message"]["role"] == "assistant"
        assert body["usage"]["completion_tokens"] == 4

        # logprobs: completions int form and chat bool+top_logprobs form
        r = await c.post("/v1/completions", json={
            "prompt": "hello world", "max_tokens": 4, "temperature": 0.0,
            "logprobs": 3})
        assert r.status_code == 200, r.text
        lp = r.json()["choices"][0]["logprobs"]
        assert len(lp["tokens"]) == len(lp["token_logprobs"]) == 4
        # dict-keyed (OpenAI completions shape): distinct ids may decode to
        # the same string (byte tokenizer drops out-of-range ids) and merge
        assert all(1 <= len(d) <= 3 for d in lp["top_logprobs"])
        assert all(v <= 0.0 for v in lp["token_logprobs"])

        r = await c.post("/v1/chat/completions", json={
            "messages": [{"role": "user", "content": "hi"}],
            "max_tokens": 3, "temperature": 0.0, "logprobs": True,
            "top_logprobs": 2})
        assert r.status_code == 200, r.text
        lp = r.json()["choices"][0]["logprobs"]["content"]
        assert len(lp) == 3
        assert all(len(e["top_logprobs"]) == 2 for e in lp)

        r = await c.post("/v1/completions", json={
            "prompt": "x", "stream": True, "logprobs": 1})
        assert r.status_code == 400  # not supported while streaming

        # n parallel samples: greedy copies are identical; bad n rejected
        r = await c.post("/v1/completions", json={
            "prompt": "hello world", "max_tokens": 4, "temperature": 0.0,
            "n": 2})
        assert r.status_code == 200, r.text
        ch = r.json()["choices"]
        assert [x["index"] for x in ch] == [0, 1]
        assert ch[0]["text"] == ch[1]["text"]  # greedy => identical
        assert r.json()["usage"]["completion_tokens"] == 8
        r = await c.post("/v1/completions", json={"prompt": "h", "n": 99})
        assert r.status_code == 400

        # SSE streaming: concatenated deltas must equal the non-streaming
        # text, chunks are OpenAI-shaped, and the stream terminates [DONE]
        import json as _json

        r = await c.post("/v1/completions", json={
            "prompt": "hello world", "max_tokens": 6, "temperature": 0.0,
            "stream": True})
        assert r.status_code == 200, r.text
        assert r.headers["content-type"].startswith("text/event-stream")
        events = [ln[len("data: "):] for ln in r.text.split("\n\n")
                  if ln.startswith("data: ")]
        assert events[-1] == "[DONE]"
        parsed = [_json.loads(e) for e in events[:-1]]
        assert all(p["object"] == "text_completion" for p in parsed)
        streamed = "".join(p["choices"][0]["text"] for p in parsed)
        assert streamed == full_text
        assert parsed[-1]["choices"][0]["finish_reason"] in ("stop", "length")
        assert all(p["choices"][0]["finish_reason"] is None
                   for p in parsed[:-1])

        r = await c.post("/v1/chat/completions", json={
            "messages": [{"role": "user", "content": "hi"}],
            "max_tokens": 4, "temperature": 0.0, "stream": True})
        assert r.status_code == 200, r.text
        events = [ln[len("data: "):] for ln in r.text.split("\n\n")
                  if ln.startswith("data: ")]
        assert events[-1] == "[DONE]"
        parsed = [_json.loads(e) for e in events[:-1]]
        assert all(p["object"] == "chat.completion.chunk" for p in parsed)
        assert parsed[0]["choices"][0]["delta"].get("role") == "assistant"
        content = "".join(p["choices"][0]["delta"].get("content", "")
                          for p in parsed)
        assert len(content) > 0

        r = await c.get("/stats")
        svc = r.json()["service"]
        assert svc["queue_waiting"] == 0 and svc["seqs_running"] == 0
        assert svc["blocks_free"] <= svc["blocks_total"]
        assert svc["executables"] > 0
        # requests ran above — the latency instruments must have samples
        assert svc["ttft_p50_ms"] > 0
        assert svc["tpot_p50_ms"] > 0

        r = await c.get("/metrics")
        if r.status_code == 200:  # prometheus_client present
            assert "shai_service_queue_waiting" in r.text


@pytest.mark.slow  # tier-1 budget: see scripts/check_tier1_budget.py
def test_stream_abandonment_cancels_engine_request():
    """A client disconnect abandons the SSE generator; the engine request
    must be cancelled (slot + blocks reclaimed), not decoded to
    max_new_tokens for nobody."""
    import time

    cfg, service = make_service()
    service.load()
    try:
        resp = service._openai_stream(
            "hello world",
            {"max_tokens": service.ecfg.max_new_tokens, "temperature": 0.0},
            "completion")
        async def abandon():
            it = resp.iterator.__aiter__()
            await anext(it)     # at least one chunk flowed
            await it.aclose()   # GeneratorExit — simulates the disconnect

        asyncio.run(abandon())
        deadline = time.time() + 30
        while time.time() < deadline:
            eng = service._engine
            if eng.n_running == 0 and eng.n_waiting == 0:
                break
            time.sleep(0.1)
        assert service._engine.n_running == 0, (
            "engine kept decoding after the stream was abandoned")
    finally:
        service.loop.stop()


@pytest.mark.slow  # tier-1 budget: see scripts/check_tier1_budget.py
def test_vllm_streaming_over_real_socket():
    """SSE through the real asyncio server: chunked transfer-encoding frames
    the stream and the connection stays reusable afterwards."""
    import http.client
    import json as _json
    import time

    from scalable_hw_agnostic_inference_tpu.serve.httpd import Server

    cfg, service = make_service()
    app = create_app(cfg, service)
    srv = Server(app, host="127.0.0.1", port=0)
    srv.start_background()
    port = srv.port
    deadline = time.time() + 300
    while True:
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
        conn.request("GET", "/readiness")
        r = conn.getresponse()
        r.read()
        if r.status == 200:
            break
        conn.close()
        assert time.time() < deadline, "service never became ready"
        time.sleep(1.0)

    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
    conn.request("POST", "/v1/completions",
                 body=_json.dumps({"prompt": "hello world", "max_tokens": 4,
                                   "temperature": 0.0, "stream": True}),
                 headers={"Content-Type": "application/json"})
    r = conn.getresponse()
    assert r.status == 200
    assert r.getheader("transfer-encoding") == "chunked"
    body = r.read().decode()  # http.client de-chunks transparently
    assert body.rstrip().endswith("data: [DONE]")
    # chunked framing ended cleanly: the SAME connection serves another
    # request (keep-alive survived the stream)
    conn.request("GET", "/health")
    r2 = conn.getresponse()
    assert r2.status == 200
    r2.read()
    conn.close()


@pytest.mark.slow  # tier-1 budget: see scripts/check_tier1_budget.py
@pytest.mark.asyncio
async def test_vllm_service_long_prompt_chunks():
    """A prompt past the largest prefill bucket must reach the engine
    un-truncated (chunked continuation prefill), not be silently cut at the
    bucket — and still generate deterministically."""
    cfg, service = make_service()
    app = create_app(cfg, service)
    async with make_client(app) as c:
        r = await wait_ready(c, timeout=300.0)
        assert r.status_code == 200, r.text
        # past the largest bucket (128) but with generation room inside
        # max_model_len=256 (byte tokenizer: ~4.3 ids per word)
        long_text = " ".join(f"w{i}" for i in range(40))
        ids = service._encode(long_text)
        max_bucket = max(service.ecfg.context_encoding_buckets)
        assert len(ids) > max_bucket, "prompt must exceed the largest bucket"
        assert len(ids) <= service._engine.max_prompt_len
        payload = {"prompt": long_text, "temperature": 0.0,
                   "max_new_tokens": 6}
        r1 = await c.post("/generate", json=payload)
        r2 = await c.post("/generate", json=payload)
        assert r1.status_code == 200, r1.text
        assert r1.json()["n_tokens"] == 6
        assert r1.json()["generated_text"] == r2.json()["generated_text"]


@pytest.mark.slow  # tier-1 budget: see scripts/check_tier1_budget.py
@pytest.mark.asyncio
async def test_vllm_service_int8_quantized(tmp_path):
    """`quantization: int8` in the mounted vllm_config.yaml boots the engine
    on int8 weights (the vLLM ConfigMap knob, TPU-natively) and still serves
    deterministic greedy generations."""
    y = tmp_path / "vllm_config.yaml"
    y.write_text("model: tiny\nmax_model_len: 256\nblock_size: 16\n"
                 "max_num_seqs: 4\ncontext_encoding_buckets: [32, 64]\n"
                 "quantization: int8\nmax_new_tokens: 8\n")
    cfg = ServeConfig(app="llm", model_id="tiny", device="cpu",
                      max_new_tokens=8, vllm_config=str(y))
    service = get_model("vllm")(cfg)
    app = create_app(cfg, service)
    async with make_client(app) as c:
        r = await wait_ready(c, timeout=300.0)
        assert r.status_code == 200, r.text
        # the engine really runs on int8 kernels
        p = service._engine.params["params"]
        assert p["layer_0"]["attn"]["q"]["kernel_q"].dtype == jnp.int8
        payload = {"prompt": "hello world", "temperature": 0.0,
                   "max_new_tokens": 6}
        r1 = await c.post("/generate", json=payload)
        r2 = await c.post("/generate", json=payload)
        assert r1.status_code == 200, r1.text
        assert r1.json()["n_tokens"] == 6
        assert r1.json()["generated_text"] == r2.json()["generated_text"]


@pytest.mark.slow  # tier-1 budget: see scripts/check_tier1_budget.py
@pytest.mark.asyncio
async def test_vllm_service_multimodal_generate():
    """vllm_model_api_m parity: optional base64 image conditions generation."""
    import base64
    import io

    from PIL import Image

    cfg, service = make_service()
    app = create_app(cfg, service)
    async with make_client(app) as c:
        r = await wait_ready(c, timeout=300.0)
        assert r.status_code == 200, r.text

        buf = io.BytesIO()
        Image.new("RGB", (32, 32), (10, 200, 30)).save(buf, format="PNG")
        img = base64.b64encode(buf.getvalue()).decode()
        base = {"prompt": "describe the image", "temperature": 0.0,
                "max_new_tokens": 6}
        r_plain = await c.post("/generate", json=base)
        r_img = await c.post("/generate", json={**base, "image_b64": img})
        assert r_img.status_code == 200, r_img.text
        assert r_img.json()["n_tokens"] == 6
        # the image actually conditions the output
        assert r_img.json()["generated_text"] != r_plain.json()["generated_text"]
        # same image -> same output
        r_img2 = await c.post("/generate", json={**base, "image_b64": img})
        assert r_img2.json()["generated_text"] == r_img.json()["generated_text"]


def test_vllm_service_reads_configmap(tmp_path):
    cfg_yaml = tmp_path / "vllm_config.yaml"
    cfg_yaml.write_text(
        "model: tiny\nmax_model_len: 128\nmax_num_seqs: 2\nblock_size: 16\n"
        "context_encoding_buckets: [32, 64]\nis_continuous_batching: true\n"
        "device: neuron\n"
    )
    cfg = ServeConfig(app="llm", model_id="", device="cpu",
                      vllm_config=str(cfg_yaml))
    service = get_model("vllm")(cfg)
    assert service.ecfg.max_num_seqs == 2
    assert service.ecfg.context_encoding_buckets == (32, 64)
    assert "device" in service.ecfg.ignored_keys
    assert service.concurrency == 2


# ---------------------------------------------------------------------------
# real VLM checkpoint support (VERDICT r1 #4): LLaVA layout converter parity
# ---------------------------------------------------------------------------

def _tiny_hf_llava():
    torch = pytest.importorskip("torch")
    from transformers import (
        CLIPVisionConfig,
        LlamaConfig as HFLlamaConfig,
        LlavaConfig,
        LlavaForConditionalGeneration,
    )

    vision = CLIPVisionConfig(
        hidden_size=32, intermediate_size=64, num_hidden_layers=3,
        num_attention_heads=2, image_size=32, patch_size=8)
    text = HFLlamaConfig(
        vocab_size=128, hidden_size=48, intermediate_size=96,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128)
    cfg = LlavaConfig(vision_config=vision, text_config=text,
                      image_token_index=127)
    torch.manual_seed(0)
    return LlavaForConditionalGeneration(cfg).eval(), cfg


@pytest.mark.slow  # tier-1 budget: see scripts/check_tier1_budget.py
def test_vlm_vision_tower_parity_with_hf_llava():
    """Converter + flax tower must reproduce HF LLaVA's get_image_features
    (vision_feature_layer=-2, CLS dropped, 2-layer gelu projector)."""
    torch = pytest.importorskip("torch")
    from scalable_hw_agnostic_inference_tpu.models import vlm

    tm, hf_cfg = _tiny_hf_llava()
    vcfg = vlm.VisionTowerConfig.from_hf(hf_cfg, lm_dim=48)
    assert vcfg.n_patches == 16 and vcfg.feature_layer == -2
    params = vlm.params_from_torch(tm, vcfg)

    px = np.random.default_rng(0).standard_normal((2, 32, 32, 3)).astype(np.float32)
    with torch.no_grad():
        want = tm.get_image_features(
            pixel_values=torch.tensor(px.transpose(0, 3, 1, 2)),
            vision_feature_layer=-2,
            vision_feature_select_strategy="default")
        if isinstance(want, (tuple, list)):
            want = torch.cat(list(want), dim=0)
        want = want.numpy()
    got = np.asarray(vlm.VisionProjector(vcfg).apply(params, jnp.asarray(px)))
    # newer transformers returns features flattened over the batch
    np.testing.assert_allclose(got, want.reshape(got.shape),
                               rtol=3e-4, atol=3e-4)


@pytest.mark.slow  # tier-1 budget: see scripts/check_tier1_budget.py
def test_vlm_language_model_conversion_roundtrip():
    """The llava-wrapped language model converts through the same llama
    mapping the text units use (prefix-stripped state dict)."""
    torch = pytest.importorskip("torch")
    from scalable_hw_agnostic_inference_tpu.models import llama

    tm, hf_cfg = _tiny_hf_llava()
    sd = tm.state_dict()
    if any(k.startswith("language_model.") for k in sd):
        lm_sd = {k[len("language_model."):]: v for k, v in sd.items()
                 if k.startswith("language_model.")}
    else:
        lm_sd = {k[len("model.language_model."):]: v for k, v in sd.items()
                 if k.startswith("model.language_model.")}
        lm_sd.update({k: v for k, v in sd.items() if k.startswith("lm_head.")})
    mcfg = llama.LlamaConfig.from_hf(hf_cfg.text_config)
    params = llama.params_from_torch(lm_sd, mcfg)

    ids = np.random.default_rng(1).integers(0, 100, (1, 12))
    with torch.no_grad():
        want = tm.language_model(torch.tensor(ids))
        want = (tm.lm_head(want.last_hidden_state)
                if hasattr(tm, "lm_head") and not hasattr(want, "logits")
                else want.logits).numpy()
    model = llama.LlamaForCausalLM(mcfg, dtype=jnp.float32)
    got, _ = model.apply(params, jnp.asarray(ids, jnp.int32))
    np.testing.assert_allclose(np.asarray(got), want, rtol=3e-4, atol=3e-4)


@pytest.mark.slow  # tier-1 budget: see scripts/check_tier1_budget.py
@pytest.mark.asyncio
async def test_dead_engine_loop_fails_readiness():
    """A crashed engine loop must drain the pod: /readiness 503, /generate
    503 — not an endless stream of 500s behind a green probe (VERDICT r2 #6)."""
    cfg, service = make_service()
    app = create_app(cfg, service)
    async with make_client(app) as c:
        r = await wait_ready(c, timeout=300.0)
        assert r.status_code == 200, r.text

        # simulate an engine-step crash: the loop stops and refuses work
        service.loop.stop()

        r = await c.get("/readiness")
        assert r.status_code == 503, r.text
        assert "engine loop" in r.json()["error"]
        r = await c.post("/generate", json={"prompt": "hi",
                                            "max_new_tokens": 4})
        assert r.status_code == 503


def test_geometry_serving_tier_registry():
    """`MODEL_ID=llama-1b-geometry` boots the full-size architecture with
    zero weights and no hub access (serve/units/causal_lm.py) so on-chip
    serving-level ramps (scripts/breaking_point.py --spawn vllm --full) can
    measure the real engine stack without a network path to checkpoints."""
    from scalable_hw_agnostic_inference_tpu.serve.units.causal_lm import (
        _geometry_models,
    )

    g = _geometry_models()
    assert set(g) >= {"llama-1b-geometry", "llama-3b-geometry",
                      "llama-8b-geometry", "mistral-7b-geometry"}
    cfg = g["llama-1b-geometry"]()
    assert (cfg.dim, cfg.n_layers, cfg.vocab_size) == (2048, 16, 128256)
    assert g["mistral-7b-geometry"]().vocab_size == 32768


# -- the token stream alone: a stub engine loop behind `_openai_stream` --------
#
# No model and no engine: the test puts the tokens itself, so it decides how
# many a stream's queue holds when its turn comes.

class _StubLoop:
    """What `_openai_stream` asks of an EngineLoop; `on_token` is the
    stream's own `StreamTrack.put`."""

    def __init__(self, tele):
        from concurrent.futures import Future

        self.tele, self.fut, self.cancelled = tele, Future(), []
        self.on_token = None

    def submit(self, ids, params, on_token=None, **kw):
        self.on_token = on_token
        return self.fut

    def cancel(self, fut):
        self.cancelled.append(fut)

    def put(self, toks, t_commit=None):
        """A step's commit: the tokens, then the one wake-up."""
        import time as _time

        self.tele.phase_t0 = t_commit or _time.monotonic()
        for tok in toks:
            self.on_token(tok)
        self.tele.stream_flush()

    def resolve(self, stop_reason="length"):
        from types import SimpleNamespace

        self.fut.set_result(SimpleNamespace(
            stop_reason=stop_reason, timing=None, req_id=0))
        self.tele.stream_flush()


def _stub_stream(body=None, kind="completion"):
    """(StreamingResponse, stub loop, telemetry) of one streamed request."""
    from types import SimpleNamespace

    from scalable_hw_agnostic_inference_tpu.obs.steploop import StepTelemetry

    _, service = make_service()
    tele = StepTelemetry()
    service._engine = SimpleNamespace(obs=tele)
    service.loop = _StubLoop(tele)
    service._encode = lambda text, add_special=True: [1, 2, 3]
    service._decode = lambda ids: "".join(chr(97 + int(i) % 26) for i in ids)
    service._sampling_from = lambda payload: None
    resp = service._openai_stream("hello", dict(body or {}), kind)
    return resp, service.loop, tele


def _event_texts(chunks):
    import json as _json

    return [_json.loads(c[6:])["choices"][0]["text"] for c in chunks
            if c.startswith("data: {")]


async def _pull(resp, it):
    """One chunk as the drain takes it: pulled, written, reported."""
    chunk = await anext(it)
    resp.on_sent(len(chunk))
    return chunk


async def _rest(resp, it):
    out = []
    try:
        while True:
            out.append(await _pull(resp, it))
    except StopAsyncIteration:
        return out


@pytest.mark.asyncio
@pytest.mark.parametrize("k", [1, 2, 7, 40])
async def test_what_the_queue_holds_leaves_as_one_event(k):
    """k tokens queued before the stream's turn are ONE event whose text is
    theirs in order; the events' sum is the unbatched stream's."""
    resp, loop, tele = _stub_stream()
    toks = list(range(3, 3 + k))
    loop.put(toks)
    it = resp.iterator.__aiter__()
    first = await _pull(resp, it)
    want = "".join(chr(97 + t % 26) for t in toks)
    assert _event_texts([first]) == [want]
    loop.put([30])          # the stream keeps up: one token, one event
    second = await _pull(resp, it)
    assert _event_texts([second]) == ["e"]
    loop.put([31, 32])
    loop.resolve()
    rest = await _rest(resp, it)
    assert rest[-1] == "data: [DONE]\n\n"
    texts = _event_texts([first, second] + rest)
    assert "".join(texts) == want + "efg"
    assert texts[-1] == ""      # the finish event carries no text
    s = tele.stream_snapshot()
    assert s["tokens_put"] == s["tokens_sent"] == k + 3
    assert s["tokens_dropped"] == s["backlog"] == 0
    assert loop.cancelled == []


@pytest.mark.asyncio
async def test_a_stop_inside_a_batch_ends_the_batch_there():
    """The stop's own text and what follows it in the same batch never
    leave; the engine request is cancelled, and what it puts until the
    cancel lands is dropped."""
    import json as _json

    resp, loop, tele = _stub_stream({"stop": "e"})
    it = resp.iterator.__aiter__()
    loop.put([1, 2, 3, 4, 5, 6, 7])      # b c d e f g h: the stop is inside
    ev = await _pull(resp, it)
    assert _event_texts([ev]) == ["bcd"]
    pending = asyncio.ensure_future(_pull(resp, it))
    await asyncio.sleep(0.01)
    assert loop.cancelled == [loop.fut] and not pending.done()
    loop.put([8, 9])                    # behind the stop, before the cancel
    loop.resolve("cancelled")
    rest = [await pending] + await _rest(resp, it)
    assert _event_texts(rest) == [""]
    assert _json.loads(rest[0][6:])["choices"][0]["finish_reason"] == "stop"
    assert rest[-1] == "data: [DONE]\n\n"
    s = tele.stream_snapshot()
    # sent: b c d and the stop's own token, which went with the stream's end
    assert (s["tokens_put"], s["tokens_sent"], s["tokens_dropped"]) == (
        9, 4, 5)
    assert s["streams_ended"] == 1 and s["backlog"] == 0


@pytest.mark.asyncio
async def test_the_stream_ends_when_its_request_does():
    """The end mark behind the last token ends the stream: no poll's
    timeout stands between the future's resolution and ``[DONE]`` (the
    best of three: a loaded test machine may hold any one of them up)."""
    import threading
    import time as _time

    lags = []
    for _ in range(3):
        resp, loop, tele = _stub_stream()
        it = resp.iterator.__aiter__()
        loop.put([1])
        await _pull(resp, it)
        pending = asyncio.ensure_future(_rest(resp, it))
        await asyncio.sleep(0.05)           # the stream waits, idle
        assert not pending.done()
        t = {}

        def engine_thread():
            t["resolved"] = _time.monotonic()
            loop.resolve()

        threading.Thread(target=engine_thread).start()
        rest = await asyncio.wait_for(pending, timeout=5.0)
        lags.append(_time.monotonic() - t["resolved"])
        assert rest[-1] == "data: [DONE]\n\n"
    assert min(lags) < 0.05, f"the streams ended {lags} s behind their requests"


@pytest.mark.asyncio
@pytest.mark.parametrize("reason,key", [("rejected", "error"),
                                        ("timeout", "error")])
async def test_an_in_band_record_still_closes_the_stream(reason, key):
    import json as _json

    resp, loop, tele = _stub_stream()
    it = resp.iterator.__aiter__()
    loop.put([1, 2])
    await _pull(resp, it)
    loop.resolve(reason)
    rest = await _rest(resp, it)
    assert key in _json.loads(rest[0][6:]) and rest[-1] == "data: [DONE]\n\n"
    s = tele.stream_snapshot()
    assert s["tokens_put"] == s["tokens_sent"] == 2 and s["streams_ended"] == 1


@pytest.mark.asyncio
async def test_a_client_that_leaves_an_idle_stream_cancels_its_request():
    """The stream waits for its first token (a queued request): the
    disconnect closes it at once, with no pull in flight to wait out, and
    its ``finally`` cancels the engine request."""
    import threading
    import time as _time

    from scalable_hw_agnostic_inference_tpu.serve import asgi

    resp, loop, tele = _stub_stream()
    app = asgi.App("t")

    @app.get("/s")
    def s(request):
        return resp

    gone = asyncio.Event()
    before = {t.ident for t in threading.enumerate()}
    call = asyncio.ensure_future(drive_asgi(app, "/s", disconnect=gone,
                                            method="GET"))
    await asyncio.sleep(0.05)
    assert not call.done() and loop.cancelled == []
    # nothing was pulled on a thread: the stream waits on the event loop
    assert {t.ident for t in threading.enumerate()} == before
    t0 = _time.monotonic()
    gone.set()
    status, chunks = await asyncio.wait_for(call, timeout=5.0)
    # at once: no pull to wait out (it could take a second, the quiet turn)
    assert _time.monotonic() - t0 < 0.2
    assert loop.cancelled == [loop.fut]
    assert status == 200 and chunks == []
    loop.resolve("cancelled")
    s_ = tele.stream_snapshot()
    assert (s_["streams_started"], s_["streams_aborted"]) == (1, 1)
    assert s_["draining"] == s_["backlog"] == 0
