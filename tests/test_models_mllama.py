"""Mllama (Llama-3.2-Vision) parity: vision tower vs HF, gated cross-attention
text path vs HF, and the engine serving it end-to-end.

Reference capability: ``app/vllm_model_api_m.py`` serving
Llama-3.2-11B-Vision through the vLLM fork (VERDICT r2 missing #4 — the
actual mllama layout, not a LLaVA stand-in).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")

from scalable_hw_agnostic_inference_tpu.models import llama, mllama


def hf_tiny_config():
    from transformers import MllamaConfig
    from transformers.models.mllama.configuration_mllama import (
        MllamaTextConfig,
        MllamaVisionConfig,
    )

    vision = MllamaVisionConfig(
        hidden_size=32, image_size=32, patch_size=8, num_hidden_layers=3,
        num_global_layers=2, attention_heads=2, intermediate_size=64,
        max_num_tiles=2, intermediate_layers_indices=[1],
        supported_aspect_ratios=[[1, 1], [1, 2], [2, 1]],
        vision_output_dim=64)
    text = MllamaTextConfig(
        vocab_size=128, hidden_size=64, num_hidden_layers=4,
        num_attention_heads=4, num_key_value_heads=2, intermediate_size=128,
        cross_attention_layers=[1, 3], max_position_embeddings=128,
        rope_theta=10000.0, rope_scaling={"rope_type": "default"},
        tie_word_embeddings=False,
        pad_token_id=0, bos_token_id=1, eos_token_id=2)
    return MllamaConfig(vision_config=vision, text_config=text)


@pytest.fixture(scope="module")
def hf_model():
    from transformers import MllamaForConditionalGeneration

    torch.manual_seed(0)
    model = MllamaForConditionalGeneration(hf_tiny_config()).eval()
    # fresh checkpoints init the cross-attention tanh gates at 0 (the layers
    # contribute nothing until trained) — open them so the tests can SEE the
    # cross path; both HF and our side consume the same state dict
    with torch.no_grad():
        for name, p in model.named_parameters():
            if "cross_attn_attn_gate" in name or "cross_attn_mlp_gate" in name:
                p.fill_(1.0)
    return model


def _lm_state_dict(sd):
    if any(k.startswith("language_model.") for k in sd):
        out = {k[len("language_model."):]: v for k, v in sd.items()
               if k.startswith("language_model.")}
    else:
        out = {k[len("model.language_model."):]: v for k, v in sd.items()
               if k.startswith("model.language_model.")}
        out.update({k: v for k, v in sd.items() if k.startswith("lm_head.")})
    return out


@pytest.mark.slow  # tier-1 budget: see scripts/check_tier1_budget.py
def test_vision_model_matches_hf(hf_model):
    """Tiled two-stage vision encoder + projector: exact HF numerics,
    including a masked padding tile."""
    hf_cfg = hf_model.config
    vcfg = mllama.MllamaVisionConfig.from_hf(hf_cfg.vision_config)
    assert vcfg.output_dim == hf_cfg.vision_config.vision_output_dim

    rng = np.random.default_rng(0)
    T = vcfg.max_num_tiles
    px = rng.standard_normal((1, T, vcfg.image_size, vcfg.image_size, 3)
                             ).astype(np.float32)
    ar_ids = np.array([2], np.int32)        # aspect ratio [1, 2]: 2 tiles
    ar_mask = np.array([[1, 1]], np.int32)

    with torch.no_grad():
        want = hf_model.model.vision_model(
            pixel_values=torch.tensor(px).permute(0, 1, 4, 2, 3)[:, None],
            aspect_ratio_ids=torch.tensor(ar_ids)[:, None],
            aspect_ratio_mask=torch.tensor(ar_mask)[:, None],
        ).last_hidden_state  # [1, 1, T, P1, out]
        want_states = hf_model.model.multi_modal_projector(want).reshape(
            1, -1, hf_cfg.text_config.hidden_size).numpy()

    vparams, pparams = mllama.vision_params_from_torch(
        hf_model, vcfg, hf_cfg.text_config.hidden_size)
    vm = mllama.MllamaVisionModel(vcfg)
    feats = vm.apply(vparams, jnp.asarray(px), jnp.asarray(ar_ids),
                     jnp.asarray(ar_mask))
    np.testing.assert_allclose(
        np.asarray(feats)[:, None], want.numpy(), rtol=2e-4, atol=2e-4)
    proj = mllama.MllamaProjector(vcfg, hf_cfg.text_config.hidden_size)
    states = proj.apply(pparams, feats)
    np.testing.assert_allclose(np.asarray(states), want_states,
                               rtol=2e-4, atol=2e-4)

    # a masked second tile changes nothing upstream of it but must change
    # the global-stage output (mask is live)
    feats_masked = vm.apply(vparams, jnp.asarray(px), jnp.asarray(ar_ids),
                            jnp.asarray(np.array([[1, 0]], np.int32)))
    assert np.abs(np.asarray(feats_masked) - np.asarray(feats)).max() > 1e-6


@pytest.mark.slow  # tier-1 budget: see scripts/check_tier1_budget.py
def test_cross_attention_prefill_logits_match_hf(hf_model):
    """Gated cross-attention text path: our paged-engine prefill's
    last-position logits equal HF's full forward given the same vision
    states (the load-bearing mllama numeric check)."""
    from scalable_hw_agnostic_inference_tpu.engine.cache import PagedKVCache
    from scalable_hw_agnostic_inference_tpu.engine.runner import (
        make_cross_kv,
        make_prefill,
    )

    hf_cfg = hf_model.config
    mcfg = llama.LlamaConfig.from_hf(hf_cfg.text_config)
    assert mcfg.cross_attention_layers == (1, 3)
    params = llama.params_from_torch(_lm_state_dict(hf_model.state_dict()),
                                     mcfg)
    Lv = 34  # 2 tiles x (16 patches + 1 cls)
    rng = np.random.default_rng(1)
    states = rng.standard_normal((Lv, mcfg.dim)).astype(np.float32)
    prompt = [5, 17, 42, 99, 7, 3]

    with torch.no_grad():
        out = hf_model(
            input_ids=torch.tensor([prompt]),
            cross_attention_states=torch.tensor(states)[None],
            cross_attention_mask=torch.ones((1, len(prompt), 1, 2),
                                            dtype=torch.long),
        )
        want = out.logits[0, -1].numpy()

    block_size, M = 8, 4
    cache = PagedKVCache(mcfg.n_layers, llama.cache_leaves(mcfg),
                         total_blocks=8, block_size=block_size,
                         blocks_per_seq=M, dtype=jnp.float32)
    cross = make_cross_kv(mcfg)(params, jnp.asarray(states))
    cross1 = [{"k": c["k"][None], "v": c["v"][None]} for c in cross]
    fn = make_prefill(mcfg, block_size, M, bucket=8)
    ids = np.zeros((1, 8), np.int32)
    ids[0, :len(prompt)] = prompt
    table = jnp.asarray([[1, 2, 0, 0]], jnp.int32)
    _, logits = fn(params, cache.kv, jnp.asarray(ids),
                   jnp.asarray([len(prompt)], jnp.int32), table,
                   cross1, jnp.ones((1,), jnp.float32),
                   jnp.full((1,), Lv, jnp.int32))
    # bf16 activations inside the engine path vs HF fp32: loose-ish bars
    np.testing.assert_allclose(np.asarray(logits)[0], want, rtol=0.1,
                               atol=0.1)
    assert int(np.argmax(np.asarray(logits)[0])) == int(np.argmax(want))


@pytest.mark.slow  # tier-1 budget: see scripts/check_tier1_budget.py
def test_engine_serves_mllama_with_cross_states(hf_model):
    """End-to-end through LLMEngine: image conditions output, identical
    states reproduce it, text-only requests work and differ."""
    from scalable_hw_agnostic_inference_tpu.engine import EngineConfig
    from scalable_hw_agnostic_inference_tpu.engine.engine import (
        LLMEngine,
        SamplingParams,
    )

    hf_cfg = hf_model.config
    mcfg = llama.LlamaConfig.from_hf(hf_cfg.text_config)
    params = llama.params_from_torch(_lm_state_dict(hf_model.state_dict()),
                                     mcfg)
    Lv = 34
    ecfg = EngineConfig(max_model_len=64, max_num_seqs=2, block_size=8,
                        context_encoding_buckets=(16,), max_new_tokens=8)
    rng = np.random.default_rng(2)
    img_a = rng.standard_normal((Lv, mcfg.dim)).astype(np.float32)
    img_b = rng.standard_normal((Lv, mcfg.dim)).astype(np.float32)
    prompt = [5, 17, 42]
    sp = SamplingParams(temperature=0.0, max_new_tokens=6)

    def run(states):
        eng = LLMEngine(mcfg, params, ecfg, cross_seq_len=Lv)
        rid = eng.add_request(prompt, sp, cross_states=states)
        done = {}
        while eng.has_work:
            for f in eng.step():
                done[f.req_id] = f
        return done[rid].token_ids

    plain = run(None)
    with_a = run(img_a)
    with_a2 = run(img_a)
    with_b = run(img_b)
    assert len(plain) == 6 and len(with_a) == 6
    assert with_a == with_a2
    assert with_a != plain
    assert with_a != with_b

    # closed executable set includes the cross signature
    eng = LLMEngine(mcfg, params, ecfg, cross_seq_len=Lv)
    n = eng.warm_executables()
    count = eng.n_executables
    eng.add_request(prompt, sp, cross_states=img_a)
    eng.add_request([9, 9], sp)     # text-only through the same engine
    done = 0
    while eng.has_work:
        done += len(eng.step())
    assert done == 2
    assert eng.n_executables == count


@pytest.mark.slow  # tier-1 budget: see scripts/check_tier1_budget.py
@pytest.mark.asyncio
async def test_vllm_service_serves_mllama_checkpoint(hf_model, tmp_path):
    """The serving unit loads an actual mllama-layout checkpoint from disk
    and conditions generation on the image through the cross-attention path
    (reference vllm_model_api_m.py semantics)."""
    import asyncio  # noqa: F401
    import base64
    import io

    from PIL import Image
    from tokenizers import Tokenizer
    from tokenizers.models import WordLevel
    from tokenizers.pre_tokenizers import Whitespace
    from transformers import PreTrainedTokenizerFast

    from scalable_hw_agnostic_inference_tpu.models.registry import get_model
    from scalable_hw_agnostic_inference_tpu.serve.app import create_app
    from scalable_hw_agnostic_inference_tpu.utils.env import ServeConfig

    from test_serve_http import make_client, wait_ready

    ckpt = tmp_path / "mllama-tiny"
    hf_model.save_pretrained(ckpt)
    vocab = {f"tok{i}": i for i in range(125)}
    vocab.update({"<pad>": 125, "<s>": 126, "</s>": 127})
    tok = Tokenizer(WordLevel(vocab, unk_token="tok0"))
    tok.pre_tokenizer = Whitespace()
    PreTrainedTokenizerFast(
        tokenizer_object=tok, pad_token="<pad>", bos_token="<s>",
        eos_token="</s>").save_pretrained(ckpt)

    cfg = ServeConfig(app="mllama", model_id=str(ckpt), device="cpu",
                      max_seq_len=32, max_new_tokens=8,
                      artifact_root=str(tmp_path / "artifacts"),
                      vllm_config="/nonexistent.yaml")
    service = get_model("vllm")(cfg)
    app = create_app(cfg, service)
    async with make_client(app) as c:
        r = await wait_ready(c, timeout=600.0)
        assert r.status_code == 200, r.text
        assert service._mllama is not None

        buf = io.BytesIO()
        Image.new("RGB", (48, 48), (200, 30, 30)).save(buf, format="PNG")
        img = base64.b64encode(buf.getvalue()).decode()
        base = {"prompt": "tok5 tok9 tok11", "temperature": 0.0,
                "max_new_tokens": 5}
        r_plain = await c.post("/generate", json=base)
        r_img = await c.post("/generate", json={**base, "image_b64": img})
        assert r_plain.status_code == 200, r_plain.text
        assert r_img.status_code == 200, r_img.text
        assert r_img.json()["n_tokens"] == 5
        # the image conditions the output through the cross layers
        assert (r_img.json()["generated_text"]
                != r_plain.json()["generated_text"])
        # deterministic: same image, same output
        r_img2 = await c.post("/generate", json={**base, "image_b64": img})
        assert (r_img2.json()["generated_text"]
                == r_img.json()["generated_text"])


@pytest.mark.slow  # tier-1 budget: see scripts/check_tier1_budget.py
def test_tiled_preprocessing_matches_hf_processor(hf_model):
    """Our tiling (canvas pick, fit-resize, normalize, pad, split) matches
    the HF MllamaImageProcessor output for a non-square image."""
    from PIL import Image
    from transformers.models.mllama.image_processing_mllama import (
        MllamaImageProcessor,
    )

    vcfg = mllama.MllamaVisionConfig.from_hf(hf_model.config.vision_config)
    supported = hf_model.config.vision_config.supported_aspect_ratios
    proc = MllamaImageProcessor(
        size={"height": vcfg.image_size, "width": vcfg.image_size},
        max_image_tiles=vcfg.max_num_tiles)
    rng = np.random.default_rng(0)
    img = Image.fromarray(
        rng.integers(0, 255, (40, 70, 3), np.uint8), "RGB")  # wide: 1x2 grid

    want = proc(images=img, return_tensors="np")
    tiles, ar_id, n_tiles = mllama.preprocess_tiled(
        img, vcfg, supported, mean=tuple(proc.image_mean),
        std=tuple(proc.image_std))
    assert ar_id == int(want["aspect_ratio_ids"][0, 0])
    assert n_tiles == int(want["aspect_ratio_mask"][0, 0].sum())
    got = tiles.transpose(0, 3, 1, 2)  # NHWC -> NCHW for comparison
    np.testing.assert_allclose(got, want["pixel_values"][0, 0], rtol=2e-5,
                               atol=2e-5)


@pytest.mark.slow  # tier-1 budget: see scripts/check_tier1_budget.py
def test_engine_cross_len_masks_padding_states(hf_model):
    """A request whose image fills only part of the static Lv buffer must
    ignore the padding rows: output equals a run where padding rows carry
    garbage."""
    from scalable_hw_agnostic_inference_tpu.engine import EngineConfig
    from scalable_hw_agnostic_inference_tpu.engine.engine import (
        LLMEngine,
        SamplingParams,
    )

    hf_cfg = hf_model.config
    mcfg = llama.LlamaConfig.from_hf(hf_cfg.text_config)
    params = llama.params_from_torch(_lm_state_dict(hf_model.state_dict()),
                                     mcfg)
    Lv, valid = 34, 17  # one of two tiles valid
    ecfg = EngineConfig(max_model_len=64, max_num_seqs=2, block_size=8,
                        context_encoding_buckets=(16,), max_new_tokens=8)
    rng = np.random.default_rng(3)
    base = rng.standard_normal((Lv, mcfg.dim)).astype(np.float32)
    garbage = base.copy()
    garbage[valid:] = 1e3 * rng.standard_normal((Lv - valid, mcfg.dim))
    prompt = [5, 17, 42]
    sp = SamplingParams(temperature=0.0, max_new_tokens=6)

    def run(states, n):
        eng = LLMEngine(mcfg, params, ecfg, cross_seq_len=Lv)
        rid = eng.add_request(prompt, sp, cross_states=states, cross_len=n)
        done = {}
        while eng.has_work:
            for f in eng.step():
                done[f.req_id] = f
        return done[rid].token_ids

    assert run(base, valid) == run(garbage, valid)


@pytest.mark.slow  # tier-1 budget: see scripts/check_tier1_budget.py
def test_engine_cross_chunked_prefill_parity(hf_model):
    """A vision-conditioned prompt longer than the largest bucket encodes
    through the continuation ladder (cross layers attending the slot's
    states every chunk) and matches a run whose bucket fits the whole
    prompt in one prefill call."""
    from scalable_hw_agnostic_inference_tpu.engine import EngineConfig
    from scalable_hw_agnostic_inference_tpu.engine.engine import (
        LLMEngine,
        SamplingParams,
    )

    hf_cfg = hf_model.config
    mcfg = llama.LlamaConfig.from_hf(hf_cfg.text_config)
    params = llama.params_from_torch(_lm_state_dict(hf_model.state_dict()),
                                     mcfg)
    Lv = 34
    rng = np.random.default_rng(7)
    states = rng.standard_normal((Lv, mcfg.dim)).astype(np.float32)
    prompt = [int(x) for x in rng.integers(2, mcfg.vocab_size, 40)]
    sp = SamplingParams(temperature=0.0, max_new_tokens=6)

    def run(buckets):
        ecfg = EngineConfig(max_model_len=128, max_num_seqs=2, block_size=8,
                            context_encoding_buckets=buckets,
                            max_new_tokens=8)
        eng = LLMEngine(mcfg, params, ecfg, cross_seq_len=Lv)
        rid = eng.add_request(list(prompt), sp, cross_states=states,
                              cross_len=Lv)
        done = {}
        while eng.has_work:
            for f in eng.step():
                done[f.req_id] = f
        return done[rid]

    chunked = run((16,))        # 40-token prompt => 16 + 16 + 8 chunks
    whole = run((16, 64))       # fits one 64 prefill
    assert chunked.n_prompt == len(prompt)
    assert chunked.token_ids == whole.token_ids, (
        f"cross chunked {chunked.token_ids} != whole {whole.token_ids}")


@pytest.mark.slow  # tier-1 budget: see scripts/check_tier1_budget.py
@pytest.mark.asyncio
async def test_mllama_artifact_boot_skips_torch(hf_model, tmp_path,
                                                monkeypatch):
    """Second boot from the same artifact root restores the converted trees
    (orbax) without touching the HF torch model — the compile-Job →
    serving-pod artifact flow for the multimodal unit."""
    from tokenizers import Tokenizer
    from tokenizers.models import WordLevel
    from tokenizers.pre_tokenizers import Whitespace
    from transformers import PreTrainedTokenizerFast

    import transformers

    from scalable_hw_agnostic_inference_tpu.core import weights as wstore
    from scalable_hw_agnostic_inference_tpu.models.registry import get_model
    from scalable_hw_agnostic_inference_tpu.utils.env import ServeConfig

    ckpt = tmp_path / "mllama-tiny"
    hf_model.save_pretrained(ckpt)
    vocab = {f"tok{i}": i for i in range(125)}
    vocab.update({"<pad>": 125, "<s>": 126, "</s>": 127})
    tok = Tokenizer(WordLevel(vocab, unk_token="tok0"))
    tok.pre_tokenizer = Whitespace()
    PreTrainedTokenizerFast(
        tokenizer_object=tok, pad_token="<pad>", bos_token="<s>",
        eos_token="</s>").save_pretrained(ckpt)

    def make(app):
        cfg = ServeConfig(app=app, model_id=str(ckpt), device="cpu",
                          max_seq_len=32, max_new_tokens=8,
                          artifact_root=str(tmp_path / "artifacts"),
                          vllm_config="/nonexistent.yaml")
        return get_model("vllm")(cfg)

    svc = make("m1")
    svc.load()
    key = f"mllama--{ckpt}"
    assert wstore.has_params(str(tmp_path / "artifacts"), key)
    want = svc.infer({"prompt": "tok5 tok9", "temperature": 0.0,
                      "max_new_tokens": 4})
    svc.loop.stop()

    # second boot: the torch model class must never be constructed, and the
    # tokenizer must restore from the artifact-local copy, not the
    # checkpoint/hub (the hub-less serving pod with only the artifacts PVC)
    import os as _os

    def boom(*a, **k):
        raise AssertionError("artifact boot must not load the torch model")

    monkeypatch.setattr(transformers.AutoModelForImageTextToText,
                        "from_pretrained", boom)
    tok_dir = wstore.aux_dir(str(tmp_path / "artifacts"), key, "tokenizer")
    assert _os.path.isdir(tok_dir), "first boot must persist tokenizer files"
    real_tok = transformers.AutoTokenizer.from_pretrained.__func__

    def guarded(pretrained, *a, **k):
        assert str(pretrained) != str(ckpt), \
            "hub-less boot must not fetch the checkpoint tokenizer"
        return real_tok(transformers.AutoTokenizer, pretrained, *a, **k)

    monkeypatch.setattr(transformers.AutoTokenizer, "from_pretrained", guarded)
    svc2 = make("m2")
    svc2.load()
    got = svc2.infer({"prompt": "tok5 tok9", "temperature": 0.0,
                      "max_new_tokens": 4})
    assert got["generated_text"] == want["generated_text"]
    svc2.loop.stop()
