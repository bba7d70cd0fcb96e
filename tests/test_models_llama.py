"""Llama-family tests: forward, KV-cache consistency, generate, TP, HF parity."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from scalable_hw_agnostic_inference_tpu.core.mesh import build_mesh
from scalable_hw_agnostic_inference_tpu.models import llama
from scalable_hw_agnostic_inference_tpu.models.generate import (
    ByteTokenizer,
    make_generate,
)
from scalable_hw_agnostic_inference_tpu.parallel.sharding import shard_pytree
from jax.sharding import PartitionSpec as P


@pytest.fixture(scope="module")
def tiny():
    cfg = llama.LlamaConfig.tiny()
    model = llama.LlamaForCausalLM(cfg, dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    return cfg, model, params


def test_forward_shapes(tiny):
    cfg, model, params = tiny
    ids = jnp.arange(12, dtype=jnp.int32).reshape(2, 6) % cfg.vocab_size
    logits, cache = model.apply(params, ids)
    assert logits.shape == (2, 6, cfg.vocab_size)
    assert logits.dtype == jnp.float32
    assert cache is None


def test_causality(tiny):
    """Changing a future token must not change past logits."""
    cfg, model, params = tiny
    ids = jnp.array([[5, 6, 7, 8, 9, 10]], jnp.int32)
    logits1, _ = model.apply(params, ids)
    ids2 = ids.at[0, 4].set(99)
    logits2, _ = model.apply(params, ids2)
    np.testing.assert_allclose(logits1[0, :4], logits2[0, :4], atol=1e-5)
    assert not np.allclose(logits1[0, 4], logits2[0, 4])


def test_cache_matches_full_forward(tiny):
    """prefill + single-token decode == full causal forward, token by token."""
    cfg, model, params = tiny
    B, T, S = 1, 6, 12
    ids = (jnp.arange(B * T, dtype=jnp.int32).reshape(B, T) * 7 + 3) % cfg.vocab_size
    full_logits, _ = model.apply(params, ids)

    # prefill the first 3 tokens
    Tp = 3
    cache = llama.init_cache(cfg, B, S, dtype=jnp.float32)
    tv = jnp.ones((B, Tp), bool)
    pos = jnp.broadcast_to(jnp.arange(Tp, dtype=jnp.int32), (B, Tp))
    logits_p, cache = model.apply(
        params, ids[:, :Tp], pos, cache, llama.prefill_mask(tv, S), jnp.int32(0)
    )
    np.testing.assert_allclose(logits_p, full_logits[:, :Tp], atol=1e-4)

    # decode tokens 3..5 one at a time
    slot_valid = jnp.zeros((B, S), bool).at[:, :Tp].set(True)
    for t in range(Tp, T):
        slot_valid = slot_valid.at[:, t].set(True)
        pos = jnp.full((B, 1), t, jnp.int32)
        step_logits, cache = model.apply(
            params, ids[:, t : t + 1], pos, cache,
            llama.decode_mask(slot_valid), jnp.int32(t),
        )
        np.testing.assert_allclose(
            step_logits[:, 0], full_logits[:, t], atol=1e-4
        )


def test_generate_greedy_deterministic(tiny):
    cfg, model, params = tiny
    gen = make_generate(model, cfg, prompt_bucket=8, max_new_tokens=6,
                        eos_id=2, pad_id=0, cache_dtype=jnp.float32)
    ids = np.zeros((1, 8), np.int32)
    ids[0, :4] = [1, 10, 11, 12]
    n = np.array([4], np.int32)
    r1 = gen(params, jnp.asarray(ids), jnp.asarray(n), jax.random.PRNGKey(0), 0.0, 0, 1.0)
    r2 = gen(params, jnp.asarray(ids), jnp.asarray(n), jax.random.PRNGKey(7), 0.0, 0, 1.0)
    # greedy: rng must not matter
    np.testing.assert_array_equal(np.asarray(r1.tokens), np.asarray(r2.tokens))
    assert r1.tokens.shape == (1, 6)
    assert 0 < int(r1.n_generated[0]) <= 6


def test_generate_matches_stepwise_argmax(tiny):
    """Greedy generate must equal manual argmax rollout through full forwards."""
    cfg, model, params = tiny
    prompt = [1, 42, 99, 7]
    N = 4
    gen = make_generate(model, cfg, prompt_bucket=4, max_new_tokens=N,
                        eos_id=2, pad_id=0, cache_dtype=jnp.float32)
    ids = np.array([prompt], np.int32)
    res = gen(params, jnp.asarray(ids), jnp.asarray([4], np.int32),
              jax.random.PRNGKey(0), 0.0, 0, 1.0)

    seq = list(prompt)
    expect = []
    for _ in range(N):
        logits, _ = model.apply(params, jnp.asarray([seq], jnp.int32))
        nxt = int(jnp.argmax(logits[0, -1]))
        expect.append(nxt)
        if nxt == 2:
            break
        seq.append(nxt)
    got = [int(t) for t in np.asarray(res.tokens)[0] if int(t) != 0]
    assert got[: len(expect)] == expect


def test_generate_per_row_lengths(tiny):
    """Rows with different prompt lengths decode independently and correctly."""
    cfg, model, params = tiny
    gen = make_generate(model, cfg, prompt_bucket=8, max_new_tokens=3,
                        eos_id=2, pad_id=0, cache_dtype=jnp.float32)
    ids = np.zeros((2, 8), np.int32)
    ids[0, :3] = [1, 5, 6]
    ids[1, :6] = [1, 20, 21, 22, 23, 24]
    n = np.array([3, 6], np.int32)
    res = gen(params, jnp.asarray(ids), jnp.asarray(n), jax.random.PRNGKey(0), 0.0, 0, 1.0)

    # row 0 must match a batch-1 run with the same prompt
    ids0 = np.zeros((1, 8), np.int32)
    ids0[0, :3] = [1, 5, 6]
    res0 = gen(params, jnp.asarray(ids0), jnp.asarray([3], np.int32),
               jax.random.PRNGKey(0), 0.0, 0, 1.0)
    np.testing.assert_array_equal(np.asarray(res.tokens)[0], np.asarray(res0.tokens)[0])


def test_tp_sharded_forward_matches(tiny, devices):
    """TP=4 sharded forward must equal the single-device forward."""
    cfg, model, params = tiny
    mesh = build_mesh("tp=4")
    sharded = shard_pytree(params, mesh, llama.tp_rules())
    ids = jnp.array([[1, 5, 9, 13]], jnp.int32)
    ref, _ = model.apply(params, ids)
    got, _ = jax.jit(lambda p, i: model.apply(p, i))(sharded, ids)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-4)


def test_tp_rules_specs(tiny):
    cfg, _, params = tiny
    rules = llama.tp_rules()
    specs = rules.tree_specs(params)
    p = specs["params"]["layer_0"]
    assert p["attn"]["q"]["kernel"] == P(None, "tp")
    assert p["attn"]["o"]["kernel"] == P("tp", None)
    assert p["mlp"]["gate"]["kernel"] == P(None, "tp")
    assert p["mlp"]["down"]["kernel"] == P("tp", None)
    assert p["attn_norm"]["scale"] == P()


def test_byte_tokenizer_roundtrip():
    tok = ByteTokenizer()
    ids, n = tok.encode("héllo wörld", 64)
    assert ids[0] == tok.bos_id and n < 64
    assert tok.decode(ids[:n]) == "héllo wörld"


@pytest.mark.slow  # tier-1 budget: see scripts/check_tier1_budget.py
def test_hf_parity_tiny_llama():
    """Our flax forward must match torch HF LlamaForCausalLM on random tiny
    weights (GQA + RoPE + SwiGLU + RMSNorm all covered)."""
    torch = pytest.importorskip("torch")
    from transformers import LlamaConfig as HFConfig
    from transformers import LlamaForCausalLM as HFModel

    hf_cfg = HFConfig(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, rms_norm_eps=1e-5, rope_theta=10000.0,
        tie_word_embeddings=False, attention_bias=False,
    )
    torch.manual_seed(0)
    tm = HFModel(hf_cfg).eval()

    cfg = llama.LlamaConfig.from_hf(hf_cfg)
    model = llama.LlamaForCausalLM(cfg, dtype=jnp.float32)
    params = llama.params_from_torch(tm, cfg)

    ids = np.array([[3, 17, 9, 101, 55, 4]], np.int64)
    with torch.no_grad():
        ref = tm(torch.from_numpy(ids)).logits.numpy()
    got, _ = model.apply(params, jnp.asarray(ids.astype(np.int32)))
    np.testing.assert_allclose(np.asarray(got), ref, atol=2e-4, rtol=1e-3)


@pytest.mark.asyncio
async def test_llama_service_end_to_end():
    import httpx

    from scalable_hw_agnostic_inference_tpu.serve.app import create_app
    from scalable_hw_agnostic_inference_tpu.serve.services import LlamaService
    from scalable_hw_agnostic_inference_tpu.utils.env import ServeConfig
    from tests.test_serve_http import wait_ready

    cfg = ServeConfig(app="llama", device="cpu", model_id="tiny",
                      max_seq_len=64, max_new_tokens=4)
    app = create_app(cfg, LlamaService(cfg))
    transport = httpx.ASGITransport(app=app)
    async with httpx.AsyncClient(transport=transport, base_url="http://t") as c:
        r = await wait_ready(c, timeout=60.0)
        assert r.status_code == 200, r.text
        r = await c.post("/generate", json={"prompt": "hello", "temperature": 0.0})
        body = r.json()
        assert "generated_text" in body and body["n_tokens"] >= 1
        r = await c.post("/sentiment", json={"text": "nice"})
        assert "sentiment" in r.json()


@pytest.mark.asyncio
async def test_llama_service_int8_quantized_end_to_end():
    """QUANTIZATION=int8 (the deepseek-tpu unit's fit-enabler): the service
    rebuilds the model with QuantDense and quantizes the param tree at boot,
    and the quantized service still generates deterministically."""
    import httpx

    from scalable_hw_agnostic_inference_tpu.serve.app import create_app
    from scalable_hw_agnostic_inference_tpu.serve.services import LlamaService
    from scalable_hw_agnostic_inference_tpu.utils.env import ServeConfig
    from tests.test_serve_http import wait_ready

    cfg = ServeConfig(app="deepseek", device="cpu", model_id="tiny",
                      max_seq_len=64, max_new_tokens=4, quantization="int8")
    svc = LlamaService(cfg)
    app = create_app(cfg, svc)
    transport = httpx.ASGITransport(app=app)
    async with httpx.AsyncClient(transport=transport, base_url="http://t") as c:
        r = await wait_ready(c, timeout=60.0)
        assert r.status_code == 200, r.text
        r = await c.post("/generate", json={"prompt": "hello",
                                            "temperature": 0.0})
        assert r.json()["n_tokens"] >= 1
    # the loaded tree really is int8: attention kernels became kernel_q+scale
    leaves = jax.tree_util.tree_leaves_with_path(svc.params)
    assert any("kernel_q" in jax.tree_util.keystr(p) for p, _ in leaves)
    assert svc.model.quant


def test_llama_in_registry():
    from scalable_hw_agnostic_inference_tpu.models import list_models

    models = list_models()
    assert {"llama", "mistral", "deepseek"} <= set(models)


def test_replicate_kv_heads_preserves_numerics():
    """Weight-side GQA widening (tp > n_kv_heads, the 70B TP=32 case): the
    widened model's logits must equal the original's bit-for-bit — each
    query head reads an exact copy of its original group head."""
    import dataclasses

    import numpy as np

    cfg = llama.LlamaConfig.tiny()  # 4 q heads, 2 kv heads
    model = llama.LlamaForCausalLM(cfg, dtype=jnp.float32)
    ids = jnp.asarray([[5, 9, 17, 3, 1, 8]], jnp.int32)
    params = model.init(jax.random.PRNGKey(0), ids)
    ref, _ = model.apply(params, ids)

    tp = 4
    wide_params, wide_cfg = llama.replicate_kv_heads(params, cfg, tp)
    assert wide_cfg.n_kv_heads == tp
    wide_model = llama.LlamaForCausalLM(wide_cfg, dtype=jnp.float32)
    out, _ = wide_model.apply(wide_params, ids)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(out))

    # no-op below the threshold; bad factors fail loudly
    same, same_cfg = llama.replicate_kv_heads(params, cfg, 2)
    assert same is params and same_cfg is cfg
    with pytest.raises(ValueError):
        llama.replicate_kv_heads(params, cfg, 3)


@pytest.mark.slow  # tier-1 budget: see scripts/check_tier1_budget.py
def test_llama70b_tp32_lowering_leg():
    """The dsr70b-mh unit's decode + continuation prefill partition at FULL
    shape on an abstract 32-way mesh (VERDICT r4 next #4) — catches illegal
    engine shardings (incl. non-shard_map'd Mosaic attention) in CI instead
    of on an 8-host boot."""
    import __graft_entry__ as g

    g.dryrun_lower_llama70b_tp32()


def test_geometry_params_mirror_converter_tree():
    """geometry_params (the seeded geometry-tier tree) must stay
    structurally identical to params_from_torch's output — the engine
    consumes both interchangeably, so drift would break geometry benches
    silently. Checked for a cross-attention (mllama) config via a synthetic
    HF state dict."""
    import numpy as np

    import jax

    cfg = llama.LlamaConfig(
        vocab_size=64, dim=16, n_layers=3, n_heads=4, n_kv_heads=2,
        mlp_dim=32, max_seq_len=32, rope_theta=10000.0,
        tie_embeddings=False, cross_attention_layers=(1,))
    D, HD = cfg.dim, cfg.head_dim
    q_out, kv_out = cfg.n_heads * HD, cfg.n_kv_heads * HD

    class T:  # minimal torch-tensor stand-in for convert.t2j
        def __init__(self, a):
            self._a = np.asarray(a, np.float32)

        def detach(self):
            return self

        def cpu(self):
            return self

        def float(self):
            return self

        def numpy(self):
            return self._a

        @property
        def T(self):
            return T(self._a.T)

    sd = {"model.embed_tokens.weight": T(np.zeros((cfg.vocab_size, D))),
          "model.norm.weight": T(np.ones(D)),
          "lm_head.weight": T(np.zeros((cfg.vocab_size, D)))}
    for i in range(cfg.n_layers):
        p = f"model.layers.{i}"
        sd[f"{p}.input_layernorm.weight"] = T(np.ones(D))
        sd[f"{p}.post_attention_layernorm.weight"] = T(np.ones(D))
        for n, o in (("gate_proj", cfg.mlp_dim), ("up_proj", cfg.mlp_dim)):
            sd[f"{p}.mlp.{n}.weight"] = T(np.zeros((o, D)))
        sd[f"{p}.mlp.down_proj.weight"] = T(np.zeros((D, cfg.mlp_dim)))
        attn = "cross_attn" if i in cfg.cross_attention_layers else "self_attn"
        sd[f"{p}.{attn}.q_proj.weight"] = T(np.zeros((q_out, D)))
        sd[f"{p}.{attn}.k_proj.weight"] = T(np.zeros((kv_out, D)))
        sd[f"{p}.{attn}.v_proj.weight"] = T(np.zeros((kv_out, D)))
        sd[f"{p}.{attn}.o_proj.weight"] = T(np.zeros((D, q_out)))
        if attn == "cross_attn":
            sd[f"{p}.cross_attn.q_norm.weight"] = T(np.ones(HD))
            sd[f"{p}.cross_attn.k_norm.weight"] = T(np.ones(HD))
            sd[f"{p}.cross_attn_attn_gate"] = T(np.zeros(1))
            sd[f"{p}.cross_attn_mlp_gate"] = T(np.zeros(1))

    converted = llama.params_from_torch(sd, cfg)
    geometry = llama.geometry_params(cfg)

    def shape_tree(t):
        return jax.tree_util.tree_map(lambda a: tuple(a.shape), t)

    assert shape_tree(converted) == shape_tree(geometry)
    # quantized variant keeps the same structure modulo the QuantDense
    # kernel_q/scale expansion the engine's _proj understands
    q = llama.geometry_params(cfg, quant=True)
    flat = {"/".join(str(getattr(k, "key", k)) for k in path)
            for path, _ in jax.tree_util.tree_flatten_with_path(q)[0]}
    assert any(p.endswith("attn/q/kernel_q") for p in flat)
    assert any(p.endswith("attn/q/scale") for p in flat)
