"""int8 KV-block quantization, and the pool kernel that reads it.

``SHAI_KV_QUANT=int8`` trades exactness for ~2x KV capacity: the contract
is a greedy-token match RATE against the bf16 pool plus exact pool/ledger
accounting (device and host tier) — and byte-exact tier round-trips
(blocks and scales are copied, never re-quantized).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from scalable_hw_agnostic_inference_tpu.engine import EngineConfig
from scalable_hw_agnostic_inference_tpu.engine.engine import (
    LLMEngine,
    SamplingParams,
)
from scalable_hw_agnostic_inference_tpu.models.llama import (
    LlamaConfig,
    LlamaForCausalLM,
)
from scalable_hw_agnostic_inference_tpu.ops.attention import (
    ragged_gather_attention,
)
from scalable_hw_agnostic_inference_tpu.ops.pallas.paged_attention import (
    paged_decode_attention,
)
from scalable_hw_agnostic_inference_tpu.ops.quant import (
    dequantize_kv_blocks,
    quantize_kv_blocks,
    requantize_block_tokens,
)


# ---------------------------------------------------------------------------
# ops: quantize/dequantize numerics
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_kv_block_quantize_roundtrip_bounds(dtype):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(6, 8, 2, 16)), dtype)
    q, s = quantize_kv_blocks(x)
    assert q.dtype == jnp.int8 and q.shape == x.shape
    assert s.dtype == jnp.float32 and s.shape == (6, 2)
    rt = dequantize_kv_blocks(q, s, jnp.float32)
    # symmetric per block x head: error bounded by half a quantization
    # step of each (block, head)'s own scale
    err = np.abs(np.asarray(rt) - np.asarray(x, np.float32))
    bound = 0.5 * np.asarray(s)[:, None, :, None] + 1e-6
    assert (err <= bound).all()


def test_kv_block_quantize_scale_is_per_block_and_head():
    # one outlier in (block 0, head 1) must not move any other scale
    x = np.ones((3, 4, 2, 8), np.float32)
    x[0, 2, 1, 3] = 100.0
    _, s = quantize_kv_blocks(jnp.asarray(x))
    s = np.asarray(s)
    assert s[0, 1] == pytest.approx(100.0 / 127.0)
    assert s[0, 0] == pytest.approx(1.0 / 127.0)
    assert np.allclose(s[1:], 1.0 / 127.0)


def test_kv_block_quantize_zero_block():
    q, s = quantize_kv_blocks(jnp.zeros((2, 4, 2, 8)))
    assert np.asarray(q).sum() == 0
    assert (np.asarray(s) > 0).all()  # epsilon floor, never /0
    assert np.asarray(dequantize_kv_blocks(q, s, jnp.float32)).sum() == 0


def test_requantize_single_token_into_empty_block():
    # a fresh pool block carries scale 0 (zeros init): the first decode
    # write must still land within the int8 error bound
    blk = jnp.zeros((2, 8, 2, 16), jnp.int8)
    sc = jnp.zeros((2, 2), jnp.float32)
    tok = jnp.asarray(np.random.default_rng(1).normal(size=(2, 2, 16)),
                      jnp.float32)
    q, s = requantize_block_tokens(blk, sc, tok, jnp.asarray([0, 5]))
    deq = np.asarray(dequantize_kv_blocks(q, s, jnp.float32))
    got = deq[np.arange(2), np.asarray([0, 5])]
    bound = 0.5 * np.asarray(s)[:, None, :].transpose(0, 2, 1)
    assert (np.abs(got - np.asarray(tok))
            <= bound.transpose(0, 2, 1)[:, :, :] .max() + 1e-6).all()
    # the scale only ever grows (running max): rewriting a smaller token
    # keeps earlier residents within the final scale's half step
    q2, s2 = requantize_block_tokens(q, s, tok * 0.01, jnp.asarray([1, 6]))
    assert (np.asarray(s2) >= np.asarray(s) - 1e-9).all()


# ---------------------------------------------------------------------------
# engine: the block-write seam vs the plain scatter it replaces
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("tp", [1, 2], ids=["one-device", "tp2"])
@pytest.mark.parametrize("quant", [False, True], ids=["float", "int8"])
@pytest.mark.parametrize("heads", [2, 4, 8])
def test_block_write_seam_equals_plain_scatter(heads, quant, tp):
    """``_scatter_blocks`` writes through a flat view of each leaf (and,
    under TP, of each device's own heads): the pool it returns is, bit for
    bit, what ``leaf.at[tbl].set(blocks)`` returns. Two rows; the second
    row's table is the null one, so its blocks all land in block 0."""
    from scalable_hw_agnostic_inference_tpu.engine.runner import (
        EngineShardings,
        _scatter_blocks,
    )
    from scalable_hw_agnostic_inference_tpu.models.llama import (
        geometry_params,
    )

    N, Bs, Dh, m = 12, 8, 16, 3
    rng = np.random.default_rng(heads)
    layer = {n: jnp.asarray(rng.normal(size=(N, Bs, heads, Dh)), jnp.float32)
             for n in ("k", "v")}
    if quant:
        layer["k"], layer["ks"] = quantize_kv_blocks(layer["k"])
        layer["v"], layer["vs"] = quantize_kv_blocks(layer["v"])
    k, v = (jnp.asarray(rng.normal(size=(2, m, Bs, heads, Dh)), jnp.float32)
            for _ in range(2))
    tbl = jnp.asarray([[7, 3, 9], [0, 0, 0]], jnp.int32)

    @jax.jit
    def plain(layer, tbl, k, v):
        fresh = {"k": k, "v": v}
        if quant:
            fresh["k"], fresh["ks"] = quantize_kv_blocks(k)
            fresh["v"], fresh["vs"] = quantize_kv_blocks(v)
        return {n: layer[n].at[tbl].set(fresh[n]) for n in layer}

    want = plain(layer, tbl, k, v)
    sh = None
    if tp > 1:
        cfg = LlamaConfig(vocab_size=64, dim=heads * Dh, n_layers=1,
                          n_heads=heads, n_kv_heads=heads, head_dim=Dh,
                          mlp_dim=32)
        mesh = jax.sharding.Mesh(np.array(jax.devices()[:tp]), ("tp",))
        sh = EngineShardings(
            mesh, jax.eval_shape(lambda: geometry_params(cfg)), cfg)
        layer = jax.device_put(layer, sh.kv_pool(1, quant)[0])
    got = jax.jit(lambda layer, tbl, k, v: _scatter_blocks(
        layer, tbl, k, v, quant, sh))(layer, tbl, k, v)
    assert set(got) == set(want)
    for n in want:
        assert got[n].dtype == want[n].dtype
        np.testing.assert_array_equal(np.asarray(got[n]),
                                      np.asarray(want[n]), err_msg=n)


# ---------------------------------------------------------------------------
# ops: the pool kernel (interpret) vs the XLA gather reference
# ---------------------------------------------------------------------------

def _pool_fixture(quant):
    rng = np.random.default_rng(3)
    kp = jnp.asarray(rng.normal(size=(12, 8, 2, 16)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(12, 8, 2, 16)), jnp.float32)
    tables = jnp.asarray([[1, 2, 3, 4], [5, 6, 0, 0], [7, 0, 0, 0]],
                         jnp.int32)
    lengths = jnp.asarray([29, 11, 3], jnp.int32)
    q = jnp.asarray(rng.normal(size=(3, 4, 16)), jnp.float32)
    if not quant:
        return q, kp, vp, None, None, tables, lengths
    kq, ks = quantize_kv_blocks(kp)
    vq, vs = quantize_kv_blocks(vp)
    return q, kq, vq, ks, vs, tables, lengths


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
def test_pool_kernel_matches_gather_reference(quant):
    q, kp, vp, ks, vs, tables, lengths = _pool_fixture(quant)
    ref = ragged_gather_attention(q[:, None], kp, vp, tables,
                                  (lengths - 1)[:, None], ks, vs)[:, 0]
    out = paged_decode_attention(q, kp, vp, tables, lengths, ks, vs,
                                 interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_bucketed_paged_kernel_accepts_int8_pool():
    # what a context bucket used to hand the kernel: an int8 pool behind a
    # table cut to the rows' live blocks reads as behind the full table (a
    # row walks its own tiles, so the width chooses nothing)
    q, kp, vp, ks, vs, tables, lengths = _pool_fixture(True)
    rows = slice(1, 3)                  # 11 and 3 tokens: two blocks hold them
    full = paged_decode_attention(q[rows], kp, vp, tables[rows],
                                  lengths[rows], ks, vs, interpret=True)
    cut = paged_decode_attention(q[rows], kp, vp, tables[rows, :2],
                                 lengths[rows], ks, vs, interpret=True)
    np.testing.assert_allclose(np.asarray(cut), np.asarray(full))


# ---------------------------------------------------------------------------
# engine: the tiny model, float pool or int8
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_model():
    cfg = LlamaConfig.tiny()
    model = LlamaForCausalLM(cfg, dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    return cfg, params


def make_engine(tiny_model, monkeypatch, *, quant=False, async_on=True,
                **over):
    cfg, params = tiny_model
    monkeypatch.setenv("SHAI_ASYNC_DECODE", "1" if async_on else "0")
    monkeypatch.setenv("SHAI_KV_QUANT", "int8" if quant else "")
    kw = dict(max_model_len=128, max_num_seqs=3, block_size=8,
              context_encoding_buckets=(16, 32), max_new_tokens=16)
    kw.update(over)
    eng = LLMEngine(cfg, params, EngineConfig(**kw))
    assert eng._kv_quant is quant
    return eng


def pool_balanced(eng) -> bool:
    return eng.cache.allocator.n_free == eng.ecfg.total_blocks - 1


MIXED = [[1, 5, 9], [2] * 20, [7, 3] * 14, [4]]  # mixed lengths, on purpose


def test_pad_accounting_fraction(tiny_model, monkeypatch):
    sp = SamplingParams(temperature=0.0, max_new_tokens=10)
    eng = make_engine(tiny_model, monkeypatch)
    eng.generate(MIXED, sp)
    snap = eng.obs.snapshot()
    assert snap["real_tokens"] > 0
    assert snap["pad_tokens"] > 0       # MIXED fills no bucket exactly
    assert snap["pad_fraction"] == pytest.approx(
        snap["pad_tokens"] / (snap["pad_tokens"] + snap["real_tokens"]),
        abs=1e-4)


# ---------------------------------------------------------------------------
# engine: int8 KV — match rate + exact accounting
# ---------------------------------------------------------------------------

def _greedy_match_rate(fa, fb) -> float:
    agree = total = 0
    for x, y in zip(fa, fb):
        for t1, t2 in zip(x.token_ids, y.token_ids):
            total += 1
            agree += t1 == t2
    return agree / max(1, total)


def test_kv_quant_greedy_match_rate(tiny_model, monkeypatch):
    sp = SamplingParams(temperature=0.0, max_new_tokens=12)
    q = make_engine(tiny_model, monkeypatch, quant=True)
    f = make_engine(tiny_model, monkeypatch, quant=False)
    rate = _greedy_match_rate(q.generate(MIXED, sp), f.generate(MIXED, sp))
    # int8 KV is lossy by design; the serving contract is a HIGH greedy
    # match rate, not exactness (threshold mirrors the PARITY.md style)
    assert rate >= 0.8, rate
    assert pool_balanced(q)


def test_kv_quant_pool_bytes_and_ledger_attribution(tiny_model,
                                                    monkeypatch):
    q = make_engine(tiny_model, monkeypatch, quant=True)
    f = make_engine(tiny_model, monkeypatch, quant=False)
    # int8 blocks halve; the f32 scale rows ride alongside (tiny overhead)
    blk_f = f.cache.pool_bytes
    blk_q = q.cache.pool_bytes
    assert blk_q < 0.6 * blk_f
    n_layers = len(q.cache.kv)
    scale_bytes = 2 * n_layers * q.cache.total_blocks * \
        q.cfg.n_kv_heads * 4
    assert blk_q == blk_f // 2 + scale_bytes
    # the HBM ledger attributes the REAL int8 pool, not the bf16 price
    sp = SamplingParams(temperature=0.0, max_new_tokens=4)
    q.generate([[1, 2, 3]], sp)
    assert q.obs.hbm.snapshot()["kv_pool_bytes"] == blk_q
    # and the kv pytree really carries int8 blocks + f32 scales
    lay = q.cache.kv[0]
    assert lay["k"].dtype == jnp.int8 and lay["ks"].dtype == jnp.float32
    assert lay["ks"].shape == (q.cache.total_blocks, q.cfg.n_kv_heads)


def test_kv_quant_cancel_evict_fuzz_pool_exact(tiny_model, monkeypatch):
    # seeded schedule fuzz with quant + prefix caching + host
    # tier: every request terminal exactly once, device pool balanced,
    # host tier accounting exact — the PR's accounting acceptance gate
    monkeypatch.setenv("SHAI_KVTIER", "1")
    monkeypatch.setenv("SHAI_KVTIER_ASYNC", "0")
    eng = make_engine(tiny_model, monkeypatch, quant=True,
                      enable_prefix_caching=True, num_blocks=20,
                      max_model_len=128)
    assert eng.cache.tier is not None
    rng = np.random.default_rng(42)
    sp = SamplingParams(temperature=0.0, max_new_tokens=6)
    live, done = [], set()
    for step in range(60):
        if rng.random() < 0.5 and len(live) < 6:
            n = int(rng.integers(2, 40))
            rid = eng.add_request(rng.integers(3, 200, n).tolist(), sp)
            live.append(rid)
        if rng.random() < 0.2 and live:
            victim = live[int(rng.integers(len(live)))]
            fin = eng.cancel(victim)
            if fin is not None:
                assert victim not in done
                done.add(victim)
                live.remove(victim)
        for f in eng.step():
            assert f.req_id not in done
            done.add(f.req_id)
            live.remove(f.req_id)
    while eng.has_work:
        for f in eng.step():
            assert f.req_id not in done
            done.add(f.req_id)
            live.remove(f.req_id)
    assert not live
    # release every cache hold (prefix cache keeps refs by design): the
    # evictable count must equal exactly the cached blocks, and live
    # holds must be zero
    assert eng.cache.leaked_blocks == 0
    snap = eng.cache.tier.snapshot()
    assert snap["used_bytes"] == snap["entries"] * snap["block_nbytes"]
    assert snap["errors"] == 0


# ---------------------------------------------------------------------------
# kvtier: quantized demote -> restore round-trip is byte-exact
# ---------------------------------------------------------------------------

def test_tier_roundtrip_quant_bytes_exact():
    from scalable_hw_agnostic_inference_tpu.kvtier.pool import HostKVTier

    rng = np.random.default_rng(8)
    L, Bs, H, D, n = 2, 8, 2, 16, 3
    tier = HostKVTier(n_layers=L, block_size=Bs, n_kv_heads=H, head_dim=D,
                      dtype=np.int8, capacity_bytes=1 << 20,
                      async_copy=False, quant=True)
    # block_nbytes prices int8 blocks + f32 scales
    assert tier.block_nbytes == 2 * L * Bs * H * D * 1 + 2 * L * H * 4
    k = rng.integers(-127, 127, (L, n, Bs, H, D)).astype(np.int8)
    v = rng.integers(-127, 127, (L, n, Bs, H, D)).astype(np.int8)
    ks = rng.random((L, n, H)).astype(np.float32)
    vs = rng.random((L, n, H)).astype(np.float32)
    hashes = [101, 202, 303]
    tier.store_batch(hashes, k, v, ks, vs, n)
    run = tier.get_run(hashes)
    assert [e[0] for e in run] == hashes
    for j, ent in enumerate(run):
        np.testing.assert_array_equal(ent[1], k[:, j])
        np.testing.assert_array_equal(ent[2], v[:, j])
        np.testing.assert_array_equal(ent[3], ks[:, j])
        np.testing.assert_array_equal(ent[4], vs[:, j])


def test_engine_tier_restore_quant_replay_greedy_equal(tiny_model,
                                                       monkeypatch):
    # demote a prompt's quantized blocks to the host tier under eviction
    # pressure, then replay: the restore path must reproduce the SAME
    # greedy tokens as the original run (byte-exact blocks+scales), and
    # the tier must actually have been exercised
    monkeypatch.setenv("SHAI_KVTIER", "1")
    monkeypatch.setenv("SHAI_KVTIER_ASYNC", "0")
    eng = make_engine(tiny_model, monkeypatch, quant=True,
                      enable_prefix_caching=True, num_blocks=14,
                      max_model_len=128, max_num_seqs=1,
                      context_encoding_buckets=(16, 32, 64))
    rng = np.random.default_rng(13)
    probe = rng.integers(3, 200, 56).tolist()
    fillers = [rng.integers(3, 200, 56).tolist() for _ in range(3)]
    sp = SamplingParams(temperature=0.0, max_new_tokens=6)
    [first] = eng.generate([probe], sp)
    for fl in fillers:
        eng.generate([fl], sp)
    assert eng.cache.tier.snapshot()["stores"] > 0
    [replay] = eng.generate([probe], sp)
    assert replay.token_ids == first.token_ids
    assert eng.cache.tier.snapshot()["restored"] > 0
