"""ops layer tests: attention (XLA + pallas-interpret), GQA, rope, sampling."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from scalable_hw_agnostic_inference_tpu.ops import (
    apply_rope,
    dot_product_attention,
    greedy,
    rope_angles,
    sample_logits,
)
from scalable_hw_agnostic_inference_tpu.ops.attention import causal_mask
from scalable_hw_agnostic_inference_tpu.ops.pallas.flash_attention import (
    flash_attention,
    flash_eligible,
)


def ref_attention(q, k, v, causal=False, mask=None):
    """Straight-line numpy-ish reference in fp32."""
    B, T, H, D = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    if H != Hkv:
        k = jnp.repeat(k, H // Hkv, axis=2)
        v = jnp.repeat(v, H // Hkv, axis=2)
    s = jnp.einsum("bthd,bshd->bhts", q.astype(jnp.float32), k.astype(jnp.float32))
    s = s / (D ** 0.5)
    if mask is not None:
        s = jnp.where(mask, s, -1e30)
    if causal:
        qi = jnp.arange(T)[:, None] + (S - T)
        kj = jnp.arange(S)[None, :]
        s = jnp.where((qi >= kj)[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhts,bshd->bthd", p, v.astype(jnp.float32))


class TestXlaAttention:
    def test_matches_reference(self):
        rng = jax.random.PRNGKey(0)
        kq, kk, kv = jax.random.split(rng, 3)
        q = jax.random.normal(kq, (2, 16, 4, 32))
        k = jax.random.normal(kk, (2, 24, 4, 32))
        v = jax.random.normal(kv, (2, 24, 4, 32))
        out = dot_product_attention(q, k, v, impl="xla")
        np.testing.assert_allclose(out, ref_attention(q, k, v), rtol=1e-5, atol=1e-5)

    def test_causal(self):
        rng = jax.random.PRNGKey(1)
        q = jax.random.normal(rng, (1, 8, 2, 16))
        out = dot_product_attention(q, q, q, causal=True, impl="xla")
        np.testing.assert_allclose(
            out, ref_attention(q, q, q, causal=True), rtol=1e-5, atol=1e-5
        )

    def test_gqa_heads(self):
        rng = jax.random.PRNGKey(2)
        kq, kk, kv = jax.random.split(rng, 3)
        q = jax.random.normal(kq, (1, 8, 8, 16))
        k = jax.random.normal(kk, (1, 8, 2, 16))  # 4 q heads per kv head
        v = jax.random.normal(kv, (1, 8, 2, 16))
        out = dot_product_attention(q, k, v, impl="xla")
        np.testing.assert_allclose(out, ref_attention(q, k, v), rtol=1e-5, atol=1e-5)

    def test_decode_step_causal_offset(self):
        """T=1 decode against S cached keys: the query is the last position."""
        rng = jax.random.PRNGKey(3)
        kq, kk, kv = jax.random.split(rng, 3)
        q = jax.random.normal(kq, (1, 1, 2, 16))
        k = jax.random.normal(kk, (1, 10, 2, 16))
        v = jax.random.normal(kv, (1, 10, 2, 16))
        out = dot_product_attention(q, k, v, causal=True, impl="xla")
        # last-position query attends everything -> same as non-causal
        np.testing.assert_allclose(out, ref_attention(q, k, v), rtol=1e-5, atol=1e-5)

    def test_bias_and_mask(self):
        rng = jax.random.PRNGKey(4)
        q = jax.random.normal(rng, (1, 4, 2, 16))
        bias = jnp.zeros((1, 2, 4, 4)).at[:, :, :, 0].set(5.0)
        out_b = dot_product_attention(q, q, q, bias=bias, impl="xla")
        out = dot_product_attention(q, q, q, impl="xla")
        assert not np.allclose(out_b, out)
        # mask that only allows self-attention == identity-ish mixing of v
        eye = jnp.eye(4, dtype=bool)[None, None]
        out_m = dot_product_attention(q, q, q, mask=eye, impl="xla")
        np.testing.assert_allclose(out_m, q.astype(out_m.dtype), rtol=1e-5, atol=1e-5)


class TestFlashAttention:
    """Pallas kernel in interpret mode on CPU; same kernel compiles on TPU."""

    def test_eligibility(self):
        q = jnp.zeros((1, 128, 4, 64))
        k = jnp.zeros((1, 256, 4, 64))
        assert flash_eligible(q, k, k)
        # ragged S is padded+masked inside the kernel wrapper (VERDICT r2 #1a)
        assert flash_eligible(q, jnp.zeros((1, 77, 4, 64)), jnp.zeros((1, 77, 4, 64)))
        # short T uses a smaller q tile (the UNet 8x8 level)
        assert flash_eligible(jnp.zeros((1, 64, 4, 64)), k, k)
        assert not flash_eligible(jnp.zeros((1, 12, 4, 64)), k, k)  # T % 8
        assert not flash_eligible(jnp.zeros((1, 128, 4, 48)), k, k)  # D % 64
        assert not flash_eligible(q, k, k, mask=jnp.ones((1, 1, 1, 1), bool))

    def test_ragged_kv_padding_matches_xla(self):
        """S=77 (CLIP context) rides the pad+length path inside the kernel."""
        rng = jax.random.PRNGKey(8)
        kq, kk, kv = jax.random.split(rng, 3)
        q = jax.random.normal(kq, (2, 256, 4, 64), jnp.float32)
        k = jax.random.normal(kk, (2, 77, 4, 64), jnp.float32)
        v = jax.random.normal(kv, (2, 77, 4, 64), jnp.float32)
        out = flash_attention(q, k, v, interpret=True)
        ref = ref_attention(q, k, v)
        np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)

    def test_short_t_small_q_tile_matches_xla(self):
        """T=S=64 (the UNet 8x8 self-attention level) uses block_q=64."""
        rng = jax.random.PRNGKey(9)
        kq, kk, kv = jax.random.split(rng, 3)
        q = jax.random.normal(kq, (2, 64, 4, 64), jnp.float32)
        k = jax.random.normal(kk, (2, 64, 4, 64), jnp.float32)
        v = jax.random.normal(kv, (2, 64, 4, 64), jnp.float32)
        out = flash_attention(q, k, v, interpret=True)
        ref = ref_attention(q, k, v)
        np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)

    def test_ragged_kv_with_lengths_matches_xla(self):
        """Explicit lengths combine with the padding path (min of the two)."""
        rng = jax.random.PRNGKey(10)
        kq, kk, kv = jax.random.split(rng, 3)
        q = jax.random.normal(kq, (2, 128, 2, 64), jnp.float32)
        k = jax.random.normal(kk, (2, 77, 2, 64), jnp.float32)
        v = jax.random.normal(kv, (2, 77, 2, 64), jnp.float32)
        lengths = jnp.array([50, 77], jnp.int32)
        out = flash_attention(q, k, v, lengths=lengths, interpret=True)
        lm = (jnp.arange(77)[None, :] < lengths[:, None])[:, None, None, :]
        ref = ref_attention(q, k, v, mask=lm)
        np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_xla(self, causal):
        rng = jax.random.PRNGKey(5)
        kq, kk, kv = jax.random.split(rng, 3)
        q = jax.random.normal(kq, (2, 256, 4, 64), jnp.float32)
        k = jax.random.normal(kk, (2, 256, 4, 64), jnp.float32)
        v = jax.random.normal(kv, (2, 256, 4, 64), jnp.float32)
        out = flash_attention(q, k, v, causal=causal, interpret=True)
        ref = ref_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)

    def test_gqa(self):
        rng = jax.random.PRNGKey(6)
        kq, kk, kv = jax.random.split(rng, 3)
        q = jax.random.normal(kq, (1, 128, 8, 64), jnp.float32)
        k = jax.random.normal(kk, (1, 128, 2, 64), jnp.float32)
        v = jax.random.normal(kv, (1, 128, 2, 64), jnp.float32)
        out = flash_attention(q, k, v, interpret=True)
        ref = ref_attention(q, k, v)
        np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)

    def test_bf16(self):
        rng = jax.random.PRNGKey(7)
        q = jax.random.normal(rng, (1, 128, 2, 64)).astype(jnp.bfloat16)
        out = flash_attention(q, q, q, interpret=True)
        assert out.dtype == jnp.bfloat16
        ref = ref_attention(q, q, q)
        np.testing.assert_allclose(
            out.astype(jnp.float32), ref, rtol=5e-2, atol=5e-2
        )


class TestRope:
    def test_shapes_and_zero_position(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 4, 3, 8))
        pos = jnp.zeros((2, 4), jnp.int32)
        out = apply_rope(x, pos)
        # position 0 => rotation by angle 0 => identity
        np.testing.assert_allclose(out, x, rtol=1e-6, atol=1e-6)

    def test_rotation_preserves_norm(self):
        x = jax.random.normal(jax.random.PRNGKey(1), (1, 6, 2, 16))
        pos = jnp.arange(6)[None, :]
        out = apply_rope(x, pos)
        np.testing.assert_allclose(
            jnp.linalg.norm(out, axis=-1), jnp.linalg.norm(x, axis=-1),
            rtol=1e-5, atol=1e-5,
        )

    def test_relative_property(self):
        """<rope(q,m), rope(k,n)> depends only on m-n."""
        rng = jax.random.PRNGKey(2)
        q = jax.random.normal(rng, (1, 1, 1, 32))
        k = jax.random.normal(jax.random.PRNGKey(3), (1, 1, 1, 32))

        def dot_at(m, n):
            qm = apply_rope(q, jnp.array([[m]]))
            kn = apply_rope(k, jnp.array([[n]]))
            return float(jnp.sum(qm * kn))

        assert dot_at(5, 3) == pytest.approx(dot_at(12, 10), rel=1e-4)
        assert dot_at(0, 0) == pytest.approx(dot_at(7, 7), rel=1e-4)

    def test_angles_shape(self):
        cos, sin = rope_angles(jnp.arange(10), 64)
        assert cos.shape == (10, 32) and sin.shape == (10, 32)


class TestSampling:
    def test_greedy(self):
        logits = jnp.array([[0.1, 5.0, -1.0], [2.0, 0.0, 3.0]])
        np.testing.assert_array_equal(greedy(logits), [1, 2])

    def test_temperature_zero_is_greedy(self):
        logits = jax.random.normal(jax.random.PRNGKey(0), (4, 100))
        toks = sample_logits(logits, jax.random.PRNGKey(1), temperature=0.0)
        np.testing.assert_array_equal(toks, greedy(logits))

    def test_top_k_restricts_support(self):
        logits = jnp.array([[10.0, 9.0, 1.0, 0.0, -5.0]])
        seen = set()
        for i in range(50):
            t = sample_logits(logits, jax.random.PRNGKey(i), temperature=2.0, top_k=2)
            seen.add(int(t[0]))
        assert seen <= {0, 1}

    def test_top_p_keeps_top1_always(self):
        logits = jnp.array([[3.0, 1.0, 0.0]])
        for i in range(20):
            t = sample_logits(logits, jax.random.PRNGKey(i), top_p=0.01)
            assert int(t[0]) == 0

    def test_per_request_knobs(self):
        """Row 0 greedy, row 1 heavily top-k-restricted."""
        logits = jnp.tile(jnp.array([[5.0, 4.0, -10.0, -10.0]]), (2, 1))
        temps = jnp.array([0.0, 1.0])
        ks = jnp.array([0, 2])
        for i in range(20):
            t = sample_logits(logits, jax.random.PRNGKey(i), temperature=temps, top_k=ks)
            assert int(t[0]) == 0
            assert int(t[1]) in (0, 1)

    def test_jit_compatible(self):
        fn = jax.jit(lambda l, r: sample_logits(l, r, temperature=0.8, top_k=50, top_p=0.9))
        logits = jax.random.normal(jax.random.PRNGKey(0), (2, 1000))
        out = fn(logits, jax.random.PRNGKey(1))
        assert out.shape == (2,) and out.dtype == jnp.int32


class TestFlashLengths:
    """Length-aware flash path — the bucketed-prefill contract (VERDICT r1 #3)."""

    def _qkv(self, B=2, T=128, H=4, Hkv=2, D=64, seed=0):
        ks = jax.random.split(jax.random.PRNGKey(seed), 3)
        q = jax.random.normal(ks[0], (B, T, H, D), jnp.float32)
        k = jax.random.normal(ks[1], (B, T, Hkv, D), jnp.float32)
        v = jax.random.normal(ks[2], (B, T, Hkv, D), jnp.float32)
        return q, k, v

    @pytest.mark.parametrize("causal", [False, True])
    def test_lengths_match_xla_mask(self, causal):
        q, k, v = self._qkv()
        lens = jnp.asarray([37, 128], jnp.int32)
        flash = flash_attention(q, k, v, causal=causal, lengths=lens,
                                interpret=True)
        ref = dot_product_attention(q, k, v, kv_lengths=lens, causal=causal,
                                    impl="xla")
        # only rows < length are consumed downstream; compare those
        for b, n in enumerate([37, 128]):
            np.testing.assert_allclose(np.asarray(flash)[b, :n],
                                       np.asarray(ref)[b, :n],
                                       rtol=2e-4, atol=2e-4)

    @pytest.mark.slow  # tier-1 budget: see scripts/check_tier1_budget.py
    def test_engine_prefill_shapes_select_pallas(self):
        """The LLM prefill call pattern (kv_lengths, causal, no mask) must be
        flash-eligible for real bucket/head geometries — impl='pallas' raises
        if the kernel is not selected."""
        for bucket, D, H, Hkv in [(128, 64, 4, 2), (512, 128, 8, 2),
                                  (2048, 128, 32, 8)]:
            q, k, v = self._qkv(B=1, T=bucket, H=H, Hkv=Hkv, D=D)
            assert flash_eligible(q, k, v)
            out = dot_product_attention(
                q, k, v, kv_lengths=jnp.asarray([bucket // 2], jnp.int32),
                causal=True, impl="pallas")
            assert out.shape == q.shape
            assert bool(jnp.isfinite(out[:, : bucket // 2]).all())

    def test_zero_padding_rows_are_finite(self):
        q, k, v = self._qkv(B=1)
        out = flash_attention(q, k, v, causal=True,
                              lengths=jnp.asarray([1], jnp.int32),
                              interpret=True)
        assert bool(jnp.isfinite(out).all())

    def test_causal_offset_when_t_lt_s(self):
        """Causal with T < S must follow the S-T offset contract (queries are
        the LAST T positions), matching the XLA path exactly."""
        B, T, S, H, D = 1, 128, 256, 2, 64
        ks = jax.random.split(jax.random.PRNGKey(7), 3)
        q = jax.random.normal(ks[0], (B, T, H, D), jnp.float32)
        k = jax.random.normal(ks[1], (B, S, H, D), jnp.float32)
        v = jax.random.normal(ks[2], (B, S, H, D), jnp.float32)
        flash = flash_attention(q, k, v, causal=True, interpret=True)
        ref = dot_product_attention(q, k, v, causal=True, impl="xla")
        np.testing.assert_allclose(np.asarray(flash), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)


class TestPagedDecodeAttention:
    """Block-table-streaming decode kernel vs a dense gather reference."""

    def _rand_pool(self, B, H, Hkv, D, bs, N, M, lengths, seed=0):
        rng = np.random.default_rng(seed)
        q = rng.standard_normal((B, H, D)).astype(np.float32)
        kp = rng.standard_normal((N, bs, Hkv, D)).astype(np.float32)
        vp = rng.standard_normal((N, bs, Hkv, D)).astype(np.float32)
        tables = np.zeros((B, M), np.int32)
        free = list(range(1, N))
        for b in range(B):
            for j in range(-(-int(lengths[b]) // bs)):
                tables[b, j] = free.pop()
        return q, kp, vp, tables

    def _dense_ref(self, q, kp, vp, tables, lengths):
        B, H, D = q.shape
        _, bs, Hkv, _ = kp.shape
        group = H // Hkv
        out = np.zeros_like(q)
        for b in range(B):
            L = int(lengths[b])
            n_live = -(-L // bs)
            kc = kp[tables[b, :n_live]].reshape(n_live * bs, Hkv, D)[:L]
            vc = vp[tables[b, :n_live]].reshape(n_live * bs, Hkv, D)[:L]
            for h in range(H):
                s = (q[b, h] @ kc[:, h // group].T) / np.sqrt(D)
                p = np.exp(s - s.max())
                p /= p.sum()
                out[b, h] = p @ vc[:, h // group]
        return out

    @pytest.mark.parametrize("Hkv", [2, 8])  # GQA and MHA
    def test_matches_dense(self, Hkv):
        from scalable_hw_agnostic_inference_tpu.ops.pallas.paged_attention import (
            paged_decode_attention,
        )

        B, H, D, bs, N, M = 3, 8, 64, 16, 32, 6
        lengths = np.array([5, 37, 96], np.int32)
        q, kp, vp, tables = self._rand_pool(B, H, Hkv, D, bs, N, M, lengths)
        out = paged_decode_attention(
            jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(tables), jnp.asarray(lengths), interpret=True)
        ref = self._dense_ref(q, kp, vp, tables, lengths)
        np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-4, atol=2e-4)

    def test_truncated_tables_match_full_window(self):
        """Dispatching on a smaller ctx bucket (tables[:, :m]) is exact as
        long as every live block fits — the engine's bucketed decode."""
        from scalable_hw_agnostic_inference_tpu.ops.pallas.paged_attention import (
            paged_decode_attention,
        )

        B, H, Hkv, D, bs, N, M = 2, 4, 2, 64, 16, 32, 8
        lengths = np.array([20, 30], np.int32)  # 2 blocks each
        q, kp, vp, tables = self._rand_pool(B, H, Hkv, D, bs, N, M, lengths)
        args = (jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp))
        full = paged_decode_attention(
            *args, jnp.asarray(tables), jnp.asarray(lengths), interpret=True)
        cut = paged_decode_attention(
            *args, jnp.asarray(tables[:, :2]), jnp.asarray(lengths),
            interpret=True)
        np.testing.assert_allclose(np.asarray(cut), np.asarray(full),
                                   rtol=1e-6, atol=1e-6)


def _poisoned_pool(lengths, M, bs, Hkv, D, tile, seed=0):
    """Tables of ``M`` distinct blocks a row; a block is clean only if it
    holds a live token. Dead blocks inside a live tile and whole dead
    tiles are NaN, K and V."""
    rng = np.random.default_rng(seed)
    B = len(lengths)
    N = B * M + 1
    kp = rng.standard_normal((N, bs, Hkv, D)).astype(np.float32)
    vp = rng.standard_normal((N, bs, Hkv, D)).astype(np.float32)
    tables = (1 + rng.permutation(N - 1)).reshape(B, M).astype(np.int32)
    clean_k, clean_v = kp.copy(), vp.copy()
    for b, n in enumerate(lengths):
        if n == 0:
            tables[b] = 0                 # an inactive slot: the null block
            continue
        dead = tables[b, -(-n // bs):]
        kp[dead] = np.nan
        vp[dead] = np.nan
    return kp, vp, clean_k, clean_v, tables


@pytest.mark.parametrize("M,lengths", [
    # M = 21 blocks of 16: one 256-token tile and a part of a second
    (21, [255, 256, 257, 336, 1, 17]),
    # a length-0 row (inactive slot) between live ones; M = 40: 2.5 tiles
    (40, [300, 0, 640, 513]),
])
def test_paged_kernel_walks_only_live_tiles(M, lengths):
    """Every pool block that holds no live token of its row is NaN, K and
    V: dead blocks inside a live tile, whole dead tiles, all of a table a
    caller did not truncate. A kernel that fetches a dead block, or
    multiplies a page it never fetched, returns NaN."""
    B, H, Hkv, D, bs = len(lengths), 4, 2, 64, 16
    from scalable_hw_agnostic_inference_tpu.ops.pallas.paged_attention import (
        paged_decode_attention,
        tile_tokens,
    )

    tile = tile_tokens(bs, Hkv, D, jnp.float32)
    assert tile == 256 and (M * bs) % tile      # M is no multiple of a tile
    kp, vp, ck, cv, tables = _poisoned_pool(lengths, M, bs, Hkv, D, tile)
    q = np.random.default_rng(1).standard_normal((B, H, D)).astype(np.float32)
    n = np.asarray(lengths, np.int32)
    out = np.asarray(paged_decode_attention(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
        jnp.asarray(tables), jnp.asarray(n), interpret=True))
    assert np.isfinite(out).all()
    live = n > 0
    ref = TestPagedDecodeAttention()._dense_ref(
        q[live], ck, cv, tables[live], n[live])
    np.testing.assert_allclose(out[live], ref, rtol=2e-4, atol=2e-4)
    # a table truncated to the live tiles' blocks gives the same bits
    m_live = -(-max(lengths) // bs)
    cut = np.asarray(paged_decode_attention(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
        jnp.asarray(tables[:, :m_live]), jnp.asarray(n), interpret=True))
    np.testing.assert_array_equal(cut, out)


def test_effective_platform_respects_default_device(monkeypatch):
    """The r5 on-chip SD bench crash: ``host_init`` places whole-model flax
    inits on the CPU device while the global backend is the TPU — dispatch
    decisions must follow the device CONTEXT or a Mosaic kernel lands in a
    CPU-placed trace ("Only interpret mode is supported on CPU backend")."""
    from scalable_hw_agnostic_inference_tpu.ops import attention as A

    # simulate a TPU-default process (CI runs cpu-only)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert A.on_tpu_platform()          # no override: global backend rules
    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        assert A.effective_platform() == "cpu"
        assert not A.on_tpu_platform()  # host-placed trace: no Mosaic
    assert A.on_tpu_platform()


@pytest.mark.slow  # tier-1 budget: see scripts/check_tier1_budget.py
def test_llama3_rope_scaling_matches_hf():
    """Our llama3 frequency remap matches transformers' reference impl."""
    torch = pytest.importorskip("torch")
    from transformers.modeling_rope_utils import ROPE_INIT_FUNCTIONS

    from scalable_hw_agnostic_inference_tpu.ops.rope import llama3_scaled_inv_freq

    class Cfg:
        rope_theta = 500000.0
        head_dim = 64
        hidden_size = 64 * 32
        num_attention_heads = 32
        partial_rotary_factor = 1.0
        max_position_embeddings = 131072
        rope_scaling = {
            "rope_type": "llama3", "factor": 8.0, "low_freq_factor": 1.0,
            "high_freq_factor": 4.0, "original_max_position_embeddings": 8192,
        }

    want, _ = ROPE_INIT_FUNCTIONS["llama3"](Cfg(), "cpu")
    base = 1.0 / (Cfg.rope_theta ** (np.arange(0, 64, 2) / 64))
    got = llama3_scaled_inv_freq(jnp.asarray(base, jnp.float32),
                                 (8.0, 1.0, 4.0, 8192))
    np.testing.assert_allclose(np.asarray(got), want.numpy(), rtol=1e-6)
