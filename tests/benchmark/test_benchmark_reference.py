"""The plain float32 reference against the served path, at the tiny size on
the CPU: prefill's last position and every decode step through the paged
cache agree with one full forward pass of the reference, on one device and
under tensor parallelism over two virtual devices; and a reference that is
wrong on purpose (no rotary embedding, keys and values rounded to 4 bits)
is refused by the same tolerance; keys and values rounded to int8 by block
and head are NOT, and the test says so."""

import jax
import numpy as np
import pytest

from benchmark import run as bench_run
from benchmark.reference import check, mistral
from benchmark.server import SystemUnderTest
from benchmark.spec import Spec

SPEC = Spec()


@pytest.fixture(scope="module", params=["mistral-7b-int8",
                                        "mistral-7b-bf16-tp4"])
def served(request, tmp_path_factory):
    """The dry run's system under test: the tiny model behind the real
    server, int8 on one device or bf16 over two."""
    cfg = SPEC.config(request.param)
    sut = SystemUnderTest(cfg, SPEC.harness, 1234,
                          str(tmp_path_factory.mktemp("sut")), dry_run=True)
    sut.devices()
    sut.start()
    yield sut
    sut.stop()


def _run(sut, variant=""):
    return bench_run.reference_check(SPEC, sut, sut.config, 99, True, variant)


def test_served_path_agrees_with_the_reference(served):
    tp = served.service.ecfg.tensor_parallel_size
    assert tp == (2 if "tp4" in served.config["name"] else 1)
    got = _run(served)
    assert got["positions"] == 8 and got["finite"]
    assert got["passed"], got
    assert got["max_abs_logprob_diff"] <= check.tolerance()[
        "max_abs_logprob_diff"]


@pytest.mark.parametrize("variant", ["no_rope", "kv_4bit"])
def test_a_wrong_reference_is_refused(served, variant):
    got = _run(served, variant)
    assert not got["passed"], got
    assert got["max_abs_logprob_diff"] > check.tolerance()[
        "max_abs_logprob_diff"]


def test_int8_keys_and_values_are_not_refused(served):
    """The limit of the check, pinned: int8 KV with one scale per block and
    head adds an error of the size of bfloat16's own, far under the
    tolerance (``tolerance.json`` says what follows from that)."""
    got = _run(served, "kv_int8")
    assert got["passed"], got
    assert got["mean_abs_logprob_diff"] < check.tolerance()[
        "mean_abs_logprob_diff"] / 3


def test_layer_matches_a_loop_written_out_by_hand():
    """The reference's own layer against the equations, position by
    position, in numpy float64 — so the yardstick is checked too."""
    rng = np.random.default_rng(0)
    T, H, HKV, D, HID, MLP = 5, 4, 2, 8, 32, 48
    f = lambda *s: rng.standard_normal(s).astype(np.float32) * 0.2
    w = {"q": f(HID, H * D), "k": f(HID, HKV * D), "v": f(HID, HKV * D),
         "o": f(H * D, HID), "gate": f(HID, MLP), "up": f(HID, MLP),
         "down": f(MLP, HID), "attn_norm": 1 + f(HID), "mlp_norm": 1 + f(HID)}
    x = f(T, HID)
    got = np.asarray(mistral.layer(x, w, n_heads=H, n_kv=HKV, eps=1e-5,
                                   theta=10000.0))

    def norm(v, g):
        return v / np.sqrt(np.mean(v * v) + 1e-5) * g

    def rot(v, pos):
        out = v.copy()
        for i in range(D // 2):
            a = pos * 10000.0 ** (-2 * i / D)
            out[i] = v[i] * np.cos(a) - v[i + D // 2] * np.sin(a)
            out[i + D // 2] = v[i + D // 2] * np.cos(a) + v[i] * np.sin(a)
        return out

    w64 = {k: v.astype(np.float64) for k, v in w.items()}
    x64 = x.astype(np.float64)
    a = np.stack([norm(x64[t], w64["attn_norm"]) for t in range(T)])
    q = (a @ w64["q"]).reshape(T, H, D)
    k = (a @ w64["k"]).reshape(T, HKV, D)
    v = (a @ w64["v"]).reshape(T, HKV, D)
    want = np.zeros((T, HID))
    for t in range(T):
        heads = []
        for h in range(H):
            g = h // (H // HKV)
            qt = rot(q[t, h], t)
            s = np.array([qt @ rot(k[u, g], u) / np.sqrt(D)
                          for u in range(t + 1)])
            p = np.exp(s - s.max())
            p /= p.sum()
            heads.append(sum(p[u] * v[u, g] for u in range(t + 1)))
        hcat = x64[t] + np.concatenate(heads) @ w64["o"]
        m = norm(hcat, w64["mlp_norm"])
        gate = m @ w64["gate"]
        want[t] = hcat + ((gate / (1 + np.exp(-gate))) * (m @ w64["up"])
                          ) @ w64["down"]
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_int8_leaves_are_dequantised_exactly():
    q = np.array([[-127, 5], [3, 127]], np.int8)
    scale = np.array([0.5, 0.25], np.float32)
    got = np.asarray(mistral.matrix({"kernel_q": q, "scale": scale}))
    np.testing.assert_array_equal(got, [[-63.5, 1.25], [1.5, 31.75]])
    bf = jax.numpy.asarray([[1.5, -2.0]], jax.numpy.bfloat16)
    assert mistral.matrix({"kernel": bf}).dtype == jax.numpy.float32
