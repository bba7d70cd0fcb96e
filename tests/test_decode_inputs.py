"""How a decode step's inputs reach the device (PR 30).

Three properties of one mechanism, the marshal of a step's inputs:

* placement: on a mesh every argument of a step program arrives with the
  sharding the program was compiled for, so no call leaves pjit's fast path
  to reshard one (``jax._src.array.shard_device_array``);
* the rng: the step programs fold the step's index into the resident base
  key themselves, and draw the tokens the eager ``fold_in(key, step * 2)``
  of the parent drew;
* tables by row: ``ResidentBatch`` rewrites the rows whose allocation's
  version moved and nothing else. The parent's full rebuild lives on here
  as the oracle.
"""

import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from scalable_hw_agnostic_inference_tpu.engine import EngineConfig, runner
from scalable_hw_agnostic_inference_tpu.engine.cache import PagedKVCache
from scalable_hw_agnostic_inference_tpu.engine.engine import (
    LLMEngine,
    SamplingParams,
)
from scalable_hw_agnostic_inference_tpu.engine.resident import ResidentBatch
from scalable_hw_agnostic_inference_tpu.models.llama import (
    LlamaConfig,
    LlamaForCausalLM,
)
from scalable_hw_agnostic_inference_tpu.ops.sampling import sample_logits


@pytest.fixture(scope="module")
def tiny_model():
    cfg = LlamaConfig.tiny()
    model = LlamaForCausalLM(cfg, dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    return cfg, params


ENGINE_KW = dict(max_model_len=64, max_num_seqs=3, block_size=8,
                 context_encoding_buckets=(16, 32), max_new_tokens=16)


def make_engine(tiny_model, async_on, monkeypatch, tp=0, **over):
    cfg, params = tiny_model
    monkeypatch.setenv("SHAI_ASYNC_DECODE", "1" if async_on else "0")
    kw = dict(ENGINE_KW, **over)
    if not tp:
        return LLMEngine(cfg, params, EngineConfig(**kw))
    from scalable_hw_agnostic_inference_tpu.core.mesh import build_mesh
    from scalable_hw_agnostic_inference_tpu.models.llama import tp_rules
    from scalable_hw_agnostic_inference_tpu.parallel.sharding import (
        shard_pytree,
    )

    mesh = build_mesh(f"tp={tp}", devices=jax.devices()[:tp])
    return LLMEngine(cfg, shard_pytree(params, mesh, tp_rules()),
                     EngineConfig(tensor_parallel_size=tp, **kw), mesh=mesh)


def record_calls(programs, log, during=lambda: 0):
    """Wrap every program of an engine's ladder so each call's arguments
    are kept (a spy on the jit call), with what ``during`` counted while
    the call ran."""
    for key, fn in list(programs.items()):
        def spy(*args, _fn=fn, _key=key):
            n = during()
            out = _fn(*args)
            log.append((_key, args, during() - n))
            return out
        programs[key] = spy


# ---------------------------------------------------------------------------
# placement
# ---------------------------------------------------------------------------

def assert_placed(eng, args, n_rep):
    """Every argument of a step program's call carries the sharding the
    program was compiled to take: params and pool by the plan, the ``n_rep``
    small inputs behind them replicated over the mesh."""
    sh = eng.shardings
    want = (sh.params, sh.kv_pool(eng.cfg.n_layers)) + (sh.rep,) * n_rep
    assert len(args) == len(want)

    def same(a, s):
        assert isinstance(a, jax.Array), type(a)
        assert a.sharding.is_equivalent_to(s, a.ndim), (a.sharding, s)
        assert a.committed

    jax.tree.map(same, tuple(args), want)


def test_no_step_argument_is_resharded_on_a_mesh(tiny_model, monkeypatch):
    """Tensor parallel 2 on the host mesh: after warm-up, twenty steady
    steps, one prefill and one continuation never call
    ``shard_device_array`` (the slow road a call takes for an argument whose
    sharding is not the program's), and every argument is placed. (Between
    the programs an admission still runs eager operations of its own: the
    sampler's fold, the first token's read. Once a request, not a step.)"""
    import jax._src.array as jarray

    eng = make_engine(tiny_model, True, monkeypatch, tp=2, max_model_len=128,
                      max_new_tokens=64)
    eng.warm_executables()
    resharded = []
    real = jarray.shard_device_array
    monkeypatch.setattr(
        jarray, "shard_device_array",
        lambda x, *a, **k: resharded.append(x.shape) or real(x, *a, **k))
    decodes, admits = [], []
    record_calls(eng._decode_fns, decodes, lambda: len(resharded))
    record_calls(eng._prefill, admits, lambda: len(resharded))
    sp = SamplingParams(temperature=0.8, top_k=8, top_p=0.9,
                        max_new_tokens=40)
    eng.add_request([1, 17, 42, 99, 7], sp)
    eng.add_request(list(range(2, 50)), sp)   # past the largest bucket
    steady = 0
    while eng.has_work:
        flushes, n = eng.obs.pipeline_flushes, len(resharded)
        had_pipe = eng._pipe is not None
        eng.step()
        if had_pipe and eng.obs.pipeline_flushes == flushes:
            steady += 1
            assert len(resharded) == n      # a whole steady step: none
    assert steady >= 20
    kinds = {"cont" if key[0] == "cont" else "prefill"
             for key, _, _ in admits}
    assert kinds == {"prefill", "cont"}
    assert len(decodes) >= 20
    for _, args, n in decodes:
        assert_placed(eng, args, 9)
        assert n == 0
    for _, args, n in admits:
        assert_placed(eng, args, 3)
        assert n == 0


def test_fed_first_tokens_are_replicated_over_four_devices(monkeypatch):
    """Tensor parallel 4 on the host mesh: the token input an event step
    builds on the device (the sampler's output written into the rows that
    wait for it) carries the engine's replicated sharding, it IS the
    array the decode call takes, and that call reshards nothing."""
    import dataclasses

    import jax._src.array as jarray

    cfg = dataclasses.replace(LlamaConfig.tiny(), n_kv_heads=4)
    params = LlamaForCausalLM(cfg, dtype=jnp.float32).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    eng = make_engine((cfg, params), True, monkeypatch, tp=4,
                      max_model_len=128, max_new_tokens=64)
    assert len(eng.shardings.rep.device_set) == 4
    eng.warm_executables()
    resharded, fed, decodes = [], [], []
    real = jarray.shard_device_array
    monkeypatch.setattr(
        jarray, "shard_device_array",
        lambda x, *a, **k: resharded.append(x.shape) or real(x, *a, **k))
    feed = eng._feed1
    monkeypatch.setattr(
        eng, "_feed1", lambda *a: fed.append(feed(*a)) or fed[-1])
    record_calls(eng._decode_fns, decodes, lambda: len(resharded))
    sp = SamplingParams(temperature=0.8, top_k=8, max_new_tokens=12)
    eng.add_request([1, 17, 42, 99, 7], sp)
    for _ in range(3):
        eng.step()
    eng.add_request([3, 5, 8], sp)
    eng.add_request(list(range(2, 50)), sp)   # its final chunk feeds too
    while eng.has_work:
        eng.step()
    assert len(fed) >= 3
    rep = eng.shardings.rep
    for tokens in fed:
        assert tokens.sharding.is_equivalent_to(rep, tokens.ndim)
        assert tokens.committed
    taken = {id(args[2]) for _, args, _ in decodes}
    assert {id(t) for t in fed} <= taken
    for _, args, n in decodes:
        assert_placed(eng, args, 9)
        assert n == 0
    snap = eng.obs.snapshot()
    assert snap["first_token_events_fed"] == snap["first_token_events"] \
        == len(fed)


@pytest.mark.parametrize("tp", [0, 2], ids=["one-device", "tp2"])
def test_an_admission_step_compiles_nothing_after_warm_up(tiny_model,
                                                          monkeypatch, tp):
    """The feed program is part of the closed set: one (decode bucket,
    sampler rows) pair each, warmed on the sampler's own output. After
    ``warm_executables`` admissions of every batch size and a long
    prompt's final chunk, joining every decode bucket, compile nothing:
    XLA is asked for no program and ``recompiles`` stays 0."""
    eng = make_engine(tiny_model, True, monkeypatch, tp=tp,
                      max_model_len=128, max_num_seqs=4)
    eng.warm_executables()
    compiled = []

    def listener(event, secs, fun_name="", **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiled.append(fun_name)

    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        sp = SamplingParams(temperature=0.7, top_k=6, max_new_tokens=10)
        arrivals = {0: [[1, 2, 3]], 2: [[4, 5], [6, 7, 8]],
                    4: [list(range(2, 50))], 14: [[9]] * 4}
        step = 0
        while eng.has_work or step <= max(arrivals):
            for prompt in arrivals.get(step, ()):
                eng.add_request(prompt, sp)
            if eng.has_work:
                eng.step()
            step += 1
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)
    snap = eng.obs.snapshot()
    assert snap["first_token_events_fed"] == snap["first_token_events"] >= 4
    assert snap["recompiles"] == 0
    assert not compiled, compiled


# ---------------------------------------------------------------------------
# the rng
# ---------------------------------------------------------------------------

def parent_decode(eng, bb):
    """The parent's decode step, from the pieces it was made of: the key
    comes FOLDED from the host. Returns a stand-in for the engine's program
    that folds eagerly, as the parent did, and answers in today's layout."""
    e = eng.ecfg
    fwd = runner._make_token_forward(
        eng.cfg, e.block_size, e.blocks_per_seq, bb, 1, None, False)

    @jax.jit
    def step(params, kv, tokens, pos, tables, active, key, temp, topk, topp):
        kv, logits, _ = fwd(params, kv, tokens[:, None], pos[:, None],
                            tables, active=active)
        nxt = sample_logits(logits[:, 0], key, temp, topk, topp)
        return (kv, nxt) + runner.token_logprobs(logits[:, 0], nxt)

    base = jax.random.PRNGKey(e.seed)
    folds = []

    def decode(params, kv, tokens, pos, tables, active, rng, fold, *knobs):
        assert np.array_equal(np.asarray(rng), np.asarray(base))
        folds.append(int(fold))
        key = jax.random.fold_in(base, int(fold))   # the eager fold
        kv, nxt, *lps = step(params, kv, tokens, pos, tables, active, key,
                             *knobs)
        if eng._async:
            return (kv, nxt, pos + 1, fold + runner.FOLD_STRIDE, *lps)
        return (kv, nxt, *lps)

    return decode, folds


@pytest.mark.parametrize("async_on", [True, False],
                         ids=["async", "lockstep"])
def test_sampled_tokens_are_the_eager_folds(tiny_model, monkeypatch,
                                            async_on):
    """A sampled request draws, step for step, what the parent drew with
    ``fold_in(key, step * 2)`` launched eagerly before each dispatch."""
    sp = SamplingParams(temperature=0.9, top_k=12, top_p=0.85,
                        max_new_tokens=12)
    prompts = [[1, 17, 42, 99, 7], [3, 5, 8]]

    def run(oracle):
        eng = make_engine(tiny_model, async_on, monkeypatch, seed=7)
        folds = None
        if oracle:
            for bb in (1, 2, 3):
                eng._decode_for(bb)
                eng._decode_fns[bb], f = parent_decode(eng, bb)
                folds = f if bb == 2 else folds
        out = eng.generate(prompts, sp)
        return [f.token_ids for f in out], folds

    got, _ = run(False)
    want, folds = run(True)
    assert got == want
    assert len(set(map(tuple, got))) == 2     # sampled, not two argmaxes
    # the index is the step's, doubled: the admission sampler holds the
    # odd ones between
    assert folds and all(f % 2 == 0 for f in folds)
    assert folds == list(range(folds[0], folds[0] + 2 * len(folds), 2))


def test_a_steady_step_launches_one_program(tiny_model, monkeypatch):
    """No eager rng program before the dispatch, nothing put to the device
    in a step where no row crossed a block: a steady step is one call."""
    eng = make_engine(tiny_model, True, monkeypatch)
    eng.warm_executables()
    calls = []
    record_calls(eng._decode_fns, calls)
    eager = []
    real = jax.random.fold_in
    monkeypatch.setattr(jax.random, "fold_in",
                        lambda *a: eager.append(a) or real(*a))
    eng.add_request([1, 2, 3], SamplingParams(temperature=0.7,
                                              max_new_tokens=14))
    eng.step()                      # admission + the first dispatch
    eager.clear()
    calls.clear()
    rows = eng._res.rows_rewritten
    before = eng.obs.snapshot()
    for _ in range(10):
        assert eng._pipe is not None
        eng.step()
    after = eng.obs.snapshot()
    assert eager == []
    assert len(calls) == 10
    assert (after["dispatches_by_phase"]["decode"]
            - before["dispatches_by_phase"]["decode"]) == 10
    assert after["pipeline_flushes"] == before["pipeline_flushes"]
    # 3 prompt tokens + 11 written so far: the row crossed block 8 once
    crossed = eng._res.rows_rewritten - rows
    assert crossed == 1
    assert (after["decode_input_uploads"]
            - before["decode_input_uploads"]) == crossed


def test_uploads_are_counted_on_the_snapshot(tiny_model, monkeypatch):
    """``decode_input_uploads``: an event step puts the composition's arrays,
    its tokens, positions and fold index and, in the same transfer, one
    array of batch rows for each record of first tokens it feeds on the
    device; the lock-step discipline puts all of a step's every step."""
    eng = make_engine(tiny_model, True, monkeypatch)
    eng.add_request([1, 2, 3], SamplingParams(max_new_tokens=4))
    eng.step()
    n_arrays = len(eng._res.arrays)
    assert eng.obs.snapshot()["decode_input_uploads"] == n_arrays + 3 + 1
    lock = make_engine(tiny_model, False, monkeypatch)
    lock.generate([[1, 2, 3]], SamplingParams(max_new_tokens=4))
    snap = lock.obs.snapshot()
    assert snap["decode_input_uploads"] == 8 * snap[
        "dispatches_by_phase"]["decode"]


def test_marshal_builds_what_the_programs_take(tiny_model, monkeypatch):
    """``_marshal_running`` builds, and the resident mirror uploads, the
    arrays a decode or verify dispatch hands its program, and no other: a
    composition change costs one put of exactly those."""
    import inspect
    import re

    eng = make_engine(tiny_model, True, monkeypatch)
    eng.add_request([1, 2, 3], SamplingParams(max_new_tokens=4))
    eng.step()
    built = set(eng._marshal_running(eng._running_slots(), 1))
    for dispatch in (LLMEngine._dispatch_async, LLMEngine._spec_step):
        taken = set(re.findall(r'\ba\["(\w+)"\]',
                               inspect.getsource(dispatch)))
        assert taken <= built, dispatch.__name__
    # the lock-step step adds the three it makes anew every step
    lock = set(re.findall(r'\bd\["(\w+)"\]',
                          inspect.getsource(LLMEngine._decode_step)))
    assert lock - {"tokens", "pos", "fold"} == built == set(eng._res.arrays)


@pytest.mark.parametrize("async_on", [True, False], ids=["async", "lockstep"])
def test_a_padding_row_asks_the_sampler_for_nothing(tiny_model, monkeypatch,
                                                    async_on):
    """Three greedy rows in a bucket of four: the padding row is greedy
    too, so the step stays one in which no row asks for a draw, and the
    sampler's full-vocabulary sort behind every real row's ``top_k``
    (``global_topk``) is skipped on the device. At temperature 1 the
    padding row made every step with a free slot pay that sort (PR 33)."""
    eng = make_engine(tiny_model, async_on, monkeypatch, max_num_seqs=4)
    assert eng.ecfg.global_topk > 0
    for n in (3, 4, 5):
        eng.add_request(list(range(1, n + 1)),
                        SamplingParams(temperature=0.0, max_new_tokens=6))
    seen = []
    put = eng._put_step
    monkeypatch.setattr(eng, "_put_step",
                        lambda x: seen.append(x) or put(x))
    while eng.has_work:
        eng.step()
    knobs = [d for d in seen if isinstance(d, dict) and "temp" in d]
    assert knobs and any(len(d["temp"]) > int(d["active"].sum())
                         for d in knobs)          # a bucket with a pad row
    for d in knobs:
        live = np.asarray(d["active"], bool)
        assert (np.asarray(d["topk"])[live] > 0).all()
        assert not np.asarray(d["temp"]).any()   # no row asks for a draw
        assert not np.asarray(d["topk"])[~live].any()
        assert (np.asarray(d["topp"])[~live] == 1.0).all()


# ---------------------------------------------------------------------------
# tables by row
# ---------------------------------------------------------------------------

BS, M = 4, 6


class TableRig:
    """A real ``PagedKVCache`` under a ``ResidentBatch``, with the parent's
    whole-table rebuild as the engine's marshal AND as the oracle."""

    def __init__(self):
        self.cache = PagedKVCache(1, {"k": (1, 4), "v": (1, 4)}, 32, BS, M,
                                  dtype=jnp.float32)
        self.ecfg = types.SimpleNamespace(blocks_per_seq=M)
        self.res = ResidentBatch()
        self.puts = 0
        self.running = []

    def _put_step(self, x):
        self.puts += len(jax.tree.leaves(x))
        return jax.tree.map(jnp.asarray, x)

    def seat(self, rid, slot):
        self.running.append(types.SimpleNamespace(
            req=types.SimpleNamespace(req_id=rid), slot=slot))
        self.running.sort(key=lambda s: s.slot)

    def unseat(self, rid):
        self.running = [s for s in self.running if s.req.req_id != rid]

    def rebuilt(self, running, Bb):
        tables = np.zeros((Bb, M), np.int32)
        for i, s in enumerate(running):
            tables[i] = self.cache.seq(s.req.req_id).table(M)
        return tables

    def _marshal_running(self, running, Bb):
        return {"tables": self.rebuilt(running, Bb),
                "active": np.arange(Bb) < len(running)}

    def check(self, Bb=2):
        a = self.res.refresh(self, self.running, Bb)
        want = self.rebuilt(self.running, Bb)
        assert np.asarray(a["tables"]).tolist() == want.tolist()
        return want


def _grow(rig):
    rig.cache.extend(0, BS)                 # row 0 takes a new block
    return {"rows": 1}


def _swap(rig):
    """Shrink then regrow: the LIFO free list hands the two rows each
    other's blocks, every count unchanged."""
    c = rig.cache
    c.extend(0, BS), c.extend(1, BS)
    before = rig.check().copy()
    rows = rig.res.rows_rewritten
    c.shrink(0, BS), c.shrink(1, BS)
    c.extend(0, BS), c.extend(1, BS)
    after = rig.rebuilt(rig.running, 2)
    assert (after != 0).sum(1).tolist() == (before != 0).sum(1).tolist()
    assert after[0, 2] == before[1, 2] and after[1, 2] == before[0, 2]
    return {"rows": 2, "rows_from": rows}


def _fork(rig):
    """Copy-on-write: a child shares its parent's partial tail block and
    takes its own copy at the first write."""
    c = rig.cache
    c.fork_sequence(0, 2)
    rig.unseat(1)
    c.release(1)
    rig.seat(2, 1)
    rig.check()                             # a new composition
    shared = c.seq(2).blocks[-1]
    rows = rig.res.rows_rewritten
    c.extend(2, 1)
    assert c.seq(2).blocks[-1] != shared and c.cow_copies == 1
    return {"rows": 1, "rows_from": rows}


def _readmit(rig):
    """Preempted and re-admitted under its id into its slot: the signature
    is the old one, the blocks are not."""
    c = rig.cache
    old = list(c.seq(1).blocks)
    c.release(1)
    c.admit(9, 2 * BS)                      # someone takes the freed block
    c.admit(1, BS + 1)
    assert c.seq(1).blocks != old
    return {"rows": 1}


def _join(rig):
    c = rig.cache
    rig.unseat(1)
    c.release(1)
    c.admit(5, 2 * BS + 1)
    rig.seat(5, 1)
    return {"rows": 0, "puts": 2}           # a new composition: both arrays


@pytest.mark.parametrize("mutate", [_grow, _swap, _fork, _readmit, _join],
                         ids=lambda f: f.__name__.strip("_"))
def test_resident_tables_track_block_identity_not_count(mutate):
    """Whatever happens to a row's block list, the mirror equals a table
    rebuilt from scratch, and only the rows whose list changed were
    rewritten (one upload)."""
    rig = TableRig()
    rig.cache.admit(0, BS + 2)
    rig.cache.admit(1, BS + 1)
    rig.seat(0, 0), rig.seat(1, 1)
    rig.check()
    rows = rig.res.rows_rewritten
    want = mutate(rig)
    puts = rig.puts
    rig.check()
    assert rig.res.rows_rewritten - want.get("rows_from", rows) == \
        want["rows"]
    assert rig.puts - puts == want.get("puts", 1)
    # and a step in which nothing moved puts nothing
    rig.check()
    assert rig.puts - puts == want.get("puts", 1)


def test_one_row_grows_one_row_is_rewritten():
    """Three rows, one crosses a block: one row rewritten, one upload, and
    the two other rows' host mirror untouched."""
    rig = TableRig()
    for rid in range(3):
        rig.cache.admit(rid, BS)
        rig.seat(rid, rid)
    rig.check(Bb=4)
    puts, rows = rig.puts, rig.res.rows_rewritten
    for rid in range(3):
        rig.cache.extend(rid, 1 if rid == 1 else 0)
    versions = list(rig.res.versions)
    rig.check(Bb=4)
    assert rig.res.rows_rewritten - rows == 1
    assert rig.puts - puts == 1
    moved = [a != b for a, b in zip(versions, rig.res.versions)]
    assert moved == [False, True, False]
    # tokens inside a block move no stamp: no upload
    rig.cache.extend(1, 1)
    rig.check(Bb=4)
    assert rig.puts - puts == 1
