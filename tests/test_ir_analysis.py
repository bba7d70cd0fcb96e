"""jaxpr-lint: the IR invariant checkers (analysis/ir/) — each rule
catches a seeded violation built from a real jitted program (and stays
quiet on the legal idiom / a valid allow annotation anchored at the
factory def), the live tree's registered executable factories all
build+lower clean, and the CLI honors the JSON/exit contract.

CPU-only: every program here is tiny and traces/lowers in milliseconds;
the live-tree pass lowers (and partly compiles) the full registry once
per module via a session fixture.
"""

import json
import os
import subprocess
import sys
import textwrap

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from scalable_hw_agnostic_inference_tpu.analysis import (  # noqa: E402
    core as lint_core,
)
from scalable_hw_agnostic_inference_tpu.analysis.contract import (  # noqa: E402
    Contract,
    DEFAULT_CONTRACT,
    IrSpec,
)
from scalable_hw_agnostic_inference_tpu.analysis.ir import (  # noqa: E402
    IR_RULES,
    factories,
    run_ir,
)
from scalable_hw_agnostic_inference_tpu.analysis.ir import (  # noqa: E402
    rules as irrules,
)
from scalable_hw_agnostic_inference_tpu.analysis.ir.program import (  # noqa: E402
    IrProgram,
)
from scalable_hw_agnostic_inference_tpu.core.mesh import build_mesh  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SDS = jax.ShapeDtypeStruct

# a fake factory module: findings anchor at these defs, so the allow
# grammar works exactly as on engine/runner.py
FIXTURE_PATH = "engine/_ir_fixture.py"
FIXTURE_SRC = textwrap.dedent("""\
    def make_fixture(feedback=False):
        pass


    # shai-lint: allow(baked-constants) lookup table, priced in the budget
    def make_allowed():
        pass
""")
FIXTURE_MOD = {FIXTURE_PATH: lint_core.Module(FIXTURE_PATH, FIXTURE_SRC)}

FIX_CONTRACT = Contract(ir=IrSpec(
    programs=(), bf16_programs=("*",), hot_programs=("*",),
    const_limit_bytes=1024))


def prog(jitted, args, key="fix", donate=(), factory="make_fixture",
         compile_cpu=False):
    return IrProgram(
        key=key, factory=factory, anchor_path=FIXTURE_PATH, jitted=jitted,
        args=args, donate_args=tuple(donate),
        compile_cpu=compile_cpu).prepare()


def run_rules(progs, contract=FIX_CONTRACT, rules=None):
    fs = irrules.check(progs, contract, rules=rules, modules=FIXTURE_MOD)
    return [f for f in fs if not f.allowed], [f for f in fs if f.allowed]


# -- donation-efficacy -------------------------------------------------------

class TestDonationEfficacy:
    def test_dropped_donation_via_dtype_mismatch(self):
        # the donated bf16 buffer matches no output aval (everything is
        # f32), so XLA silently drops the alias — the KV-pool
        # double-buffering class
        def f(a, b):
            return a.astype(jnp.float32) + b

        p = prog(jax.jit(f, donate_argnums=(0,)),
                 (SDS((8, 8), jnp.bfloat16), SDS((8, 8), jnp.float32)),
                 donate=(0,))
        live, _ = run_rules([p], rules=("donation-efficacy",))
        assert len(live) == 1
        assert "0 of 1 declared donated buffers" in live[0].message
        # the compiler's own diagnosis is carried into the finding
        assert "donated" in live[0].message
        assert live[0].context == "fix"
        assert live[0].path == FIXTURE_PATH

    def test_intact_donation_is_clean(self):
        def f(a, b):
            return a + b, a * 2

        p = prog(jax.jit(f, donate_argnums=(0,)),
                 (SDS((8, 8), jnp.float32), SDS((8, 8), jnp.float32)),
                 donate=(0,), compile_cpu=True)
        live, _ = run_rules([p], rules=("donation-efficacy",))
        assert live == []
        # the compiled executable agrees with lowering
        assert p.compiled_alias_count() == p.lowered_alias_count() == 1

    def test_stale_declared_contract_flagged(self):
        # jit donates but the registry says nothing is donated: the
        # declared contract is stale in the other direction
        def f(a):
            return a + 1

        p = prog(jax.jit(f, donate_argnums=(0,)),
                 (SDS((8,), jnp.float32),), donate=())
        live, _ = run_rules([p], rules=("donation-efficacy",))
        assert len(live) == 1 and "stale" in live[0].message

    def test_pytree_donation_counts_leaves(self):
        # a donated pytree (the KV pool shape) counts every array leaf
        def f(kv, x):
            return [{k: v + x for k, v in layer.items()} for layer in kv], x

        kv = [{"k": SDS((4, 4), jnp.bfloat16),
               "v": SDS((4, 4), jnp.bfloat16)} for _ in range(2)]
        p = prog(jax.jit(f, donate_argnums=(0,)),
                 (kv, SDS((), jnp.bfloat16)), donate=(0,))
        assert p.expected_donated_leaves() == 4
        live, _ = run_rules([p], rules=("donation-efficacy",))
        assert live == []


# -- dtype-drift -------------------------------------------------------------

class TestDtypeDrift:
    def test_nonweak_f32_scalar_promotes_bf16(self):
        def f(x):
            return x * jnp.float32(1.5)

        p = prog(jax.jit(f), (SDS((8,), jnp.bfloat16),))
        live, _ = run_rules([p], rules=("dtype-drift",))
        assert len(live) == 1
        assert "implicit bf16->f32 promotion at `mul`" in live[0].message

    def test_np_scalar_promotes_too(self):
        def f(x):
            return x + np.float32(2.0)

        p = prog(jax.jit(f), (SDS((8,), jnp.bfloat16),))
        live, _ = run_rules([p], rules=("dtype-drift",))
        assert len(live) == 1

    def test_python_scalar_stays_weak_and_clean(self):
        def f(x):
            return x * 1.5 + 2.0

        p = prog(jax.jit(f), (SDS((8,), jnp.bfloat16),))
        live, _ = run_rules([p], rules=("dtype-drift",))
        assert live == []

    def test_explicit_astype_island_is_clean(self):
        # the rmsnorm idiom: deliberate f32 compute behind an astype,
        # scaled by an f32 scalar, cast back down — not drift
        def f(x):
            x32 = x.astype(jnp.float32) * np.float32(0.5)
            return (x32 * jax.lax.rsqrt(jnp.mean(x32 * x32) + 1e-5)
                    ).astype(x.dtype)

        p = prog(jax.jit(f), (SDS((8,), jnp.bfloat16),))
        live, _ = run_rules([p], rules=("dtype-drift",))
        assert live == []

    def test_undeclared_program_not_checked(self):
        def f(x):
            return x * jnp.float32(1.5)

        p = prog(jax.jit(f), (SDS((8,), jnp.bfloat16),))
        c = Contract(ir=IrSpec(bf16_programs=("something-else",)))
        fs = irrules.check([p], c, rules=("dtype-drift",),
                           modules=FIXTURE_MOD)
        assert fs == []


# -- collective-schedule -----------------------------------------------------

def _sp_mesh():
    return build_mesh("sp=2", devices=jax.devices()[:2])


def _collective_prog(key, order):
    from jax.sharding import PartitionSpec as P

    mesh = _sp_mesh()

    def inner(x):
        for what in order:
            if what == "psum":
                x = jax.lax.psum(x, "sp")
            else:
                x = jax.lax.ppermute(x, "sp", [(0, 1), (1, 0)])
        return x

    def f(x):
        return jax.shard_map(inner, mesh=mesh, in_specs=P("sp"),
                         out_specs=P(None) if order[-1] == "psum"
                         else P("sp"))(x)

    return prog(jax.jit(f), (SDS((4,), jnp.float32),), key=key)


class TestCollectiveSchedule:
    def test_reordered_two_rank_pair_flagged(self):
        # the deadlock class: two programs of one composition issue the
        # same collectives in different orders
        a = _collective_prog("rank_a", ("psum", "ppermute"))
        b = _collective_prog("rank_b", ("ppermute", "psum"))
        c = Contract(ir=IrSpec(
            compositions={"fix-pair": ("rank_a", "rank_b")}))
        fs = irrules.check([a, b], c, rules=("collective-schedule",),
                           modules=FIXTURE_MOD)
        assert len(fs) == 1
        assert "diverge" in fs[0].message and "hang" in fs[0].message
        assert fs[0].context == "fix-pair"

    def test_matching_pair_is_clean(self):
        a = _collective_prog("rank_a", ("psum", "ppermute"))
        b = _collective_prog("rank_b", ("psum", "ppermute"))
        c = Contract(ir=IrSpec(
            compositions={"fix-pair": ("rank_a", "rank_b")}))
        fs = irrules.check([a, b], c, rules=("collective-schedule",),
                           modules=FIXTURE_MOD)
        assert fs == []

    def test_partial_composition_skipped(self):
        # a --keys subset that builds one member must not judge the pair
        a = _collective_prog("rank_a", ("psum", "ppermute"))
        c = Contract(ir=IrSpec(
            compositions={"fix-pair": ("rank_a", "rank_b")}))
        fs = irrules.check([a], c, rules=("collective-schedule",),
                           modules=FIXTURE_MOD)
        assert fs == []

    def test_pbroadcast_bookkeeping_ignored(self):
        # shard_map's varying-manifest pcasts are not wire traffic; two
        # programs differing only in them must compare equal
        a = _collective_prog("rank_a", ("ppermute",))
        assert all(e[0] != "pbroadcast" for e in a.jaxpr_schedule())


# -- host-interop ------------------------------------------------------------

class TestHostInterop:
    def test_debug_print_in_hot_executable(self):
        def f(x):
            jax.debug.print("x={x}", x=x)
            return x + 1

        p = prog(jax.jit(f), (SDS((4,), jnp.float32),))
        live, _ = run_rules([p], rules=("host-interop",))
        assert len(live) == 1
        assert "debug_print" in live[0].message

    def test_pure_callback_flagged_and_cold_program_exempt(self):
        def f(x):
            return jax.pure_callback(
                lambda a: np.asarray(a) + 1,
                jax.ShapeDtypeStruct((4,), np.float32), x)

        p = prog(jax.jit(f), (SDS((4,), jnp.float32),))
        live, _ = run_rules([p], rules=("host-interop",))
        assert len(live) == 1 and "pure_callback" in live[0].message
        cold = Contract(ir=IrSpec(hot_programs=("other",)))
        assert irrules.check([p], cold, rules=("host-interop",),
                             modules=FIXTURE_MOD) == []


# -- baked-constants ---------------------------------------------------------

class TestBakedConstants:
    def test_oversized_closed_over_array(self):
        big = jnp.arange(64 * 1024, dtype=jnp.float32)  # 256 KiB

        def f(x):
            return x + big.sum()

        p = prog(jax.jit(f), (SDS((), jnp.float32),))
        live, _ = run_rules([p], rules=("baked-constants",))
        assert len(live) == 1
        assert "262144 bytes" in live[0].message

    def test_small_consts_are_fine(self):
        small = jnp.arange(8, dtype=jnp.float32)

        def f(x):
            return x + small.sum()

        p = prog(jax.jit(f), (SDS((), jnp.float32),))
        live, _ = run_rules([p], rules=("baked-constants",))
        assert live == []

    def test_allow_anchored_at_factory_def(self):
        big = jnp.arange(64 * 1024, dtype=jnp.float32)

        def f(x):
            return x + big.sum()

        p = prog(jax.jit(f), (SDS((), jnp.float32),),
                 factory="make_allowed")
        live, allowed = run_rules([p], rules=("baked-constants",))
        assert live == [] and len(allowed) == 1
        assert allowed[0].reason.startswith("lookup table")


# -- the live tree -----------------------------------------------------------

@pytest.fixture(scope="module")
def live_findings():
    return run_ir()


class TestLiveTree:
    def test_registry_covers_contract_and_builds(self):
        progs = factories.build_programs(DEFAULT_CONTRACT)
        assert {p.key for p in progs} == set(DEFAULT_CONTRACT.ir.programs)
        # every composition member is a registered program
        for name, members in DEFAULT_CONTRACT.ir.compositions.items():
            assert set(members) <= set(DEFAULT_CONTRACT.ir.programs), name

    @pytest.mark.slow  # tier-1 budget: see scripts/check_tier1_budget.py
    def test_live_tree_is_clean(self, live_findings):
        fresh = [f for f in live_findings if not f.allowed]
        assert not fresh, "\n".join(f.render() for f in fresh)

    def test_live_decode_disciplines_schedules_compared(self):
        # the decode composition actually compares COMPILED schedules
        # (dense TP collectives are SPMD-inserted, invisible at jaxpr
        # level) — guard that the members stay compiled-on-CPU
        progs = {p.key: p for p in factories.build_programs(
            DEFAULT_CONTRACT,
            DEFAULT_CONTRACT.ir.compositions["decode-disciplines@tp2"])}
        for p in progs.values():
            p.prepare()
        scheds = [p.compiled_schedule() for p in progs.values()]
        assert all(s is not None for s in scheds)
        assert scheds[0] and scheds[0] == scheds[1]

    def test_live_donation_aliases_match_declarations(self):
        # the feedback decode donates kv pool + position buffer; the
        # artifact roundtrip preserves all four kv aliases
        progs = {p.key: p.prepare() for p in factories.build_programs(
            DEFAULT_CONTRACT, ("decode", "decode_feedback",
                               "aot_decode_export"))}
        assert progs["decode"].lowered_alias_count() == 4
        assert progs["decode_feedback"].lowered_alias_count() == 5
        assert progs["aot_decode_export"].lowered_alias_count() == 4


# -- CLI ---------------------------------------------------------------------

class TestCli:
    def test_ir_cli_subset_json_contract(self):
        # exit/JSON contract on a fast subset (full-registry run is the
        # slow-marked test below; the driver's acceptance run uses it)
        r = subprocess.run(
            [sys.executable, os.path.join(ROOT, "scripts", "shai_lint.py"),
             "--ir", "--keys", "decode, decode_feedback", "--json"],
            capture_output=True, text=True, cwd=ROOT, timeout=120)
        assert r.returncode == 0, r.stdout + r.stderr
        payload = json.loads(r.stdout)
        assert payload["pass"] == "ir"
        assert payload["new"] == []
        assert payload["stale_baseline"] == []

    @pytest.mark.slow
    def test_ir_cli_full_registry_under_budget(self):
        r = subprocess.run(
            [sys.executable, os.path.join(ROOT, "scripts", "shai_lint.py"),
             "--ir", "--json"],
            capture_output=True, text=True, cwd=ROOT, timeout=120)
        assert r.returncode == 0, r.stdout + r.stderr
        payload = json.loads(r.stdout)
        assert payload["new"] == []
        # acceptance: every registered factory lowered/checked in < 60s
        assert payload["elapsed_s"] < 60.0

    def test_ir_cli_unknown_key_is_exit_2(self):
        r = subprocess.run(
            [sys.executable, os.path.join(ROOT, "scripts", "shai_lint.py"),
             "--ir", "--keys", "nope"],
            capture_output=True, text=True, cwd=ROOT, timeout=120)
        assert r.returncode == 2, r.stdout + r.stderr
        assert "internal error" in r.stderr
