"""shai-lint: the AST invariant checkers (analysis/) — fixture snippets
prove each rule catches a seeded violation (and stays quiet on the legal
idiom / a valid allow annotation), the live tree stays clean, and a fresh
run matches the committed baseline.

Pure-AST and CPU-only: no jax execution anywhere in this file.
"""

import dataclasses
import json
import os
import subprocess
import sys
import textwrap

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from scalable_hw_agnostic_inference_tpu.analysis import (  # noqa: E402
    DEFAULT_CONTRACT,
    Module,
    run_all,
)
from scalable_hw_agnostic_inference_tpu.analysis import (  # noqa: E402
    core as lint_core,
)
from scalable_hw_agnostic_inference_tpu.analysis import (  # noqa: E402
    donation,
    envknobs,
    hostsync,
    routes,
    threads,
)
from scalable_hw_agnostic_inference_tpu.analysis.contract import (  # noqa: E402
    ClassPolicy,
    Contract,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def mod(relpath: str, src: str) -> Module:
    return Module(relpath, textwrap.dedent(src))


def live(findings):
    return [f for f in findings if not f.allowed]


# -- host-sync ---------------------------------------------------------------

HOT = dataclasses.replace(
    Contract(), hot_paths={"engine/engine.py": ("Engine._steady",)})


class TestHostSync:
    def test_positive_each_pattern(self):
        m = mod("engine/engine.py", """\
            import numpy as np
            import jax

            class Engine:
                def _steady(self, pipe):
                    a = np.asarray(pipe.nxt)
                    b = pipe.nxt.item()
                    c = pipe.nxt.tolist()
                    d = jax.device_get(pipe.nxt)
                    pipe.nxt.block_until_ready()
                    e = int(pipe.pos)
                    return a, b, c, d, e
            """)
        found = live(hostsync.check([m], HOT))
        kinds = sorted(f.message for f in found)
        assert len(found) == 6, kinds
        assert all(f.context == "Engine._steady" for f in found)

    def test_negative_outside_hot_path_and_benign_calls(self):
        m = mod("engine/engine.py", """\
            import numpy as np

            class Engine:
                def _steady(self, running):
                    t = np.zeros((4,), np.int32)   # host alloc: fine
                    n = int(len(running))          # len(): fine
                    k = int(4)                     # literal: fine
                    return t, n, k

                def _event_path(self, pipe):
                    return np.asarray(pipe.nxt)    # not a hot path
            """)
        assert live(hostsync.check([m], HOT)) == []

    def test_nested_defs_inherit_hot_scope(self):
        m = mod("engine/engine.py", """\
            import numpy as np

            class Engine:
                def _steady(self, pipe):
                    def inner():
                        return np.asarray(pipe.nxt)
                    return inner()
            """)
        found = live(hostsync.check([m], HOT))
        assert len(found) == 1

    def test_async_def_hot_path_and_nested_async_inherit(self):
        c = dataclasses.replace(
            Contract(), hot_paths={"engine/engine.py": ("Engine._steady",)})
        m = mod("engine/engine.py", """\
            import numpy as np

            class Engine:
                async def _steady(self, pipe):
                    a = np.asarray(pipe.nxt)

                    async def inner():
                        return np.asarray(pipe.top)
                    return a, await inner()

                async def _event(self, pipe):
                    return np.asarray(pipe.nxt)   # not hot
            """)
        found = live(hostsync.check([m], c))
        assert len(found) == 2
        assert all(f.context.startswith("Engine._steady") for f in found)

    def test_lambda_assigned_to_hot_name_inherits_scope(self):
        # a hot path rebound as `name = lambda ...` is the same contract
        m = mod("engine/engine.py", """\
            import numpy as np

            class Engine:
                _steady = lambda self, pipe: np.asarray(pipe.nxt)
            """)
        found = live(hostsync.check([m], HOT))
        assert len(found) == 1 and found[0].context == "Engine._steady"

    def test_lambda_nested_in_hot_body_inherits_scope(self):
        m = mod("engine/engine.py", """\
            import numpy as np

            class Engine:
                def _steady(self, running):
                    pull = lambda s: np.asarray(s.nxt)
                    return [pull(s) for s in running]
            """)
        found = live(hostsync.check([m], HOT))
        assert len(found) == 1

    def test_module_level_lambda_under_star_scope(self):
        c = dataclasses.replace(
            Contract(), hot_paths={"engine/resident.py": ("*",)})
        m = mod("engine/resident.py", """\
            import numpy as np

            fetch = lambda x: np.asarray(x)
            """)
        found = live(hostsync.check([m], c))
        assert len(found) == 1 and found[0].context == "fetch"

    def test_allowlisted_with_reason_and_without(self):
        m = mod("engine/engine.py", """\
            import numpy as np

            class Engine:
                def _steady(self, pipe):
                    # shai-lint: allow(host-sync) the one blocking fetch
                    a = np.asarray(pipe.nxt)
                    # shai-lint: allow(host-sync)
                    b = np.asarray(pipe.top)
                    return a, b
            """)
        found = hostsync.check([m], HOT)
        allowed = [f for f in found if f.allowed]
        still_live = live(found)
        assert len(allowed) == 1 and allowed[0].reason
        # reason-less allow comment does NOT suppress; the finding says why
        assert len(still_live) == 1
        assert "missing its required reason" in still_live[0].message

    def test_star_covers_whole_module(self):
        c = dataclasses.replace(
            Contract(), hot_paths={"engine/resident.py": ("*",)})
        m = mod("engine/resident.py", """\
            import numpy as np

            def anything(x):
                return np.asarray(x)
            """)
        assert len(live(hostsync.check([m], c))) == 1


# -- donation ----------------------------------------------------------------

DON = dataclasses.replace(
    Contract(),
    donation_factory_files=("engine/runner.py",),
    donation_check_files=("engine/engine.py", "engine/runner.py"),
    accessor_factories={"_decode_for": ("make_decode", 1)},
)

RUNNER_SRC = """\
    import jax

    def make_decode(feedback=False):
        def decode(params, kv, tokens, pos):
            return kv, tokens, pos
        donate = (1, 3) if feedback else (1,)
        return jax.jit(decode, donate_argnums=donate)
    """


class TestDonation:
    def test_factory_registry_resolves_conditional_donations(self):
        m = mod("engine/runner.py", RUNNER_SRC)
        reg = donation.factory_registry([m], DON)
        assert reg == {"make_decode": frozenset({1, 3})}

    def test_intra_scope_read_after_donation_flagged(self):
        m = mod("engine/engine.py", """\
            import jax

            def step(params, kv, tokens, pos):
                f = jax.jit(lambda p, k: k, donate_argnums=(1,))
                out = f(params, kv)
                return kv.shape  # read after donation
            """)
        found = live(donation.check([m], DON))
        assert len(found) == 1
        assert "`kv`" in found[0].message

    def test_donate_and_rebind_idiom_is_clean(self):
        m = mod("engine/engine.py", """\
            import jax

            def step(params, kv, tokens, pos):
                f = jax.jit(lambda p, k: (k, 1), donate_argnums=(1,))
                kv, logits = f(params, kv)
                return kv.shape  # rebound by the donating statement
            """)
        assert live(donation.check([m], DON)) == []

    def test_star_args_list_and_accessor_resolution(self):
        m = mod("engine/engine.py", """\
            class Engine:
                def _decode_step(self):
                    _, decode = self._decode_for(2)
                    args = [self.params, self.cache.kv]
                    args += [self.tokens, self.pos_dev]
                    out = decode(*args)
                    x = self.pos_dev      # donated position 3: flagged
                    y = self.tokens       # position 2 is NOT donated
                    z = self.cache.kv     # donated position 1: flagged
                    return out, x, y, z
            """)
        r = mod("engine/runner.py", RUNNER_SRC)
        found = live(donation.check([m, r], DON))
        assert len(found) == 2
        paths = {f.message.split("`")[1] for f in found}
        assert paths == {"self.cache.kv", "self.pos_dev"}

    def test_star_args_rebound_kv_is_clean(self):
        m = mod("engine/engine.py", """\
            class Engine:
                def _decode_step(self):
                    _, decode = self._decode_for(2)
                    args = [self.params, self.cache.kv, self.tokens,
                            self.pos_dev]
                    self.cache.kv, nxt, pos = decode(*args)
                    self.pos_dev = None
                    return nxt
            """)
        r = mod("engine/runner.py", RUNNER_SRC)
        assert live(donation.check([m, r], DON)) == []

    def test_allow_annotation(self):
        m = mod("engine/engine.py", """\
            import jax

            def step(params, kv):
                f = jax.jit(lambda p, k: k, donate_argnums=(1,))
                out = f(params, kv)
                # shai-lint: allow(donation) deliberate aliasing test
                return kv.shape
            """)
        found = donation.check([m], DON)
        assert len(found) == 1 and found[0].allowed

    def test_declared_donating_call(self):
        c = dataclasses.replace(
            DON, donating_calls={"_dispatch_async": (4,)})
        m = mod("engine/engine.py", """\
            class Engine:
                def _steady_step(self, decode, running):
                    tokens_dev, pos_dev = self.prev.nxt, self.prev.pos_next
                    self._dispatch_async(decode, running, 2, tokens_dev,
                                         pos_dev, {}, None)
                    return pos_dev  # donated onward: flagged
            """)
        found = live(donation.check([m], c))
        assert len(found) == 1 and "`pos_dev`" in found[0].message


# -- thread discipline -------------------------------------------------------

THR = dataclasses.replace(
    Contract(),
    thread_contract={
        "Loop": ClassPolicy(
            immutable_after_init=("engine",),
            lock_guarded={"_futures": "_futures_lock"},
            owning_modules=("engine/loop.py",),
            instance_markers=(".loop.",),
        ),
        "Engine": ClassPolicy(
            owning_modules=("engine/engine.py",),
            instance_markers=("engine.",),
        ),
    },
    dict_guards={"serve/app.py": {"state": (("inflight",),
                                            "inflight_lock")}},
)


class TestThreadDiscipline:
    def test_lock_guarded_write_outside_lock_flagged(self):
        m = mod("engine/loop.py", """\
            class Loop:
                def __init__(self):
                    self._futures = {}

                def bad(self, rid, fut):
                    self._futures[rid] = fut

                def also_bad(self, rid):
                    self._futures.pop(rid, None)

                def good(self, rid, fut):
                    with self._futures_lock:
                        self._futures[rid] = fut

                def good_mutator(self):
                    with self._futures_lock:
                        self._futures.clear()
            """)
        found = live(threads.check([m], THR))
        assert len(found) == 2
        assert {f.context for f in found} == {"Loop.bad", "Loop.also_bad"}

    def test_immutable_after_init_rebind_flagged(self):
        m = mod("engine/loop.py", """\
            class Loop:
                def __init__(self, engine):
                    self.engine = engine

                def hot_swap(self, engine):
                    self.engine = engine  # rebinding the engine mid-flight
            """)
        found = live(threads.check([m], THR))
        assert len(found) == 1 and found[0].context == "Loop.hot_swap"

    def test_method_calls_on_immutable_objects_are_fine(self):
        m = mod("engine/loop.py", """\
            class Loop:
                def __init__(self, engine):
                    self.engine = engine

                def fine(self):
                    self.engine.step()
            """)
        assert live(threads.check([m], THR)) == []

    def test_external_write_from_non_owning_module_flagged(self):
        m = mod("serve/handlers.py", """\
            def hack(service):
                service.loop.engine = None
                engine.waiting.append("req")
            """)
        found = live(threads.check([m], THR))
        assert len(found) == 2

    def test_external_write_from_owning_module_ok(self):
        m = mod("engine/engine.py", """\
            def helper(engine):
                engine.waiting.append("req")
            """)
        assert live(threads.check([m], THR)) == []

    def test_dict_guard(self):
        m = mod("serve/app.py", """\
            def make(state, inflight_lock):
                def bad():
                    state["inflight"] += 1

                def good():
                    with inflight_lock:
                        state["inflight"] += 1

                def unguarded_key():
                    state["loaded"] = True
                return bad, good, unguarded_key
            """)
        found = live(threads.check([m], THR))
        assert len(found) == 1 and found[0].context == "bad"

    def test_allow_annotation(self):
        m = mod("serve/handlers.py", """\
            def boot(engine):
                # shai-lint: allow(thread) boot-time, loop not started yet
                engine.waiting.append("warm")
            """)
        found = threads.check([m], THR)
        assert len(found) == 1 and found[0].allowed

    def test_class_body_lambda_mutator_checked(self):
        # a lock-guarded mutation hidden in a class-level lambda is a
        # write site like any other
        m = mod("engine/loop.py", """\
            class Loop:
                def __init__(self):
                    self._futures = {}

                flush = lambda self: self._futures.clear()
            """)
        found = live(threads.check([m], THR))
        assert len(found) == 1 and found[0].context == "Loop.flush"

    def test_annotated_class_body_lambda_checked(self):
        m = mod("engine/loop.py", """\
            class Loop:
                def __init__(self):
                    self._futures = {}

                flush: object = lambda self: self._futures.clear()
            """)
        found = live(threads.check([m], THR))
        assert len(found) == 1 and found[0].context == "Loop.flush"


# -- env knobs ---------------------------------------------------------------

ENV = dataclasses.replace(
    Contract(),
    env_parser_modules=("obs/util.py",),
    env_exempt_modules={"perf/topo.py": "save/restore helper"},
)


class TestEnvKnobs:
    def test_raw_int_cast_is_env_parse(self):
        m = mod("serve/x.py", """\
            import os
            N = int(os.environ.get("SHAI_FAKE_KNOB_X", "8"))
            """)
        found = live(envknobs.check([m], ENV, "SHAI_FAKE_KNOB_X docs"))
        assert [f.rule for f in found] == ["env-parse"]
        assert found[0].context == "SHAI_FAKE_KNOB_X"

    def test_raw_read_is_env_read_and_parser_call_is_not(self):
        m = mod("serve/x.py", """\
            import os
            from ..obs.util import env_int
            A = os.environ.get("SHAI_FAKE_A", "")
            B = env_int("SHAI_FAKE_B", 4)
            """)
        found = live(envknobs.check([m], ENV, "SHAI_FAKE_A SHAI_FAKE_B"))
        assert [f.rule for f in found] == ["env-read"]
        assert found[0].context == "SHAI_FAKE_A"

    def test_subscript_read_and_constant_name_resolution(self):
        m = mod("serve/x.py", """\
            import os
            ENV_NAME = "SHAI_FAKE_SUB"
            V = os.environ[ENV_NAME]
            """)
        found = live(envknobs.check([m], ENV, "SHAI_FAKE_SUB"))
        assert [f.rule for f in found] == ["env-read"]
        assert found[0].context == "SHAI_FAKE_SUB"

    def test_undocumented_name_is_env_doc(self):
        m = mod("serve/x.py", """\
            from ..obs.util import env_int
            B = env_int("SHAI_FAKE_UNDOCUMENTED", 4)
            """)
        found = live(envknobs.check([m], ENV, "no mention here"))
        assert [f.rule for f in found] == ["env-doc"]
        # documented -> clean
        assert live(envknobs.check(
            [m], ENV, "knob: SHAI_FAKE_UNDOCUMENTED")) == []

    def test_shai_literal_anywhere_needs_docs(self):
        m = mod("serve/x.py", '''\
            """Reads ``SHAI_FAKE_DOCSTRING_ONLY`` at boot."""
            ''')
        found = live(envknobs.check([m], ENV, ""))
        assert [f.rule for f in found] == ["env-doc"]

    def test_parser_module_knobs_still_need_docs(self):
        """The ServeConfig gap: knobs read THROUGH the parsers inside a
        parser module (utils/env.py) are exempt from the read rules but
        NOT from the documentation rule."""
        m = mod("obs/util.py", """\
            import os

            def env_int(name, default):
                return int(os.environ.get(name, default))

            PORT = env_int("SHAI_FAKE_PARSERMOD_KNOB", 8000)
            """)
        found = live(envknobs.check([m], ENV, "no docs"))
        assert [f.rule for f in found] == ["env-doc"]
        assert found[0].context == "SHAI_FAKE_PARSERMOD_KNOB"

    def test_sub_rule_name_in_allow_comment_works(self):
        m = mod("serve/x.py", """\
            import os
            # shai-lint: allow(env-parse) deliberate strict parse
            A = int(os.environ.get("SHAI_FAKE_STRICT", "1"))
            # shai-lint: allow(env-read) raw string gate by design
            B = os.environ.get("SHAI_FAKE_RAW", "")
            """)
        found = envknobs.check(
            [m], ENV, "SHAI_FAKE_STRICT SHAI_FAKE_RAW")
        assert len(found) == 2 and all(f.allowed for f in found)

    def test_exempt_module_and_allow_comment(self):
        topo = mod("perf/topo.py", """\
            import os
            V = int(os.environ.get("WHATEVER", "1"))
            """)
        annotated = mod("serve/x.py", """\
            import os
            # shai-lint: allow(env-knob) platform var, not a serving knob
            F = os.environ.get("XLA_FLAGS", "")
            """)
        c = dataclasses.replace(ENV, env_doc_exempt=("XLA_FLAGS",
                                                     "WHATEVER"))
        assert live(envknobs.check([topo, annotated], c, "")) == []


class TestEnvDeploy:
    def test_typod_manifest_knob_flagged(self):
        # code reads SHAI_REAL; the manifest sets SHAI_REAL and a typo —
        # the typo applies fine on the cluster and no pod ever reads it
        m = mod("serve/x.py", """\
            from ..obs.util import env_int
            A = env_int("SHAI_REAL", 1)
            """)
        deploy = {"SHAI_REAL": ("deploy/units/x-deploy.yaml", 10),
                  "SHAI_RAEL": ("deploy/units/x-deploy.yaml", 11)}
        found = live(envknobs.check([m], ENV, "SHAI_REAL SHAI_RAEL",
                                    deploy_names=deploy))
        assert [f.rule for f in found] == ["env-deploy"]
        assert found[0].context == "SHAI_RAEL"
        assert found[0].path == "deploy/units/x-deploy.yaml"

    def test_read_name_in_manifest_is_clean(self):
        m = mod("serve/x.py", """\
            from ..obs.util import env_int
            A = env_int("SHAI_REAL", 1)
            """)
        deploy = {"SHAI_REAL": ("deploy/units/x-deploy.yaml", 10)}
        assert live(envknobs.check([m], ENV, "SHAI_REAL",
                                   deploy_names=deploy)) == []

    def test_live_deploy_names_all_read_by_code(self):
        """Every SHAI_* name a committed manifest sets resolves to a code
        read site (the live half of the env-deploy rule)."""
        names = lint_core.deploy_env_names()
        assert names, "deploy/ scan found no SHAI_ names — scanner broken?"
        found = live(envknobs.check(lint_core.iter_modules(),
                                    DEFAULT_CONTRACT, "ignored",
                                    deploy_names=names))
        deploy_findings = [f for f in found if f.rule == "env-deploy"]
        assert deploy_findings == [], "\n".join(
            f.render() for f in deploy_findings)


# -- rename-stable fingerprints ----------------------------------------------

class TestFingerprintStability:
    SRC = """\
        import numpy as np

        class Engine:
            def _steady(self, pipe):
                return np.asarray(pipe.nxt)
        """

    def test_fingerprint_survives_file_move(self):
        c = dataclasses.replace(
            Contract(),
            hot_paths={"engine/engine.py": ("Engine._steady",),
                       "engine/moved_engine.py": ("Engine._steady",)})
        before = live(hostsync.check([mod("engine/engine.py",
                                          self.SRC)], c))
        after = live(hostsync.check([mod("engine/moved_engine.py",
                                         self.SRC)], c))
        assert len(before) == len(after) == 1
        # identity is (rule, context, message, snippet) — path-free
        assert before[0].fingerprint == after[0].fingerprint
        assert "engine/engine.py" not in before[0].fingerprint

    def test_old_path_keyed_entries_go_stale_not_resurrected(self, tmp_path):
        """Migration: a version-1 baseline entry (path in the fingerprint)
        never matches a fresh finding — it reports as stale debt, and the
        finding it used to cover shows up as NEW (so it gets fixed or
        annotated, not silently inherited under a moved path)."""
        old_fp = ("host-sync|engine/engine.py|Engine._steady|"
                  "host sync numpy.asarray(...) in declared hot path")
        bl = tmp_path / "baseline.json"
        bl.write_text(json.dumps({"version": 1, "findings": [old_fp]}))
        loaded = set(lint_core.load_baseline(str(bl)))
        c = dataclasses.replace(
            Contract(), hot_paths={"engine/engine.py": ("Engine._steady",)})
        fresh = {f.fingerprint
                 for f in live(hostsync.check(
                     [mod("engine/engine.py", self.SRC)], c))}
        assert old_fp in loaded and not (fresh & loaded)

    def test_update_baseline_writes_version_2(self, tmp_path):
        bl = tmp_path / "baseline.json"
        f = lint_core.Finding(rule="host-sync", path="a.py", line=3,
                              context="X.y", message="m", snippet="s")
        lint_core.save_baseline([f], str(bl))
        data = json.loads(bl.read_text())
        assert data["version"] == 2
        assert data["findings"] == [f.fingerprint]


# -- trace exclusion ---------------------------------------------------------

TRC = dataclasses.replace(
    Contract(),
    trace_files=("serve/app.py", "serve/asgi.py"),
    poll_routes=("/profile", "/stats"),
)


class TestTraceExclude:
    def test_missing_debug_route_flagged(self):
        asgi = mod("serve/asgi.py", """\
            class App:
                def __init__(self):
                    self.trace_exclude = {"/health"}
            """)
        app = mod("serve/app.py", """\
            def create_app(app):
                app.trace_exclude |= {"/profile"}

                @app.get("/debug/flight")
                def flight(request):
                    return {}

                @app.get("/profile")
                def prof(request):
                    return {}

                @app.get("/stats")
                def stats(request):
                    return {}

                @app.get("/genimage")
                def task(request):
                    return {}
            """)
        found = live(routes.check([asgi, app], TRC))
        assert {f.context for f in found} == {"/debug/flight", "/stats"}

    def test_excluded_routes_are_clean(self):
        asgi = mod("serve/asgi.py", """\
            class App:
                def __init__(self):
                    self.trace_exclude = {"/stats", "/debug/flight"}
            """)
        app = mod("serve/app.py", """\
            def create_app(app):
                @app.get("/debug/flight")
                def flight(request):
                    return {}

                @app.get("/stats")
                def stats(request):
                    return {}
            """)
        assert live(routes.check([asgi, app], TRC)) == []

    def test_parameterized_poll_route_covered_by_literal_exclude(self):
        """PR 18: ``/trace/{trace_id}`` is poll-class but parameterized —
        the rule must accept the LITERAL pattern string in trace_exclude
        (the asgi layer compiles it at match time) and flag its absence."""
        trc = dataclasses.replace(
            Contract(),
            trace_files=("serve/app.py", "serve/asgi.py"),
            poll_routes=("/stats", "/trace/{trace_id}"),
        )
        asgi = mod("serve/asgi.py", """\
            class App:
                def __init__(self):
                    self.trace_exclude = {"/stats"}
            """)
        covered = mod("serve/app.py", """\
            def create_app(app):
                app.trace_exclude |= {"/trace/{trace_id}"}

                @app.get("/trace/{trace_id}")
                def trace_by_id(request, trace_id):
                    return {}
            """)
        assert live(routes.check([asgi, covered], trc)) == []
        missing = mod("serve/app.py", """\
            def create_app(app):
                @app.get("/trace/{trace_id}")
                def trace_by_id(request, trace_id):
                    return {}
            """)
        found = live(routes.check([asgi, missing], trc))
        assert {f.context for f in found} == {"/trace/{trace_id}"}


# -- the live tree -----------------------------------------------------------

class TestLiveTree:
    def test_live_tree_is_clean_and_intentional_syncs_annotated(self):
        findings = run_all()
        fresh = [f for f in findings if not f.allowed]
        assert not fresh, "\n".join(f.render() for f in fresh)
        # the one blocking fetch of the async pipeline stays DOCUMENTED:
        # if someone deletes the annotation (or the fetch moves), this
        # test points straight at the contract
        allowed = [f for f in findings if f.allowed]
        assert any(f.rule == "host-sync"
                   and f.context == "LLMEngine._retire_pipe"
                   for f in allowed)

    def test_fresh_run_matches_committed_baseline(self):
        """--update-baseline regression: the committed baseline equals a
        fresh run exactly (no stale entries, no missing ones). The live
        tree is clean, so the committed baseline must be empty — debt is
        either fixed or allow-annotated, never silently inherited."""
        fresh = {f.fingerprint for f in run_all() if not f.allowed}
        committed = set(lint_core.load_baseline())
        assert fresh == committed
        assert committed == set(), (
            "the baseline is expected to stay empty; run "
            "scripts/shai_lint.py --update-baseline only when inheriting "
            "debt wholesale and update this test's expectation")

    def test_factory_registry_sees_the_real_donations(self):
        """The donation checker's ground truth: the engine's executable
        factories donate exactly the documented positions (kv pool always;
        the feedback decode additionally donates the position buffer)."""
        mods = [m for m in lint_core.iter_modules()
                if m.relpath in DEFAULT_CONTRACT.donation_factory_files]
        reg = donation.factory_registry(mods, DEFAULT_CONTRACT)
        assert reg["make_prefill"] == frozenset({1})
        assert reg["make_prefill_cont"] == frozenset({1})
        assert reg["make_verify"] == frozenset({1})
        assert reg["make_decode"] == frozenset({1, 3})
        assert reg["make_cross_slot_write"] == frozenset({0})

    def test_live_get_routes_all_covered(self):
        """Every /debug + poll GET route in serve/app.py is actually seen
        by the route scanner (a refactor that moves registration behind a
        helper must update the checker, not silently pass)."""
        mods = [m for m in lint_core.iter_modules()
                if m.relpath in DEFAULT_CONTRACT.trace_files]
        app = next(m for m in mods if m.relpath == "serve/app.py")
        patterns = {p for p, _ in routes._get_routes(app)}
        assert {"/debug/flight", "/debug/conformance", "/debug/faults",
                "/profile", "/stats", "/metrics", "/health"} <= patterns


# -- CLI ---------------------------------------------------------------------

class TestCli:
    def test_cli_gate_green_json_contract(self):
        r = subprocess.run(
            [sys.executable, os.path.join(ROOT, "scripts", "shai_lint.py"),
             "--json"],
            capture_output=True, text=True, cwd=ROOT, timeout=60)
        assert r.returncode == 0, r.stdout + r.stderr
        payload = json.loads(r.stdout)
        assert payload["new"] == []
        assert payload["stale_baseline"] == []
        # acceptance: whole-tree run comfortably under the 10 s budget
        assert payload["elapsed_s"] < 10.0
        # the intentional annotations are visible to tooling
        assert any(f["rule"] == "host-sync" for f in payload["allowed"])

    def test_cli_rule_filter(self):
        r = subprocess.run(
            [sys.executable, os.path.join(ROOT, "scripts", "shai_lint.py"),
             "--rule", "env-doc"],
            capture_output=True, text=True, cwd=ROOT, timeout=60)
        assert r.returncode == 0, r.stdout + r.stderr

    def test_cli_changed_mode_green_and_fast(self):
        """--changed lints only git-touched files (pre-commit speed); on a
        tree whose changed files are clean it exits 0. Staleness is not
        judged from the partial view."""
        r = subprocess.run(
            [sys.executable, os.path.join(ROOT, "scripts", "shai_lint.py"),
             "--changed", "--json"],
            capture_output=True, text=True, cwd=ROOT, timeout=60)
        assert r.returncode == 0, r.stdout + r.stderr
        payload = json.loads(r.stdout)
        assert payload["new"] == []
        assert payload["stale_baseline"] == []

    def test_check_all_fast_combined_gate(self):
        """scripts/check_all.py --fast: AST + metrics docs under one exit
        code (the full gate adds the IR pass and the tier-1 budget)."""
        r = subprocess.run(
            [sys.executable, os.path.join(ROOT, "scripts", "check_all.py"),
             "--fast"],
            capture_output=True, text=True, cwd=ROOT, timeout=120)
        assert r.returncode == 0, r.stdout + r.stderr
        assert "shai-lint (AST)" in r.stdout and "ok" in r.stdout

    def test_cli_partial_run_cannot_rewrite_baseline(self):
        """--update-baseline on a partial view (--changed / --ir --keys)
        would erase every baselined finding outside the view; the CLI
        refuses with the internal-error code."""
        for extra in (["--changed"], ["--ir", "--keys", "decode"]):
            r = subprocess.run(
                [sys.executable,
                 os.path.join(ROOT, "scripts", "shai_lint.py"),
                 "--update-baseline"] + extra,
                capture_output=True, text=True, cwd=ROOT, timeout=60)
            assert r.returncode == 2, (extra, r.stdout, r.stderr)
            assert "full run" in r.stderr

    def test_cli_corrupt_baseline_is_exit_2(self, tmp_path):
        """The documented exit contract: a corrupt baseline is an internal
        error (2), never mistakable for 'new finding' (1)."""
        bad = tmp_path / "baseline.json"
        bad.write_text("{not json")
        r = subprocess.run(
            [sys.executable, os.path.join(ROOT, "scripts", "shai_lint.py"),
             "--baseline", str(bad)],
            capture_output=True, text=True, cwd=ROOT, timeout=60)
        assert r.returncode == 2, r.stdout + r.stderr
        assert "internal error" in r.stderr
