"""THE declared invariant tables shai-lint checks the tree against.

Every checker reads its ground truth from here, not from heuristics buried
in checker code: which functions are the decode hot path, which callables
donate which argument positions, which attributes of which classes are
loop-thread-only / lock-guarded / immutable-after-init, which env reads
are deliberately strict, which GET routes are poll surfaces. Changing an
invariant is a one-line diff in this file — reviewed as a contract change,
not an incidental checker tweak.

Tests override :data:`DEFAULT_CONTRACT` with fixture-sized tables via
``dataclasses.replace``.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple


@dataclasses.dataclass(frozen=True)
class ClassPolicy:
    """Concurrency contract for one class's attributes.

    Any attribute not listed in ``immutable_after_init`` or
    ``lock_guarded`` is *owner-thread-only* mutable state: it may be
    written only from ``owning_modules`` (for the engine: code that runs
    on the engine-loop thread).
    """

    #: attrs bound in __init__ (or a declared init method) and never again
    immutable_after_init: Tuple[str, ...] = ()
    #: methods that count as construction time (lock/immutability exempt)
    init_methods: Tuple[str, ...] = ("__init__",)
    #: attr -> the ``self.<lock>`` a write site must hold lexically
    lock_guarded: Dict[str, str] = dataclasses.field(default_factory=dict)
    #: repo-relative modules allowed to write the mutable attrs
    owning_modules: Tuple[str, ...] = ()
    #: dotted-path markers identifying an instance at a write site OUTSIDE
    #: the class body (e.g. ``engine.`` / ``eng.`` locals, ``.engine.``
    #: attribute chains). Checked as a prefix or infix of the write path.
    instance_markers: Tuple[str, ...] = ()
    #: lock attributes this class OWNS. Each becomes a lock IDENTITY
    #: ``"<Class>.<attr>"`` in the shai-race acquisition graph
    #: (``analysis/race.py``); defaults to the distinct values of
    #: ``lock_guarded`` when empty, so a class whose only lock guards
    #: attributes needs no duplicate declaration.
    locks: Tuple[str, ...] = ()


@dataclasses.dataclass(frozen=True)
class RaceSpec:
    """Declared tables for the shai-race pass (``analysis/race.py``).

    Lock IDENTITIES are ``"<Class>.<attr>"`` for locks owned by a
    ``thread_contract`` class (``ClassPolicy.locks`` /
    ``lock_guarded`` values, resolved through ``self.<attr>`` inside the
    class body and through ``instance_markers`` outside it) plus the
    module-scope ids declared in :attr:`module_locks` (closure locks
    like ``serve.app``'s ``inflight_lock``).
    """

    #: module relpath -> {with-target dotted name: lock identity} for
    #: locks that live in closures / module scope rather than on a
    #: contract class
    module_locks: Dict[str, Dict[str, str]] = dataclasses.field(
        default_factory=dict)
    #: lock identities declared HOT: a blocking call (queue get/put,
    #: Future.result, Thread.join, Event.wait, time.sleep, sockets,
    #: device fetches) lexically under one of these is a finding —
    #: every thread in the process eventually serializes behind them
    hot_locks: Tuple[str, ...] = ()
    #: the allowed partial order: ``(outer, inner)`` means "``outer`` may
    #: be held while acquiring ``inner``". Every observed cross-lock
    #: acquisition edge must appear here (transitively); an edge whose
    #: REVERSE is derivable, or that is simply undeclared, is a finding.
    #: The declared set itself must be acyclic — checked every run.
    lock_order: Tuple[Tuple[str, str], ...] = ()


@dataclasses.dataclass(frozen=True)
class IrSpec:
    """Declared tables for the jaxpr-lint IR pass (``analysis/ir/``).

    The AST layer checks what Python source says; this layer checks what
    the COMPILED programs actually are. ``programs`` names registry keys
    resolved by ``analysis/ir/factories.py`` — each key builds one
    executable variant (tiny config, CPU/virtual-device mesh) and lowers
    (where cheap, compiles) it. Keys carry their geometry in the name
    (``decode_feedback@tp2``) so a finding names the exact variant.
    """

    #: registry keys analysis/ir/factories.py knows how to build; the IR
    #: pass builds and checks every one of these
    programs: Tuple[str, ...] = ()
    #: program keys whose compute is declared bf16 — dtype-drift applies
    bf16_programs: Tuple[str, ...] = ()
    #: program keys that are decode-hot — host-interop applies (a
    #: pure_callback in a hot executable serializes every step)
    hot_programs: Tuple[str, ...] = ()
    #: composition name -> program keys whose collective schedules must be
    #: IDENTICAL (primitive, axis names, shapes, replica groups, order) —
    #: divergence between programs that run on the ranks of one slice is
    #: a runtime hang, not an error message
    compositions: Dict[str, Tuple[str, ...]] = dataclasses.field(
        default_factory=dict)
    #: bytes above which a constant baked into a program body is a
    #: finding (per-executable HBM bloat the HBM ledger cannot attribute)
    const_limit_bytes: int = 1 << 16


@dataclasses.dataclass(frozen=True)
class Contract:
    # -- host-sync: declared decode hot paths ------------------------------
    #: repo-relative file -> qualnames whose bodies (nested defs included)
    #: must not synchronize device->host. "*" = every function in the file.
    hot_paths: Dict[str, Tuple[str, ...]] = dataclasses.field(
        default_factory=dict)

    # -- donation ----------------------------------------------------------
    #: files scanned for ``jax.jit(fn, donate_argnums=...)`` factory defs
    donation_factory_files: Tuple[str, ...] = ()
    #: files whose call sites are checked for donated-read-after-dispatch
    donation_check_files: Tuple[str, ...] = ()
    #: method name -> (factory name, index of the executable in the
    #: accessor's returned tuple; None = the whole return value). Example:
    #: ``_decode_for`` returns ``(batch_bucket, decode_fn)`` built by
    #: ``make_decode`` -> ("make_decode", 1).
    accessor_factories: Dict[str, Tuple[str, Optional[int]]] = (
        dataclasses.field(default_factory=dict))
    #: function qualname -> {parameter name: factory name} for executables
    #: passed in as arguments (the dispatch helpers)
    param_factories: Dict[str, Dict[str, str]] = dataclasses.field(
        default_factory=dict)
    #: instance-attribute callables built by a factory (``self._cross_write``)
    attr_factories: Dict[str, str] = dataclasses.field(default_factory=dict)
    #: method name -> 0-based positional indices (self excluded) whose
    #: argument buffers the method donates onward
    donating_calls: Dict[str, Tuple[int, ...]] = dataclasses.field(
        default_factory=dict)

    # -- thread discipline -------------------------------------------------
    thread_contract: Dict[str, ClassPolicy] = dataclasses.field(
        default_factory=dict)
    #: module -> {dict var name: (guarded keys, lock name)} for
    #: closure-state dicts (serve.app's ``state``)
    dict_guards: Dict[str, Dict[str, Tuple[Tuple[str, ...], str]]] = (
        dataclasses.field(default_factory=dict))

    # -- env knobs ---------------------------------------------------------
    #: the modules that OWN raw env reads (the parser seam itself)
    env_parser_modules: Tuple[str, ...] = ()
    #: modules exempt from the env rules entirely (with the reason)
    env_exempt_modules: Dict[str, str] = dataclasses.field(
        default_factory=dict)
    #: (file, env name) -> reason: declared strict-parse/raw-read exemptions
    env_exempt_sites: Dict[Tuple[str, str], str] = dataclasses.field(
        default_factory=dict)
    #: lenient parser helpers (calls to these register the name, satisfy
    #: the read rule, and are doc-checked)
    env_parser_names: Tuple[str, ...] = (
        "env_int", "env_float", "env_str", "env_bool", "env_flag")
    #: env names that need no README entry (platform/infra, not knobs)
    env_doc_exempt: Tuple[str, ...] = ()

    # -- trace exclusion ---------------------------------------------------
    #: files defining the app surface: routes + trace_exclude literals
    trace_files: Tuple[str, ...] = ()
    #: GET routes (beyond /debug/*) that are poll surfaces and must be
    #: excluded from the flight-recorder trace ring
    poll_routes: Tuple[str, ...] = ()

    # -- race pass (shai-race) ---------------------------------------------
    race: RaceSpec = dataclasses.field(default_factory=RaceSpec)

    # -- IR pass (jaxpr-lint) ----------------------------------------------
    ir: IrSpec = dataclasses.field(default_factory=IrSpec)


#: the live tree's contract ---------------------------------------------------

DEFAULT_CONTRACT = Contract(
    # The async decode hot loop (PR 6): the steady path dispatches step N+1
    # before retiring step N — any host synchronization here serializes the
    # pipeline and silently reverts the 1.4x async win. _retire_pipe is ON
    # this list although it contains the one intentional blocking fetch:
    # that fetch is documented via the allow grammar, not exempted.
    hot_paths={
        "engine/engine.py": (
            "LLMEngine._step_async",
            "LLMEngine._steady_step",
            "LLMEngine._decode_dispatch",
            "LLMEngine._dispatch_async",
            "LLMEngine._retire_pipe",
            # the QoS weighted-fair dequeue runs on every admission step:
            # it must stay pure host arithmetic — a device sync here would
            # serialize admission behind the decode pipeline
            "LLMEngine._schedule_head",
        ),
        # the scheduler kernel itself (stride select + head rotation):
        # same discipline, shared by the engine and the property tests
        "resilience/qos.py": (
            "WeightedFairScheduler.select", "schedule_rotate"),
        "engine/resident.py": ("*",),
        # the jitted decode/verify bodies: a host sync here would be a
        # trace-time crash on device — and on CPU fallbacks a silent
        # per-step serialization
        "engine/runner.py": (
            "make_decode", "make_verify", "_make_token_forward"),
        # the KV-tier movers' jitted bodies: a host sync traced into the
        # demotion gather or restore scatter would serialize every
        # eviction/warm-hit on the host (same discipline as runner.py)
        "kvtier/restore.py": ("make_tier_gather", "make_tier_restore"),
        # the autoscaler's decision kernel and tick: pure host arithmetic
        # by contract — the control loop must never block on a device (a
        # sync here would couple scaling cadence to decode dispatch)
        "orchestrate/scaler.py": (
            "Scaler._decide_pool", "Scaler.tick", "Scaler.run_tick"),
    },
    donation_factory_files=("engine/runner.py", "core/aot.py",
                            "kvtier/restore.py"),
    donation_check_files=(
        "engine/engine.py", "engine/runner.py", "engine/warm.py",
        "engine/cross.py", "core/aot.py", "engine/cache.py",
        "kvtier/restore.py", "kvtier/pool.py"),
    accessor_factories={
        "_prefill_for": ("make_prefill", None),
        "_cont_for": ("make_prefill_cont", None),
        "_decode_for": ("make_decode", 1),
        "_verify_for": ("make_verify", 1),
    },
    param_factories={
        # the async dispatch helper receives the compiled decode executable
        "LLMEngine._dispatch_async": {"decode": "make_decode"},
    },
    attr_factories={"_cross_write": "make_cross_slot_write",
                    # the cache's restore scatter (donate-and-rebind per
                    # layer) and demotion gather (no donation)
                    "_tier_restore": "make_tier_restore",
                    "_tier_gather": "make_tier_gather"},
    donating_calls={
        # _dispatch_async(decode, running, Bb, tokens_dev, pos_dev, a, rng):
        # pos_dev (index 4) is donated into the feedback-decode dispatch
        # (tokens_dev is NOT — the host reads it back one step later)
        "_dispatch_async": (4,),
    },
    thread_contract={
        # The engine is single-threaded by design: ONE loop thread owns it;
        # the serve lane reaches it only through EngineLoop's queues. Any
        # attribute write from outside the owning modules is a cross-thread
        # mutation of unlocked state.
        "LLMEngine": ClassPolicy(
            immutable_after_init=(
                "cfg", "ecfg", "params", "cross_seq_len", "shardings",
                "cache", "buckets", "_chunk_cap",
                "_drafter", "spec", "_spec_rng", "_sample1", "_lp1",
                "_cross_embed", "_cross_write", "ttft", "tpot", "obs",
                "_hbm_every", "_hbm_dev", "_async", "_ids", "_res",
                "_kv_quant", "_kv_cow", "role",
                "_prefill_role"),
            owning_modules=(
                "engine/engine.py", "engine/warm.py", "engine/cross.py",
                "engine/logprobs.py", "engine/speculative.py",
                "engine/loop.py"),
            instance_markers=("engine.", "eng."),
        ),
        "ResidentBatch": ClassPolicy(
            owning_modules=("engine/resident.py", "engine/engine.py"),
            instance_markers=("._res.",),
        ),
        # EngineLoop bridges the serve lane and the loop thread: the
        # futures table is the one cross-thread structure, guarded by
        # _futures_lock at every mutation site.
        "EngineLoop": ClassPolicy(
            immutable_after_init=("engine", "_poll_s", "_submit_q",
                                  "_cancel_q", "_futures_lock", "_stop",
                                  "_draining", "_thread",
                                  "_migrate_evt", "_migrate_done"),
            lock_guarded={"_futures": "_futures_lock"},
            owning_modules=("engine/loop.py",),
            instance_markers=(".loop.",),
        ),
        # The flight ring takes writes from every request thread.
        "FlightRecorder": ClassPolicy(
            immutable_after_init=("max_requests", "max_steps", "_lock"),
            lock_guarded={"_requests": "_lock", "_seq": "_lock",
                          "_by_trace": "_lock"},
            owning_modules=("obs/flight.py",),
        ),
        # The step telemetry is written by the engine-loop thread and read
        # by scrape/dump threads: the container attrs (step ring, gauge
        # dict, tenant tables) are lock-guarded on BOTH sides — the
        # guarded-read rule is what catches a torn /stats snapshot.
        # Scalar counters (steps, preemptions, ...) stay undeclared: a
        # torn int read cannot exist under the GIL and declaring them
        # would bury the structural reads in noise.
        "StepTelemetry": ClassPolicy(
            immutable_after_init=("ttft", "tpot", "queue_wait", "step_gap",
                                  "_lock", "_stream_lock"),
            lock_guarded={"_steps": "_lock", "_gauges": "_lock",
                          "_tenants": "_lock", "_tenant_ttft": "_lock",
                          "_flush_reasons": "_lock",
                          "_ahead_reasons": "_lock",
                          "_stream": "_stream_lock"},
            owning_modules=("obs/steploop.py",),
        ),
        # The admission gate's shed counters take writes from every
        # request thread and reads from /stats scrapes.
        "AdmissionGate": ClassPolicy(
            immutable_after_init=(
                "thresholds", "max_inflight", "retry_after_s",
                "drain_retry_after_s", "ledger", "tenant_max_inflight",
                "tier_full_utilization", "tier_full_kv_utilization",
                "_lock"),
            lock_guarded={"_shed": "_lock"},
            owning_modules=("resilience/admission.py",),
            instance_markers=("gate.", ".gate."),
        ),
        # The drain flag is armed by the SIGTERM handler and read by every
        # admission/readiness path.
        "DrainController": ClassPolicy(
            immutable_after_init=("budget_s", "_clock", "_lock"),
            lock_guarded={"_started_at": "_lock"},
            owning_modules=("resilience/drain.py",),
            instance_markers=("drainer.", ".drainer."),
        ),
        # The host KV tier is written from TWO threads by design: the
        # engine thread stores/probes/restores, the copy-out worker
        # publishes materialized entries — every mutation of the entry
        # map and the counters moves under _lock.
        "HostKVTier": ClassPolicy(
            immutable_after_init=(
                "n_layers", "block_size", "n_kv_heads", "head_dim",
                "dtype", "block_nbytes", "capacity_bytes", "async_copy",
                "_lock"),
            lock_guarded={"_entries": "_lock", "_stats": "_lock",
                          "_closing": "_lock"},
            owning_modules=("kvtier/pool.py",),
            instance_markers=(".tier.",),
        ),
        # The copy-out worker's queue/thread bindings are fixed at
        # construction; the queue object itself is the cross-thread seam.
        "CopyOutWorker": ClassPolicy(
            immutable_after_init=("_pool", "_q", "_thread", "_closed",
                                  "_sub_lock"),
            locks=("_sub_lock",),
            owning_modules=("kvtier/pool.py",),
        ),
        # The kvnet transport counters take writes from lane threads (the
        # decode-role fetch) AND the event loop (the /kv/blocks serve
        # side), reads from scrape threads — all under _lock.
        "KvNetStats": ClassPolicy(
            immutable_after_init=("_lock",),
            lock_guarded={"_counts": "_lock"},
            owning_modules=("kvnet/client.py",),
        ),
        # The kvnet client is shared by every serving-lane thread: the
        # lazily-built httpx client and the per-peer breaker table move
        # under _lock; the HTTP call itself runs OUTSIDE it (the
        # blocking-under-lock rule is what enforces that stays true).
        "KvNetClient": ClassPolicy(
            immutable_after_init=(
                "tier", "stats", "timeout_s", "connect_timeout_s",
                "connect_retries", "allowed_peers", "_breaker_factory",
                "_transport", "_lock"),
            lock_guarded={"_client": "_lock", "_breakers": "_lock"},
            owning_modules=("kvnet/client.py",),
        ),
        # Live migration (kvnet/migrate.py): the counters take writes
        # from the drain thread (ship), the event loop (accept), and
        # lane threads (resume); the inbox takes puts from the accept
        # path and pops from replay lanes — all under their _lock. The
        # SNAPSHOT itself happens on the engine loop thread; the SHIP
        # runs on a serving thread outside every declared lock (the
        # hot_locks entries below make blocking-under-lock enforce that
        # mechanically — the PR-14 httpx-under-lock lesson).
        "MigrateStats": ClassPolicy(
            immutable_after_init=("_lock",),
            lock_guarded={"_counts": "_lock"},
            owning_modules=("kvnet/migrate.py",),
        ),
        "MigrationInbox": ClassPolicy(
            immutable_after_init=("capacity", "_lock"),
            lock_guarded={"_entries": "_lock", "_accepting": "_lock"},
            owning_modules=("kvnet/migrate.py",),
        ),
        # KV fabric (kvnet/directory.py): counters take writes from the
        # engine loop (probe outcomes) and lane threads (replication
        # pulls); the directory takes updates from whoever polls peers
        # and reads from the probe path — every map under _lock, every
        # HTTP fetch outside it (the hot_locks entries enforce that).
        "KvFabricStats": ClassPolicy(
            immutable_after_init=("_lock",),
            lock_guarded={"_counts": "_lock"},
            owning_modules=("kvnet/directory.py",),
        ),
        "KvDirectory": ClassPolicy(
            immutable_after_init=("ttl_s", "_lock"),
            lock_guarded={"_holders": "_lock", "_by_holder": "_lock",
                          "_hits": "_lock", "_aff2head": "_lock"},
            owning_modules=("kvnet/directory.py",),
        ),
        # The probe's own lock guards ONLY the refresh deadline — the
        # digest fetches and the run pull run outside it by contract.
        "FabricProbe": ClassPolicy(
            immutable_after_init=("tier", "stats", "client", "peers",
                                  "ttl_s", "directory", "_lock"),
            lock_guarded={"_refresh_at": "_lock"},
            owning_modules=("kvnet/directory.py",),
        ),
        # The tenant ledger takes writes from every serving thread
        # (admission checks, completion charges) and reads from scrape
        # threads: bucket state and per-tenant counters move under _lock
        # at every mutation site.
        "TenantLedger": ClassPolicy(
            immutable_after_init=("budgets", "default_budget",
                                  "max_tenants", "_clock", "_lock"),
            lock_guarded={"_buckets": "_lock", "_stats": "_lock"},
            owning_modules=("resilience/qos.py",),
            instance_markers=(".ledger.", "led."),
        ),
        # The scheduler is engine-loop-thread-only by contract (select()
        # mutates stride state); only the engine and the qos module may
        # touch it.
        "WeightedFairScheduler": ClassPolicy(
            immutable_after_init=("weights", "aging_rounds"),
            owning_modules=("resilience/qos.py", "engine/engine.py"),
            instance_markers=("sched.",),
        ),
        # The autoscaler: decision counters take writes from the control
        # tick and reads from scrape threads; pool state moves only under
        # the scaler's own lock. The apply callback (drain/spawn, which
        # may block on HTTP) runs OUTSIDE both by contract — the
        # hot_locks entries enforce that mechanically.
        "ScalerStats": ClassPolicy(
            immutable_after_init=("_lock",),
            lock_guarded={"_counts": "_lock"},
            owning_modules=("orchestrate/scaler.py",),
        ),
        "Scaler": ClassPolicy(
            immutable_after_init=("cfg", "pricer", "stats", "clock",
                                  "_lock"),
            lock_guarded={"_pools": "_lock"},
            owning_modules=("orchestrate/scaler.py",),
        ),
        # Request reliability (PR 20): the idempotency cache takes writes
        # from every keyed lane thread and reads from scrapes; joiners
        # park on per-entry events strictly OUTSIDE the lock.
        "IdempotencyCache": ClassPolicy(
            immutable_after_init=("max_entries", "ttl_s", "_clock",
                                  "_lock"),
            lock_guarded={"_entries": "_lock", "_counts": "_lock"},
            owning_modules=("resilience/idempotency.py",),
            instance_markers=("idem.", ".idem"),
        ),
        # cova's hedge/budget/poison state is shared between the async
        # dispatch path and scrape threads; every mutation is a leaf
        # under the instance lock — the hot_locks entries below keep
        # httpx (and anything else blocking) out from under them.
        "RetryBudget": ClassPolicy(
            immutable_after_init=("pct", "burst", "window", "_lock"),
            lock_guarded={"_tokens": "_lock", "_counts": "_lock"},
            owning_modules=("resilience/hedge.py",),
        ),
        "HedgeGovernor": ClassPolicy(
            immutable_after_init=("default_s", "min_s", "max_s",
                                  "min_samples", "_lock"),
            lock_guarded={"_lat": "_lock"},
            owning_modules=("resilience/hedge.py",),
        ),
        "PoisonRegistry": ClassPolicy(
            immutable_after_init=("k", "max_entries", "_lock"),
            lock_guarded={"_counts": "_lock", "_stats": "_lock"},
            owning_modules=("resilience/hedge.py",),
        ),
        "HedgeStats": ClassPolicy(
            immutable_after_init=("_lock",),
            lock_guarded={"_counts": "_lock",
                          "_follow_depth_max": "_lock"},
            owning_modules=("resilience/hedge.py",),
        ),
    },
    dict_guards={
        # serve.app closure state shared between the event loop and lane/
        # stream-pool threads: the in-flight counters must move under the lock
        "serve/app.py": {
            "state": (("inflight", "lane_pending"), "inflight_lock"),
        },
    },
    env_parser_modules=("obs/util.py", "utils/env.py"),
    env_exempt_modules={
        "perf/topo.py": "env snapshot/restore helper — sets and restores "
                        "arbitrary entries around subprocess topology "
                        "probes; it parses nothing",
    },
    env_exempt_sites={},
    env_doc_exempt=(
        # platform/infra variables owned by JAX/XLA or the test harness,
        # not operator-facing serving knobs
        "XLA_FLAGS", "JAX_DEFAULT_DEVICE", "JAX_PLATFORMS",
        "ALLOW_MULTIPLE_LIBTPU_LOAD", "SHAI_TEST_DURATIONS",
    ),
    trace_files=("serve/app.py", "serve/asgi.py", "orchestrate/cova.py"),
    poll_routes=("/profile", "/health", "/readiness", "/health/ready",
                 "/metrics", "/stats", "/kv/blocks", "/kv/digests",
                 "/fleet", "/trace/{trace_id}"),
    race=RaceSpec(
        # serve.app's closure lock guarding the in-flight counters (the
        # dict_guards entry above names the same lock for the write rule)
        module_locks={"serve/app.py": {"inflight_lock":
                                       "app.inflight_lock"}},
        # the locks every thread in the process eventually serializes
        # behind: the engine-loop/serve futures seam, the QoS ledger (on
        # every admission AND completion), the step telemetry + flight
        # ring (written per step / per request, scraped concurrently),
        # the host KV pool (engine probes vs worker publishes), and the
        # request-path in-flight counters. Blocking while holding any of
        # these stalls request threads fleet-wide, not just one caller.
        hot_locks=(
            "EngineLoop._futures_lock",
            "TenantLedger._lock",
            "StepTelemetry._lock",
            # the server's event loop passes here once a written SSE event,
            # the engine loop once a resolved request
            "StepTelemetry._stream_lock",
            "FlightRecorder._lock",
            "HostKVTier._lock",
            "AdmissionGate._lock",
            "DrainController._lock",
            "app.inflight_lock",
            # the kvnet transport: stats count on every handoff fetch and
            # every /kv/blocks serve; the client lock fronts every lane
            # thread's fetch — an HTTP call under either would serialize
            # the whole decode tier behind one slow peer
            "KvNetStats._lock",
            "KvNetClient._lock",
            # live migration: stats count on every ship/accept/resume and
            # the inbox fronts every replay — an HTTP ship under either
            # would serialize the whole drain behind one slow peer
            "MigrateStats._lock",
            "MigrationInbox._lock",
            # KV fabric: the probe rung runs ON the engine loop thread
            # and the directory serves every routing decision — an HTTP
            # probe or digest refresh under any of these would stall
            # admission fleet-wide behind one slow holder
            "KvFabricStats._lock",
            "KvDirectory._lock",
            "FabricProbe._lock",
            # the autoscaler: stats count on every tick and pool state
            # fronts every decision — a drain HTTP call under either
            # would freeze the control loop behind one slow pod
            "ScalerStats._lock",
            "Scaler._lock",
            # request reliability: the idempotency cache fronts every
            # keyed request (joiners wait on entry events OUTSIDE the
            # lock), and the hedge/budget/poison locks sit on cova's
            # dispatch hot path — an httpx call under any of them would
            # serialize the fan-out behind one slow pod
            "IdempotencyCache._lock",
            "RetryBudget._lock",
            "HedgeGovernor._lock",
            "PoisonRegistry._lock",
            "HedgeStats._lock",
        ),
        # The declared partial order is EMPTY on purpose: the control
        # plane's design rule is "no lock nesting at all" — every
        # declared lock protects a leaf structure and is released before
        # any call that could take another. Any observed cross-lock
        # acquisition (lexical or through the 2-level call graph) is
        # therefore a finding until a pair is deliberately added here.
        lock_order=(),
    ),
    ir=IrSpec(
        # every registered executable-factory variant the engine serves
        # with, built at tiny geometry by analysis/ir/factories.py:
        # runner.py's prefill/cont/decode (both feedback disciplines)/
        # verify/cross writers, the AOT export tier (core/aot.py's
        # artifact analog of per-rank NEFFs), and the SP legs in
        # parallel/ring.py. @tpN/@spN suffixes lower on an N-way virtual
        # CPU mesh; @tp2_paged lowers the Pallas paged path for the tpu
        # platform (trace + SPMD partition only, like the dryrun legs).
        programs=(
            "prefill", "prefill@tp2", "prefill_cont",
            "decode", "decode_feedback",
            "decode@tp2", "decode_feedback@tp2", "decode@tp2_paged",
            # int8 KV pool (SHAI_KV_QUANT): quantized scatter on prefill,
            # requantizing decode write + in-executable dequant, and the
            # scale-carrying tier restore — dtype-drift and donation gate
            # these from day one
            "prefill_kvquant", "decode_kvquant", "tier_restore_quant",
            "verify",
            "cross_kv", "cross_slot_write",
            "tier_restore",
            "aot_decode_export",
            "ring@sp2", "ring_causal@sp2", "ulysses@sp2",
        ),
        # the engine's token paths are declared-bf16 compute (residual
        # stream, KV pool); f32 is legal only behind an explicit astype
        # (rmsnorm/logits islands). The SP legs are dtype-polymorphic
        # test rigs, not declared-bf16.
        bf16_programs=(
            "prefill", "prefill@tp2", "prefill_cont",
            "decode", "decode_feedback",
            "decode@tp2", "decode_feedback@tp2", "decode@tp2_paged",
            "prefill_kvquant", "decode_kvquant", "tier_restore_quant",
            "verify", "cross_kv", "cross_slot_write",
            "tier_restore",
        ),
        # a host callback inside any of these serializes every engine
        # step (decode) or admission (prefill/cross) on the host
        hot_programs=(
            "prefill", "prefill@tp2", "prefill_cont",
            "decode", "decode_feedback",
            "decode@tp2", "decode_feedback@tp2", "decode@tp2_paged",
            "prefill_kvquant", "decode_kvquant", "tier_restore_quant",
            "verify", "cross_kv", "cross_slot_write",
            "tier_restore",
        ),
        compositions={
            # one multihost slice may roll SHAI_ASYNC_DECODE across its
            # hosts: the two decode disciplines must keep identical
            # collective schedules or the first mixed step deadlocks
            "decode-disciplines@tp2": ("decode@tp2",
                                       "decode_feedback@tp2"),
            # the causal flag must not change ring attention's
            # communication pattern (a causal "optimization" that skips
            # rotations per-rank is exactly how ring impls deadlock)
            "ring-mask-variants@sp2": ("ring@sp2", "ring_causal@sp2"),
        },
        const_limit_bytes=1 << 16,
    ),
)
