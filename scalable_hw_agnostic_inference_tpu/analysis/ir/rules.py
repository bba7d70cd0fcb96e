"""The five jaxpr-lint rules, over prepared :class:`~.program.IrProgram`s.

- ``donation-efficacy``   declared ``donate_argnums`` vs the aliases the
  compiler actually established. XLA drops a donation silently when the
  donated aval matches no output (dtype/shape drift); the cost is a full
  second copy of the donated pool in HBM — for the KV pool, the largest
  single allocation in the budget — invisible until a pod OOMs.
- ``dtype-drift``         an implicit bf16→f32 promotion inside
  declared-bf16 compute: a non-weak f32 scalar (``np.float32`` config
  value, ``jnp.float32(...)`` literal) met a bf16 operand and dragged the
  op — and everything downstream of it — to f32. Explicit ``astype``
  islands (rmsnorm, logits) don't match: the rule requires the promoting
  partner to be a SCALAR, which deliberate upcasts never are.
- ``collective-schedule`` programs of one composition (the executables
  that run on the ranks of a single slice) must carry IDENTICAL ordered
  collectives — primitive, axis names, operand shapes, replica groups —
  at the jaxpr tier (explicit shard_map collectives) and, where compiled,
  in post-optimization HLO (SPMD-inserted ones). A mismatch is not an
  error message at runtime; it is a slice-wide hang.
- ``host-interop``        ``pure_callback``/``io_callback``/``debug_callback``/``debug_print``
  (``jax.debug.print``) in a hot executable: every dispatch round-trips
  through Python, re-serializing the step loop the async pipeline exists
  to overlap.
- ``baked-constants``     closed-over arrays above the contract's size
  threshold embedded in the program: per-executable HBM the ledger's
  pool attribution can never see (it prices pools, not program bodies) —
  and one copy PER COMPILED BUCKET, not per engine.

Findings anchor at the factory ``def`` in source: the allow grammar
(``# shai-lint: allow(<rule>) <reason>`` on/above the def) and the
baseline fingerprints work exactly as for the AST rules. ``context`` is
the program key (or composition name) — path-free, rename-stable.
"""

from __future__ import annotations

import os
from typing import Dict, List, Optional, Tuple

from ..core import Finding, Module, PKG_ROOT, snippet_of
from .program import IrProgram

IR_RULES = ("donation-efficacy", "dtype-drift", "collective-schedule",
            "host-interop", "baked-constants")


# -- factory-def anchoring ----------------------------------------------------

class _Anchors:
    """Resolve (relpath, factory qualname) -> (Module, def node) once.

    ``preloaded`` lets tests inject fixture Modules for relpaths that
    don't exist under the package tree."""

    def __init__(self, preloaded: Optional[Dict[str, Module]] = None):
        self._modules: Dict[str, Module] = dict(preloaded or {})

    def module(self, relpath: str) -> Module:
        if relpath not in self._modules:
            full = os.path.join(PKG_ROOT, relpath)
            with open(full, encoding="utf-8") as f:
                self._modules[relpath] = Module(relpath, f.read())
        return self._modules[relpath]

    def node(self, relpath: str, qualname: str):
        import ast

        mod = self.module(relpath)
        scope = mod.tree
        parts = qualname.split(".")
        for i, part in enumerate(parts):
            nxt = None
            for child in ast.iter_child_nodes(scope):
                if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                      ast.ClassDef)) \
                        and child.name == part:
                    nxt = child
                    break
            if nxt is None:
                return None
            scope = nxt
        return scope


def _finding(anchors: _Anchors, prog: IrProgram, rule: str, context: str,
             message: str) -> Finding:
    mod = anchors.module(prog.anchor_path)
    node = anchors.node(prog.anchor_path, prog.factory)
    line = getattr(node, "lineno", 0)
    allowed, reason, problem = (False, "", None)
    if node is not None:
        # the rule's own name, or the umbrella token allow(ir)
        allowed, reason, problem = mod.allow_at(node, rule)
        if not allowed and problem is None:
            allowed, reason, problem = mod.allow_at(node, "ir")
    if problem:
        message += f" ({problem})"
    return Finding(
        rule=rule, path=prog.anchor_path, line=line, context=context,
        message=message, allowed=allowed, reason=reason,
        snippet=snippet_of(mod, node) if node is not None else "")


# -- the rules ----------------------------------------------------------------

def check_donation(progs: List[IrProgram], contract, anchors: _Anchors
                   ) -> List[Finding]:
    findings: List[Finding] = []
    for p in progs:
        expected = p.expected_donated_leaves()
        actual = p.lowered_alias_count()
        if actual < expected:
            detail = ""
            if p.donation_warnings:
                detail = (" — the compiler said: "
                          + "; ".join(sorted(set(p.donation_warnings))))
            where = ("the exported artifact"
                     if p.artifact else "the lowered module")
            findings.append(_finding(
                anchors, p, "donation-efficacy", p.key,
                f"{actual} of {expected} declared donated buffers are "
                f"aliased in {where} — each dropped donation "
                f"double-buffers its pool in HBM{detail}"))
        elif actual > expected:
            findings.append(_finding(
                anchors, p, "donation-efficacy", p.key,
                f"{actual} aliased buffers but only {expected} declared "
                f"donated leaves — the declared donation contract is "
                f"stale; update donate_args for this program"))
        compiled = p.compiled_alias_count()
        if compiled is not None and compiled < actual:
            findings.append(_finding(
                anchors, p, "donation-efficacy", p.key,
                f"the compiled executable's input_output_alias table has "
                f"{compiled} entries but lowering established {actual} — "
                f"XLA dropped donations at compile time (layout "
                f"mismatch class)"))
    return findings


#: user-facing conversion entry points: a convert whose traceback passes
#: through one of these was WRITTEN, not inserted by type promotion
_EXPLICIT_CONVERT_FRAMES = frozenset({
    "astype", "_astype", "convert_element_type", "asarray", "_asarray",
    "array",
})


def _is_explicit_convert(eq) -> bool:
    tb = getattr(getattr(eq, "source_info", None), "traceback", None)
    if tb is None:
        return False  # no provenance: treat as implicit (conservative)
    try:
        return any(fr.function_name in _EXPLICIT_CONVERT_FRAMES
                   for fr in tb.frames)
    except Exception:
        return False


def check_dtype_drift(progs: List[IrProgram], contract, anchors: _Anchors
                      ) -> List[Finding]:
    from jax.extend.core import Literal

    findings: List[Finding] = []
    declared = set(contract.ir.bf16_programs)
    for p in progs:
        if p.key not in declared and "*" not in declared:
            continue
        hit_prims: List[str] = []
        for j in p.all_jaxprs():
            jx = getattr(j, "jaxpr", j)
            converted = set()
            for eq in jx.eqns:
                if eq.primitive.name == "convert_element_type":
                    iv = eq.invars[0]
                    if hasattr(iv, "aval") \
                            and str(iv.aval.dtype) == "bfloat16" \
                            and str(eq.outvars[0].aval.dtype) == "float32" \
                            and not _is_explicit_convert(eq):
                        converted.add(eq.outvars[0])
                    continue
                uses_conv = any(
                    (not isinstance(v, Literal)) and v in converted
                    for v in eq.invars)
                if not uses_conv:
                    continue
                for other in eq.invars:
                    av = getattr(other, "aval", None)
                    if av is None or str(av.dtype) != "float32":
                        continue
                    if av.shape == () and not getattr(av, "weak_type", True):
                        if eq.primitive.name not in hit_prims:
                            hit_prims.append(eq.primitive.name)
                        break
        for prim in hit_prims:
            findings.append(_finding(
                anchors, p, "dtype-drift", p.key,
                f"implicit bf16->f32 promotion at `{prim}`: a non-weak "
                f"f32 scalar met bf16 compute and upcast it — the hot "
                f"path runs (and writes) f32 from here on; wrap the "
                f"scalar as a python float or .astype the intent "
                f"explicitly"))
    return findings


def check_collectives(progs: List[IrProgram], contract, anchors: _Anchors
                      ) -> List[Finding]:
    findings: List[Finding] = []
    by_key = {p.key: p for p in progs}
    for comp, members in sorted(contract.ir.compositions.items()):
        built = [by_key[m] for m in members if m in by_key]
        if len(built) < 2:
            continue  # subset run: composition not comparable
        base = built[0]
        base_sched = base.jaxpr_schedule()
        for other in built[1:]:
            sched = other.jaxpr_schedule()
            diff = _first_divergence(base_sched, sched)
            if diff is not None:
                i, a, b = diff
                findings.append(_finding(
                    anchors, other, "collective-schedule", comp,
                    f"collective schedules diverge between `{base.key}` "
                    f"and `{other.key}` at collective #{i}: "
                    f"{a or 'end-of-schedule'} vs {b or 'end-of-schedule'}"
                    f" — rank-mismatched collectives hang the slice"))
        scheds = [(p, p.compiled_schedule()) for p in built]
        if all(s is not None for _, s in scheds):
            base_p, base_s = scheds[0]
            for other_p, other_s in scheds[1:]:
                diff = _first_divergence(base_s, other_s)
                if diff is not None:
                    i, a, b = diff
                    findings.append(_finding(
                        anchors, other_p, "collective-schedule", comp,
                        f"compiled (SPMD-inserted) collective schedules "
                        f"diverge between `{base_p.key}` and "
                        f"`{other_p.key}` at collective #{i}: "
                        f"{a or 'end-of-schedule'} vs "
                        f"{b or 'end-of-schedule'}"))
    return findings


def _first_divergence(a: List, b: List
                      ) -> Optional[Tuple[int, object, object]]:
    for i in range(max(len(a), len(b))):
        ea = a[i] if i < len(a) else None
        eb = b[i] if i < len(b) else None
        if ea != eb:
            return i, ea, eb
    return None


def check_host_interop(progs: List[IrProgram], contract, anchors: _Anchors
                       ) -> List[Finding]:
    findings: List[Finding] = []
    hot = set(contract.ir.hot_programs)
    for p in progs:
        if p.key not in hot and "*" not in hot:
            continue
        for prim in p.callback_prims():
            findings.append(_finding(
                anchors, p, "host-interop", p.key,
                f"host callback `{prim}` inside a hot executable — every "
                f"dispatch round-trips through Python, serializing the "
                f"step loop"))
    return findings


def check_baked_constants(progs: List[IrProgram], contract,
                          anchors: _Anchors) -> List[Finding]:
    findings: List[Finding] = []
    limit = contract.ir.const_limit_bytes
    for p in progs:
        seen = set()
        for c in p.all_consts():
            nbytes = getattr(c, "nbytes", 0)
            if nbytes <= limit:
                continue
            shape = tuple(getattr(c, "shape", ()))
            dtype = str(getattr(c, "dtype", type(c).__name__))
            ident = (dtype, shape)
            if ident in seen:
                continue
            seen.add(ident)
            findings.append(_finding(
                anchors, p, "baked-constants", p.key,
                f"constant {dtype}{list(shape)} ({nbytes} bytes > "
                f"{limit} limit) is baked into the program body — "
                f"per-executable HBM the ledger's pool attribution "
                f"cannot see, one copy per compiled bucket"))
    return findings


def check(progs: List[IrProgram], contract,
          rules: Optional[Tuple[str, ...]] = None,
          modules: Optional[Dict[str, Module]] = None) -> List[Finding]:
    """Run the (selected) IR rules over prepared programs."""
    anchors = _Anchors(modules)
    selected = set(rules) if rules else set(IR_RULES)
    findings: List[Finding] = []
    if "donation-efficacy" in selected:
        findings += check_donation(progs, contract, anchors)
    if "dtype-drift" in selected:
        findings += check_dtype_drift(progs, contract, anchors)
    if "collective-schedule" in selected:
        findings += check_collectives(progs, contract, anchors)
    if "host-interop" in selected:
        findings += check_host_interop(progs, contract, anchors)
    if "baked-constants" in selected:
        findings += check_baked_constants(progs, contract, anchors)
    findings.sort(key=lambda f: (f.path, f.line, f.rule, f.message))
    return findings
