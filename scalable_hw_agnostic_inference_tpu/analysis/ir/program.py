"""IrProgram: one executable-factory variant, traced/lowered/compiled for
inspection.

The AST checkers see what the source SAYS; this layer materializes what
the compiler actually BUILT, at tiny geometry on the CPU backend:

- ``trace`` -> the jaxpr (dtype-drift, collective-schedule, host-interop,
  baked-constants all walk it, nested sub-jaxprs included),
- ``lower`` -> the StableHLO module text (donation shows up as
  ``tf.aliasing_output`` attributes on the flattened donated inputs; a
  donation JAX dropped — aval mismatch — is a missing attribute plus a
  ``Some donated buffers were not usable`` warning, both captured here),
- ``compile`` (CPU, where cheap) -> post-optimization HLO text: the
  executable's real ``input_output_alias`` table and the collective ops
  the SPMD partitioner inserted (shard_map jaxprs only carry the
  explicit collectives; dense TP programs get theirs at compile time),
- ``export`` (artifact programs) -> the serialized ``jax.export`` module,
  the distributable analog of the reference's per-rank NEFFs — checked
  so the artifact tier cannot silently shed donation metadata.

Everything here imports jax lazily: ``analysis/`` stays importable in
milliseconds; only an explicit ``--ir`` run pays for a backend.
"""

from __future__ import annotations

import dataclasses
import re
import warnings
from typing import Any, List, Optional, Tuple

#: wire collectives at jaxpr level (pbroadcast/pcast are shard_map's
#: varying-manifest bookkeeping, not communication — excluded on purpose)
JAXPR_COLLECTIVES = frozenset({
    "psum", "psum_invariant", "ppermute", "pmax", "pmin", "pgather",
    "all_to_all", "all_gather", "all_gather_invariant",
    "reduce_scatter", "psum_scatter",
})

#: host-callback primitives: each dispatch round-trips to Python
CALLBACK_PRIMS = frozenset({
    "pure_callback", "io_callback", "debug_callback", "debug_print",
})

#: collective op mnemonics in post-optimization HLO text
_HLO_COLLECTIVE = re.compile(
    r"=\s+(\S+)\s+(all-reduce|all-gather|all-to-all|collective-permute|"
    r"reduce-scatter|collective-broadcast)(?:-start)?\(")
_HLO_GROUPS = re.compile(
    r"(?:replica_groups|source_target_pairs)=(\S+?)(?:,|\s|$)")


@dataclasses.dataclass
class IrProgram:
    """One registered executable variant plus its inspection artifacts."""

    key: str                       # registry key, e.g. "decode_feedback@tp2"
    factory: str                   # factory qualname, e.g. "make_decode"
    anchor_path: str               # repo-relative file of the factory def
    jitted: Any                    # the jax.jit-wrapped callable
    args: Tuple                    # jax.ShapeDtypeStruct example arguments
    donate_args: Tuple[int, ...] = ()   # declared donated python positions
    compile_cpu: bool = False      # also compile (CPU) and cross-check
    lowering_platforms: Optional[Tuple[str, ...]] = None  # e.g. ("tpu",)
    artifact: bool = False         # jax.export roundtrip instead of lower

    # filled by prepare() (a trace/lower/compile failure propagates —
    # the CLI's documented exit-2 internal-error contract)
    jaxpr: Any = None              # ClosedJaxpr
    lowered_text: str = ""
    compiled_text: str = ""
    donation_warnings: Tuple[str, ...] = ()

    # ------------------------------------------------------------------
    def prepare(self) -> "IrProgram":
        """Trace, lower, and (per flags) compile/export the program."""
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            traced = self.jitted.trace(*self.args)
            self.jaxpr = traced.jaxpr
            if self.artifact:
                from jax import export as jexport

                exported = jexport.export(self.jitted)(*self.args)
                # what a loader pod deserializes is what we inspect
                roundtrip = jexport.deserialize(exported.serialize())
                self.lowered_text = roundtrip.mlir_module()
            else:
                if self.lowering_platforms is not None:
                    lowered = traced.lower(
                        lowering_platforms=self.lowering_platforms)
                else:
                    lowered = traced.lower()
                self.lowered_text = lowered.as_text()
                if self.compile_cpu:
                    self.compiled_text = lowered.compile().as_text()
        self.donation_warnings = tuple(
            str(w.message) for w in caught if "donated" in str(w.message))
        return self

    # -- donation ------------------------------------------------------
    def expected_donated_leaves(self) -> int:
        import jax

        return sum(len(jax.tree.leaves(self.args[i]))
                   for i in self.donate_args if i < len(self.args))

    def lowered_alias_count(self) -> int:
        return self.lowered_text.count("tf.aliasing_output")

    def compiled_alias_count(self) -> Optional[int]:
        """Entries in the executable's ``input_output_alias`` table, or
        None when the program was not compiled."""
        if not self.compiled_text:
            return None
        return len(re.findall(r"(?:may|must)-alias", self.compiled_text))

    # -- jaxpr walking -------------------------------------------------
    def all_jaxprs(self) -> List[Any]:
        """Every (sub-)jaxpr reachable from the traced program, outer
        first, deduplicated."""
        out: List[Any] = []
        seen = set()

        def add(j) -> None:
            jx = getattr(j, "jaxpr", j)
            if not hasattr(jx, "eqns") or id(jx) in seen:
                return
            seen.add(id(jx))
            out.append(j if hasattr(j, "jaxpr") else jx)
            for eq in jx.eqns:
                for v in eq.params.values():
                    if hasattr(v, "jaxpr") or hasattr(v, "eqns"):
                        add(v)
                    elif isinstance(v, (list, tuple)):
                        for e in v:
                            if hasattr(e, "jaxpr") or hasattr(e, "eqns"):
                                add(e)

        if self.jaxpr is not None:
            add(self.jaxpr)
        return out

    def all_eqns(self):
        for j in self.all_jaxprs():
            jx = getattr(j, "jaxpr", j)
            for eq in jx.eqns:
                yield jx, eq

    def all_consts(self) -> List[Any]:
        """Constants closed over by the program (outer + nested closed
        jaxprs), deduplicated by identity."""
        out: List[Any] = []
        seen = set()
        for j in self.all_jaxprs():
            for c in getattr(j, "consts", []) or []:
                if id(c) not in seen:
                    seen.add(id(c))
                    out.append(c)
        return out

    # -- collective schedules ------------------------------------------
    def jaxpr_schedule(self) -> List[Tuple[str, str, str, str]]:
        """Ordered wire collectives in the traced program:
        (primitive, axes, perm/groups, operand shapes)."""
        sched: List[Tuple[str, str, str, str]] = []
        for _, eq in self.all_eqns():
            name = eq.primitive.name
            if name not in JAXPR_COLLECTIVES:
                continue
            axes = eq.params.get("axis_name", eq.params.get("axes", ""))
            extra = eq.params.get("perm",
                                  eq.params.get("axis_index_groups", ""))
            shapes = ",".join(
                f"{v.aval.dtype}{list(v.aval.shape)}"
                for v in eq.invars if hasattr(v, "aval"))
            sched.append((name, str(axes), str(extra), shapes))
        return sched

    def compiled_schedule(self) -> Optional[List[Tuple[str, str, str]]]:
        """Ordered collective ops in the post-optimization HLO:
        (op, result type, replica groups). None when not compiled."""
        if not self.compiled_text:
            return None
        sched: List[Tuple[str, str, str]] = []
        for line in self.compiled_text.splitlines():
            m = _HLO_COLLECTIVE.search(line)
            if not m:
                continue
            g = _HLO_GROUPS.search(line)
            sched.append((m.group(2), m.group(1),
                          g.group(1) if g else ""))
        return sched

    # -- callbacks ------------------------------------------------------
    def callback_prims(self) -> List[str]:
        found = []
        for _, eq in self.all_eqns():
            if eq.primitive.name in CALLBACK_PRIMS \
                    and eq.primitive.name not in found:
                found.append(eq.primitive.name)
        return found
