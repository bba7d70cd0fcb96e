"""From a ``jax.profiler`` trace to device numbers.

A trace is first brought into a plain form, which is also what the tests'
recorded trace is stored in::

    {"planes": {"<plane name>": {"<line name>": [[name, start_ns, dur_ns],
                                                  ...]}}}

and every number is reduced from that form:

- busy: the union of the intervals in which an XLA op ran on a device,
  clipped to the traced window; idle share is 1 - busy / window;
- per-program time: the events of the device's "XLA Modules" line, by name;
- per-op time: SELF time on the "XLA Ops" line (an event's duration less
  what its children cover), so nested events are not counted twice;
- gaps: each idle interval of device 0, named by the program before it, the
  program after it and the host span that covers most of it.

The window is the ``bench_window`` annotation the benchmark's own thread
wrote into the host plane (same clock as the device lines); where it is
missing, the extent of the device events.

    python -m benchmark.trace <file.xplane.pb> [--export out.json
        --from-ms A --to-ms B]      # look at a trace, or cut a small one
"""

from __future__ import annotations

import bisect
import json
import re
from typing import Any, Dict, List, Optional, Tuple

WINDOW_SPAN = "bench_window"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
_SUFFIX = re.compile(r"(\.\d+)+$")
_MODULE_ID = re.compile(r"\(\d+\)$")
#: anywhere in the op's name, so that ``all-reduce.5``, the asynchronous pair
#: ``all-reduce-start`` / ``all-reduce-done`` and a fusion named after the
#: collective it holds (``all-reduce-scatter``, ``fusion.all-gather``) all
#: count. Read off XLA's op names; no four-chip trace has been seen yet
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute"
    r"|collective-broadcast", re.IGNORECASE)

#: control-flow shells hold their children's time and compute nothing
CONTAINERS = ("while", "conditional", "call")
Event = Tuple[str, float, float]   # name, start_ns, dur_ns
BIN_NS = 1e6
MAX_SPAN_NS = 50e6


def load_xplane(path: str) -> Dict[str, Any]:
    from jax.profiler import ProfileData

    planes: Dict[str, Dict[str, List]] = {}
    for plane in ProfileData.from_file(path).planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                [e.name, float(e.start_ns), float(e.duration_ns)]
                for e in line.events)
    return {"planes": planes}


def load(path: str) -> Dict[str, Any]:
    if path.endswith(".json"):
        with open(path) as f:
            return json.load(f)
    return load_xplane(path)


def op_name(raw: str) -> str:
    """``%fusion.123 = bf16[8,128]{...} fusion(...)`` -> ``fusion``: the
    op's own name, without the HLO text the TPU's trace carries after it and
    without its serial numbers."""
    head = raw.split(" = ", 1)[0].strip().lstrip("%")
    return _SUFFIX.sub("", head) or raw


def program_name(raw: str) -> str:
    """``jit_decode(1234)`` -> ``jit_decode``."""
    return _MODULE_ID.sub("", raw)


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals: List[Tuple[float, float]]) -> float:
    return sum(e - s for s, e in intervals)


def _clip(events: List[Event], lo: float, hi: float) -> List[Event]:
    out = []
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((name, a, b - a))
    return out


def self_times(events: List[Event]) -> Dict[str, float]:
    """Seconds of self time by op name: an event's duration less the part
    its children (events nested inside it on the same line) cover."""
    out: Dict[str, float] = {}
    stack: List[List] = []   # [name, end, self_ns]

    def close(upto: float):
        while stack and stack[-1][1] <= upto:
            name, _, self_ns = stack.pop()
            out[name] = out.get(name, 0.0) + max(self_ns, 0.0) / 1e9

    for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        close(s)
        if stack:
            stack[-1][2] -= min(d, stack[-1][1] - s)
        stack.append([op_name(name), s + d, d])
    close(float("inf"))
    return out


def _subtract(a: List[Tuple[float, float]],
              b: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """The parts of the (disjoint, sorted) intervals ``a`` outside ``b``."""
    out = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


class Reduced:
    """The numbers of one trace. Seconds throughout."""

    def __init__(self, trace: Dict[str, Any]):
        planes = trace["planes"]
        self.devices = sorted(
            (int(DEVICE_PLANE.match(n).group(1)), n) for n in planes
            if DEVICE_PLANE.match(n))
        host = [(n, ln, ev) for n, lines in planes.items()
                if n.startswith("/host:") for ln, ev in lines.items()]
        self.window = self._window(planes, host)
        lo, hi = self.window
        self.window_s = (hi - lo) / 1e9
        self.busy_s_by_device: Dict[int, float] = {}
        self.program_s: Dict[str, List[float]] = {}    # name -> durations
        self.op_s: Dict[str, float] = {}
        self.collective_s = 0.0
        self.collective_exposed_s = 0.0
        self.gaps: List[Tuple[str, float]] = []
        for idx, name in self.devices:
            lines = planes[name]
            ops = _clip([tuple(e) for e in lines.get(OPS_LINE, ())], lo, hi)
            mods = _clip([tuple(e) for e in lines.get(MODULES_LINE, ())],
                         lo, hi)
            busy = union([(s, s + d) for _, s, d in (ops or mods)])
            self.busy_s_by_device[idx] = total(busy) / 1e9
            # a program cut by the window's edge would read short
            whole = [tuple(e) for e in lines.get(MODULES_LINE, ())
                     if e[1] >= lo and e[1] + e[2] <= hi]
            for raw, _, d in whole:
                self.program_s.setdefault(program_name(raw), []).append(
                    d / 1e9)
            for op, secs in self_times(ops).items():
                self.op_s[op] = self.op_s.get(op, 0.0) + secs
            coll = union([(s, s + d) for n, s, d in ops
                          if COLLECTIVE.search(op_name(n))])
            rest = union([(s, s + d) for n, s, d in ops
                          if not COLLECTIVE.search(op_name(n))
                          and op_name(n) not in CONTAINERS])
            self.collective_s += total(coll) / 1e9
            self.collective_exposed_s += total(_subtract(coll, rest)) / 1e9
            if idx == self.devices[0][0]:
                self.gaps = self._gaps(busy, mods, host, lo, hi)
        n = max(1, len(self.devices))
        self.busy_s = sum(self.busy_s_by_device.values()) / n
        # per-chip means: what one chip spent
        self.op_s = {k: v / n for k, v in self.op_s.items()}
        self.collective_s /= n
        self.collective_exposed_s /= n

    @staticmethod
    def _window(planes, host) -> Tuple[float, float]:
        for _, _, events in host:
            for name, s, d in events:
                if name == WINDOW_SPAN:
                    return (s, s + d)
        starts, ends = [], []
        for n, lines in planes.items():
            if DEVICE_PLANE.match(n):
                for events in lines.values():
                    for _, s, d in events:
                        starts.append(s)
                        ends.append(s + d)
        if not starts:
            return (0.0, 0.0)
        return (min(starts), max(ends))

    @staticmethod
    def _gaps(busy, mods, host, lo, hi) -> List[Tuple[str, float]]:
        """Idle time of one device, summed by name, longest first. The idle
        between two programs is named by both and by the host span that
        covers most of it; the idle between the ops of one program is one
        entry."""
        mods = sorted(mods, key=lambda e: e[1])
        busy_mod = union([(s, s + d) for _, s, d in mods]) or busy
        edges = [lo] + [x for iv in busy_mod for x in iv] + [hi]
        idle = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        by_name: Dict[str, float] = {}
        inside = (total(busy_mod) - total(busy)) / 1e9
        if inside > 0:
            by_name["inside programs (between ops)"] = inside
        # host spans by millisecond bin; a span longer than MAX_SPAN_NS
        # encloses whole steps and says nothing about one gap
        bins: Dict[int, List[Tuple[str, float, float]]] = {}
        for _, _, events in host:
            for n, s, d in events:
                if 0 < d <= MAX_SPAN_NS and n != WINDOW_SPAN:
                    for b in range(int(s // BIN_NS), int((s + d) // BIN_NS) + 1):
                        bins.setdefault(b, []).append((n, s, s + d))
        starts = [m[1] for m in mods]
        ends = sorted(m[1] + m[2] for m in mods)
        by_end = sorted(mods, key=lambda m: m[1] + m[2])
        for s, e in idle:
            i = bisect.bisect_right(ends, s + 1)
            j = bisect.bisect_left(starts, e - 1)
            prev = program_name(by_end[i - 1][0]) if i else "-"
            nxt = program_name(mods[j][0]) if j < len(mods) else "-"
            best, best_ov, best_len = "-", 0.0, float("inf")
            seen = set()
            for b in range(int(s // BIN_NS), int(e // BIN_NS) + 1):
                for span in bins.get(b, ()):
                    if span in seen:
                        continue
                    seen.add(span)
                    n, a, z = span
                    ov = min(z, e) - max(a, s)
                    if ov > best_ov * 1.001 or (
                            ov > 0 and ov > 0.999 * best_ov
                            and z - a < best_len):
                        best, best_ov, best_len = n, ov, z - a
            key = f"{prev} -> {nxt} | host: {best}"[:120]
            by_name[key] = by_name.get(key, 0.0) + (e - s) / 1e9
        return sorted(by_name.items(), key=lambda kv: -kv[1])

    # -- what the readers and the breakdown ask for -------------------------

    def program_mean_s(self, pattern: str) -> Optional[float]:
        rx = re.compile(pattern)
        d = [x for n, v in self.program_s.items() if rx.search(n) for x in v]
        return sum(d) / len(d) if d else None

    def program_total_s(self, pattern: str) -> float:
        """Per chip: every device runs the program once per execution."""
        rx = re.compile(pattern)
        n = max(1, len(self.devices))
        return sum(x for k, v in self.program_s.items() if rx.search(k)
                   for x in v) / n

    def program_count(self, pattern: str) -> int:
        rx = re.compile(pattern)
        n = max(1, len(self.devices))
        return sum(len(v) for k, v in self.program_s.items()
                   if rx.search(k)) // n

    def op_total_s(self, patterns: List[str]) -> float:
        rxs = [re.compile(p) for p in patterns]
        return sum(v for k, v in self.op_s.items()
                   if any(rx.search(k) for rx in rxs))

    def breakdown(self) -> Dict[str, List]:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in self.gaps[:10]]}


def _main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description="look at a trace, or cut one")
    ap.add_argument("path")
    ap.add_argument("--export")
    ap.add_argument("--from-ms", type=float, default=0.0)
    ap.add_argument("--to-ms", type=float, default=0.0)
    args = ap.parse_args(argv)
    tr = load(args.path)
    for pname, lines in tr["planes"].items():
        print("PLANE", pname)
        for lname, events in lines.items():
            names = sorted({e[0] for e in events})[:6]
            print(f"  LINE {lname!r}: {len(events)} events, e.g. {names}")
    red = Reduced(tr)
    print("window_s", red.window_s, "busy_s", red.busy_s_by_device)
    print("programs", {k: (len(v), sum(v)) for k, v in red.program_s.items()})
    print(json.dumps(red.breakdown(), indent=1))
    if args.export:
        lo = red.window[0] + args.from_ms * 1e6
        hi = red.window[0] + args.to_ms * 1e6
        cut = {"planes": {}}
        for pname, lines in tr["planes"].items():
            keep = {}
            for lname, events in lines.items():
                if pname.startswith("/host:") or lname in (MODULES_LINE,
                                                           OPS_LINE):
                    short = op_name if lname == OPS_LINE else (lambda x: x)
                    ev = [[short(n), max(s, lo) - lo,
                           min(s + d, hi) - max(s, lo)]
                          for n, s, d in events
                          if s < hi and s + d > lo and d > 0
                          and not n.startswith("$")]
                    if ev:
                        keep[lname] = ev
            if keep:
                cut["planes"][pname] = keep
        cut["planes"].setdefault("/host:CPU", {})["bench-tracer"] = [
            [WINDOW_SPAN, 0.0, hi - lo]]
        with open(args.export, "w") as f:
            json.dump(cut, f, separators=(",", ":"))
        print("exported", args.export)
    return 0


if __name__ == "__main__":
    raise SystemExit(_main())
