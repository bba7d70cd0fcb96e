"""When the pod did not run, and by what it was stopped.

A token that arrives late says nothing of why. Three things stop every
thread of a serving process at once, and none of them shows in a phase, a
histogram or a device trace: the garbage collector (a collection holds the
interpreter lock from its first object to its last), the machine (a noisy
neighbour, a live migration, a cgroup freeze: nobody in the process runs)
and one thread that keeps the interpreter lock. :class:`ProcessStops` counts
all three inside the process, always on, from two hooks:

- ``gc.callbacks``: the collector calls :meth:`ProcessStops._on_gc` at the
  start and the stop of every collection, on the thread that triggered it.
  Exact: collections by generation, their pauses, the longest, the objects
  collected. A collection of the OLDEST generation also opens a
  ``gc.collect`` annotation (``obs.trace.annotate``: on the profiler's
  clock beside the engine loop's phases) and leaves a record in the ring.
- a heartbeat: one daemon thread (``shai-heartbeat``) asks for sleeps of
  ``TICK_S`` and reads the monotonic clock and the process's CPU clock at
  each wake. A wake more than ``LATE_S`` behind its due time is a *stop*
  ``[due, woke]``, known only once it has ended. Its cause, in this order:
  ``gc`` where the recorded collections cover more than half of it;
  ``frozen`` where the process's CPU clock advanced by less than a quarter
  of its length (nobody ran: the machine, not the program); else
  ``starved`` (somebody ran, and kept the interpreter lock).

Stdlib only, and nothing from the rest of the package: ``obs`` stays
importable by the engine and by the serving layer alike. The serving app
starts ``PROCESS`` where it starts and stops it where it shuts down
(``serve.app.create_app``); an engine with no app around it has none.
"""

from __future__ import annotations

import gc
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional

from .trace import annotate

#: the sleep the heartbeat asks for: 50 wakes a second, each a clock pair and
#: a compare (some 30 us where the interpreter lock is contended, 0.15%)
TICK_S = 0.02
#: how far behind its due time a wake is a stop. The interpreter hands its
#: lock round every 5 ms (``sys.getswitchinterval``); with five threads that
#: want it (engine loop, event loop, a lane, the client, this one) a fair
#: round is 20 ms and an unlucky one twice that, and a lock handed round must
#: NOT count. The shortest stops the records hold are 0.11 s (PERF.md
#: section 7 (27) (g)), over twice this.
LATE_S = 0.05
#: the stops and oldest-generation collections the ring keeps
STOPS_KEPT = 64
#: the generation whose collection walks the whole heap
OLDEST = 2
CAUSES = ("frozen", "starved", "gc")


class ProcessStops:
    """The process's collections and stops, counted (see the module).

    Two writers, one lock: the collector's callback (any thread, one
    collection at a time) and the heartbeat thread; scrapes read. The lock
    is REENTRANT because a collection can start at any allocation, also at
    one the lock's holder makes: the callback then runs on that thread,
    inside the locked region, and a plain lock would never be given.

    ``clock``, ``cpu_clock``, ``wall`` and ``sleep`` are arguments so that a
    test drives them (``sleep`` defaults to a wait on the halt event, which
    :meth:`stop` cuts short). ``loop_phase``: a callable that returns the
    ``engine-loop`` thread's open phase (``StepTelemetry.open_phase``), set
    by whoever owns an engine; ``None`` with no engine.
    """

    def __init__(self, clock: Callable[[], float] = time.monotonic,
                 cpu_clock: Callable[[], float] = time.process_time,
                 wall: Callable[[], float] = time.time,
                 sleep: Optional[Callable[[float], Any]] = None):
        self._clock, self._cpu_clock, self._wall = clock, cpu_clock, wall
        self._halt = threading.Event()
        self._sleep = sleep if sleep is not None else self._halt.wait
        self._annotate = annotate   # kept here: a callback outlives modules
        self._lock = threading.RLock()
        self._thread: Optional[threading.Thread] = None
        self.started = False        # ever: the counters outlive a stop()
        self.loop_phase: Optional[Callable[[], Optional[str]]] = None
        self._collections = [0, 0, 0]
        self._pause_s = [0.0, 0.0, 0.0]
        self._pause_max_s = 0.0
        self._collected = 0
        # the open collection: its start, and its annotation if it has one
        self._gc_t0: Optional[float] = None
        self._gc_ann = None
        # [t0, t1] of the last collections, for the heartbeat to lay a stop
        # against (a stop of 50 ms holds a handful of young ones at most)
        self._gc_recent: deque = deque(maxlen=32)
        self._stop_s = dict.fromkeys(CAUSES, 0.0)
        self._stop_n = dict.fromkeys(CAUSES, 0)
        self._stop_max_s = 0.0
        # what the process burns in one on-time tick (smoothed): a stop's
        # own CPU is the clock's advance since the last wake LESS this
        self._tick_cpu_s = 0.0
        self._ring: deque = deque(maxlen=STOPS_KEPT)

    # -- start and stop -----------------------------------------------------

    def start(self) -> None:
        """Hook the collector and start the heartbeat; a second start is a
        no-op."""
        with self._lock:
            if self._thread is not None:
                return
            self.started = True
            self._halt.clear()
            gc.callbacks.append(self._on_gc)
            self._thread = threading.Thread(
                target=self._run, daemon=True, name="shai-heartbeat")
            self._thread.start()

    def stop(self) -> None:
        """Unhook and join: ``gc.callbacks`` as found, no thread left. The
        counters stay readable."""
        with self._lock:
            thread, self._thread = self._thread, None
            if thread is None:
                return
            self._halt.set()
            try:
                gc.callbacks.remove(self._on_gc)
            except ValueError:
                pass
            self._gc_t0 = self._gc_ann = None
        thread.join(5.0)

    # -- the collector's callback -------------------------------------------

    def _on_gc(self, phase: str, info: Dict[str, int]) -> None:
        if phase == "start":
            if info["generation"] >= OLDEST:
                self._gc_ann = self._annotate(
                    "gc.collect", generation=info["generation"])
                self._gc_ann.__enter__()
            self._gc_t0 = self._clock()
            return
        t1 = self._clock()
        t0, self._gc_t0 = self._gc_t0, None
        ann, self._gc_ann = self._gc_ann, None
        if ann is not None:
            ann.__exit__(None, None, None)
        if t0 is None:      # hooked between a collection's start and stop
            return
        gen, dt = min(info["generation"], OLDEST), t1 - t0
        with self._lock:
            self._collections[gen] += 1
            self._pause_s[gen] += dt
            self._collected += info["collected"]
            if dt > self._pause_max_s:
                self._pause_max_s = dt
            self._gc_recent.append((t0, t1))
            if gen == OLDEST:
                self._ring.append(self._record(dt, "collection", gen))

    # -- the heartbeat ------------------------------------------------------

    def _run(self) -> None:
        woke, cpu = self._clock(), self._cpu_clock()
        while not self._halt.is_set():
            self._sleep(TICK_S)
            woke, cpu = self.beat(woke + TICK_S, cpu)

    def beat(self, due: float, cpu0: float):
        """One wake, due at ``due``, the CPU clock ``cpu0`` at the wake
        before: counts a stop if it is late. Returns this wake's two
        clocks."""
        now, cpu = self._clock(), self._cpu_clock()
        late, burnt = now - due, cpu - cpu0
        if late <= LATE_S:
            self._tick_cpu_s += 0.25 * (burnt - self._tick_cpu_s)
            return now, cpu
        with self._lock:
            covered = sum(max(0.0, min(t1, now) - max(t0, due))
                          for t0, t1 in self._gc_recent)
            open_t0 = self._gc_t0   # started, its stop not yet recorded
            if open_t0 is not None:
                covered += max(0.0, now - max(open_t0, due))
            ran = max(0.0, burnt - self._tick_cpu_s)
            if covered > late / 2:
                cause = "gc"
            elif ran < late / 4:
                cause = "frozen"
            else:
                cause = "starved"
            self._stop_s[cause] += late
            self._stop_n[cause] += 1
            if late > self._stop_max_s:
                self._stop_max_s = late
            self._ring.append(self._record(late, cause, None, ran))
        return now, cpu

    def _record(self, dur_s: float, cause: str, generation: Optional[int],
                cpu_s: Optional[float] = None) -> Dict[str, Any]:
        """A ring record, stamped (as a step record is) when it is written:
        at the END of what it records. ``cpu_s``: a stop's own CPU seconds,
        what its cause was decided on."""
        phase = self.loop_phase
        try:
            open_phase = phase() if phase is not None else None
        except Exception:   # a dead engine must not break the count
            open_phase = None
        return {"ts": round(self._wall(), 4), "dur_s": round(dur_s, 6),
                "cause": cause, "generation": generation,
                "loop_phase": open_phase,
                "cpu_s": None if cpu_s is None else round(cpu_s, 6)}

    # -- readouts -----------------------------------------------------------

    def recent(self) -> List[Dict[str, Any]]:
        """The ring, oldest first: the ``stops`` of ``/debug/flight``."""
        with self._lock:
            return list(self._ring)

    def snapshot(self) -> Dict[str, Dict[str, Any]]:
        """The groups ``gc`` and ``stops`` of the engine's snapshot; empty
        while the instrument was never started."""
        if not self.started:
            return {}
        with self._lock:
            n, pause = list(self._collections), list(self._pause_s)
            out_gc: Dict[str, Any] = {
                "pause_s": sum(pause), "full_pause_s": pause[OLDEST],
                "pause_max_s": self._pause_max_s,
                "collected": self._collected}
            stop_s, stop_n = dict(self._stop_s), dict(self._stop_n)
            stop_max = self._stop_max_s
        for gen in range(OLDEST + 1):
            out_gc[f"collections_gen{gen}"] = n[gen]
            out_gc[f"pause_s_gen{gen}"] = pause[gen]
        out_stops: Dict[str, Any] = {"max_s": stop_max}
        for cause in CAUSES:
            out_stops[f"{cause}_s"] = stop_s[cause]
            out_stops[f"count_{cause}"] = stop_n[cause]
        return {"gc": out_gc, "stops": out_stops}


#: the process's one instrument: what ``serve.app`` starts and stops
PROCESS = ProcessStops()
