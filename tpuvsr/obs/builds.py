"""Build counters from inside the program.

One process-wide pair of listeners on JAX's own monitoring events
forwards every build to the ``BuildMeter`` of the observer that is
running on the calling thread, and does nothing when none is.  Always
on: the events fire once per built program, never per dispatch.

A program is built in three stages, each with its own event (jax
0.9.0; the durations carry ``fun_name``):

    /jax/core/compile/jaxpr_trace_duration           trace  (Python)
    /jax/core/compile/jaxpr_to_mlir_module_duration  lower  (Python)
    /jax/core/compile/backend_compile_duration       backend: XLA
        compiles, or the persistent cache is read; it ends the program

and inside the backend stage ``/jax/compilation_cache/cache_hits`` (read
back), ``.../cache_misses`` (compiled and written) and
``.../cache_retrieval_time_sec`` say which.  A jit called while another
is traced reports its own trace from inside the outer one's: the meter
counts the union, so ``build_trace_s`` never counts a second twice.

A stage that a program's trace runs many times with equal argument
types (the engines' trace-once stages, ``engine/device_bfs.py``) says
so here: ``shared_stage`` counts every use and, apart, the uses whose
Python body really ran.

A program that went to the store of traced programs
(``engine/program_store.py``) says how it came out, just before its
backend stage: ``export_store`` counts the hits and the misses, the
seconds of each, and stamps the outcome on the program's record.
"""

from __future__ import annotations

import threading
import time

TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"
CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"

#: a program whose three stages sum to this many seconds gets a
#: ``build`` event in the run journal
JOURNAL_BUILD_S = 0.5

_local = threading.local()
_registered = False
_lock = threading.Lock()


class BuildMeter:
    """What one observer's run built.  ``report(record)`` is called
    with every program of ``JOURNAL_BUILD_S`` or more."""

    def __init__(self, report=None, clock=time.perf_counter):
        self.trace_s = self.lower_s = self.backend_s = 0.0
        self.cache_load_s = 0.0
        self.programs = self.cache_hits = self.cache_misses = 0
        self.shared_calls = self.shared_traces = 0
        self.export_hits = self.export_misses = 0
        self.export_load_s = self.export_store_s = 0.0
        self._report = report
        self._clock = clock
        self._open = []         # top-level trace intervals [start, secs]
        self._pending = self._fresh()

    @staticmethod
    def _fresh():
        return {"fun_name": None, "trace_s": 0.0, "lower_s": 0.0,
                "cache": "none", "export": "none"}

    def duration(self, event, secs, fun_name=None):
        if event == TRACE_EVENT:
            # an interval that starts before earlier ones encloses
            # them: count it in their place
            start = self._clock() - secs
            inner = 0.0
            while self._open and self._open[-1][0] >= start:
                inner += self._open.pop()[1]
            self._open.append([start, secs])
            self.trace_s += secs - inner
            self._pending["trace_s"] += secs - inner
            self._pending["fun_name"] = fun_name
        elif event == LOWER_EVENT:
            self.lower_s += secs
            self._pending["lower_s"] += secs
            self._pending["fun_name"] = fun_name
        elif event == CACHE_LOAD_EVENT:
            self.cache_load_s += secs
        elif event == BACKEND_EVENT:
            self.backend_s += secs
            self.programs += 1
            rec, self._pending = self._pending, self._fresh()
            self._open.clear()
            rec["backend_s"] = secs
            rec["fun_name"] = fun_name or rec["fun_name"]
            total = rec["trace_s"] + rec["lower_s"] + secs
            if self._report is not None and total >= JOURNAL_BUILD_S:
                self._report(rec)

    def event(self, event):
        # both fire inside the backend stage, before its duration:
        # they belong to the program that the next BACKEND_EVENT closes
        if event == CACHE_HIT_EVENT:
            self.cache_hits += 1
            self._pending["cache"] = "hit"
        elif event == CACHE_MISS_EVENT:
            self.cache_misses += 1
            self._pending["cache"] = "miss"

    def export(self, outcome, load_s, store_s):
        # just before the backend stage of the program it describes
        self.export_hits += outcome == "hit"
        self.export_misses += outcome == "miss"
        self.export_load_s += load_s
        self.export_store_s += store_s
        self._pending["export"] = outcome

    def stamp(self, metrics):
        """Write the gauges and counters of the metrics document."""
        metrics.gauge("build_trace_s", self.trace_s)
        metrics.gauge("build_lower_s", self.lower_s)
        metrics.gauge("build_backend_s", self.backend_s)
        metrics.gauge("build_cache_load_s", self.cache_load_s)
        metrics.gauge("build_export_load_s", self.export_load_s)
        metrics.gauge("build_export_store_s", self.export_store_s)
        for name, n in (("build_programs", self.programs),
                        ("build_cache_hits", self.cache_hits),
                        ("build_cache_misses", self.cache_misses),
                        ("build_shared_calls", self.shared_calls),
                        ("build_shared_traces", self.shared_traces),
                        ("build_export_hits", self.export_hits),
                        ("build_export_misses", self.export_misses)):
            metrics.counters[name] = n


def _on_duration(event, secs, fun_name=None, **_kw):
    meter = getattr(_local, "meter", None)
    if meter is not None:
        meter.duration(event, secs, fun_name)


def _on_event(event, **_kw):
    meter = getattr(_local, "meter", None)
    if meter is not None:
        meter.event(event)


def shared_stage(traced):
    """One use of a trace-once stage while a program is traced;
    `traced` when it is the stage's Python body running, so not a
    reuse of an earlier trace."""
    meter = getattr(_local, "meter", None)
    if meter is not None:
        if traced:
            meter.shared_traces += 1
        else:
            meter.shared_calls += 1


def export_store(outcome, load_s=0.0, store_s=0.0):
    """A program is about to enter its backend stage as a ``hit`` of
    the store of traced programs (`load_s`: read, deserialize, lower
    the wrapper), a ``miss`` (`store_s`: serialize, write; the trace
    and the lowering are counted by their own events) or a
    ``bypass``."""
    meter = getattr(_local, "meter", None)
    if meter is not None:
        meter.export(outcome, load_s, store_s)


def _register():
    global _registered
    with _lock:
        if _registered:
            return
        import jax.monitoring as mon
        mon.register_event_duration_secs_listener(_on_duration)
        mon.register_event_listener(_on_event)
        _registered = True


def attach(meter):
    """Make `meter` the calling thread's; returns the one it replaces
    (an enclosing run's), for ``detach``."""
    _register()
    previous = getattr(_local, "meter", None)
    _local.meter = meter
    return previous


def detach(previous=None):
    _local.meter = previous
