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

A read-back is ``compilation_cache.get_executable_and_time``: the
cache's ``get`` (a file read), ``decompress_executable``, and
``backend.deserialize_executable`` (deserialize and load are one
runtime call from Python).  ``install_read_back_seam`` times the first
through a delegating ``CacheInterface`` around JAX's own cache and the
second through a wrapper of the function; the third is JAX's
``cache_retrieval_time_sec`` of the same program less the two, so the
three sum to ``build_cache_load_s``.  Each is a ``TraceAnnotation`` of
``spans.READ_BACK_PARTS`` in a profiled run (the load's opens where
the decompression returns and closes on the retrieval event).  Only
hits count: a ``get`` that finds nothing ends in no retrieval event
and its seconds are dropped with the program's record.  On a JAX
without these names nothing is installed and the gauges are absent.

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

from . import spans

TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"
CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"

#: a program whose three stages sum to this many seconds gets a
#: ``build`` event in the run journal
JOURNAL_BUILD_S = 0.5

#: what a program read back through the seam adds to its record, and
#: so to the journal's ``build`` event
READ_BACK_KEYS = ("cache_read_s", "cache_decompress_s",
                  "executable_load_s", "cache_read_bytes",
                  "cache_decompressed_bytes")

_local = threading.local()
_registered = False
_seam = None            # None: not tried; then whether it is installed
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
        #: over the programs read back through the seam, by key
        self.read_back_totals = {k: 0.0 if k.endswith("_s") else 0
                                 for k in READ_BACK_KEYS}
        #: TraceAnnotation when the observer's run is profiled
        self.annotation = None
        self._load_mark = None      # the open executable_load annotation
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
            self._close_load_mark()
            rec = self._pending
            if "cache_decompress_s" in rec:
                # the retrieval less the read and the decompression:
                # deserialize + load
                rec["executable_load_s"] = max(
                    0.0, secs - rec["cache_read_s"]
                    - rec["cache_decompress_s"])
                for key in READ_BACK_KEYS:
                    self.read_back_totals[key] += rec[key]
        elif event == BACKEND_EVENT:
            self._close_load_mark()     # a load that raised
            self.backend_s += secs
            self.programs += 1
            rec, self._pending = self._pending, self._fresh()
            self._open.clear()
            if "executable_load_s" not in rec:
                # no retrieval event: what was read did not load
                for key in READ_BACK_KEYS:
                    rec.pop(key, None)
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

    def read_back(self, name, secs, nbytes):
        """One step of the pending program's read-back took `secs` and
        gave `nbytes` (``_timed_step``).  Counted once the retrieval
        event says the read-back was a hit."""
        rec = self._pending
        if name == spans.BUILD_CACHE_READ:
            rec["cache_read_s"] = rec.get("cache_read_s", 0.0) + secs
            rec["cache_read_bytes"] = nbytes
        else:
            rec["cache_decompress_s"] = secs
            rec["cache_decompressed_bytes"] = nbytes
            # what follows on this thread, up to the retrieval event,
            # is the runtime's deserialize + load
            if self.annotation is not None:
                self._load_mark = self.annotation(
                    spans.BUILD_EXECUTABLE_LOAD)
                self._load_mark.__enter__()

    def _close_load_mark(self):
        mark, self._load_mark = self._load_mark, None
        if mark is not None:
            mark.__exit__(None, None, None)

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
        if _seam:       # absent where JAX gave no seam, never guessed
            for key, total in self.read_back_totals.items():
                if key.endswith("_s"):
                    metrics.gauge("build_" + key, total)
                else:
                    metrics.counters["build_" + key] = total
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


def _timed_step(name, fn, *args):
    """Run one step of a read-back (`fn`: the cache's ``get``, or
    ``decompress_executable``) for the calling thread's meter: its
    seconds, its bytes, its annotation.  Nobody's without a meter."""
    meter = getattr(_local, "meter", None)
    if meter is None:
        return fn(*args)
    mark = None if meter.annotation is None else meter.annotation(name)
    if mark is not None:
        mark.__enter__()
    t0 = time.perf_counter()
    try:
        out = fn(*args)
    finally:
        secs = time.perf_counter() - t0
        if mark is not None:
            mark.__exit__(None, None, None)
    if out is not None:         # a `get` that found nothing: a miss
        meter.read_back(name, secs, len(out))
    return out


def install_read_back_seam():
    """Time JAX's persistent-cache read where it happens (module
    docstring).  The cache JAX made, or makes later, is wrapped in a
    ``CacheInterface`` that forwards everything to it: same directory,
    same keys, same bytes.  Once a process; returns whether this JAX
    has the seam."""
    global _seam
    with _lock:
        if _seam is not None:
            return _seam
        try:
            from jax._src import compilation_cache as cc
            from jax._src.compilation_cache_interface import \
                CacheInterface
        except ImportError:
            _seam = False
            return _seam
        if not all(hasattr(cc, name) for name in (
                "get_file_cache", "decompress_executable", "_cache",
                "get_executable_and_time")):
            _seam = False
            return _seam
        make, decompress = cc.get_file_cache, cc.decompress_executable

        class TimedCache(CacheInterface):
            """JAX's cache, its ``get`` timed."""

            def __init__(self, inner):
                self._inner = inner
                self._path = inner._path

            def get(self, key):
                return _timed_step(spans.BUILD_CACHE_READ,
                                   self._inner.get, key)

            def put(self, key, value):
                return self._inner.put(key, value)

            def __getattr__(self, name):
                return getattr(self._inner, name)

        def get_file_cache(path):
            made = make(path)
            return made if made is None else (TimedCache(made[0]),
                                              made[1])

        def decompress_executable(executable):
            return _timed_step(spans.BUILD_CACHE_DECOMPRESS, decompress,
                               executable)

        cc.get_file_cache = get_file_cache
        cc.decompress_executable = decompress_executable
        if cc._cache is not None:       # made before this call
            cc._cache = TimedCache(cc._cache)
        _seam = True
        return _seam


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
