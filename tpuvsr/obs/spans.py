"""The fixed vocabulary of names the program writes into a profile.

Two kinds, both documented in ``SCHEMA.md``:

* **host spans** — one per host phase.  ``RunObserver.span(name,
  **attrs)`` times the phase (``ENGINE_SPANS[name]`` is its key in the
  metrics document's ``phases``) and, when profiling is on, opens a
  ``jax.profiler.TraceAnnotation`` of the same name, so the phase lies
  on the device trace's clock.  Numbers (``depth=``, ``tiles=``) are
  attributes, never part of the name.  The service's spans carry no
  phase: the job journal already times a job.
* **parts of a phase** — what a host phase does, named where it does
  it.  ``RunObserver.part(name, **attrs)`` times the part INSIDE its
  phase (the phase's own seconds do not change; the part's go to the
  metrics document's ``phase_parts``) and, when profiling is on, opens
  a ``TraceAnnotation`` of the part's name nested in its phase's.  The
  three parts of a read-back from the persistent cache are timed where
  JAX reads (``builds.py``), under whatever phase called the fresh
  jit: annotations and gauges, not ``phase_parts``.
* **level-program stages** — ``jax.named_scope`` names around the
  stages of the level program and the sharded step.  Metadata only:
  they reach the trace on every device operation of the stage and
  change no operation, shape or program.

This module imports nothing: the service front imports ``tpuvsr.obs``
without JAX.
"""

CHECK = "tpuvsr.engine.check"
BUILD = "tpuvsr.engine.build"
DISPATCH = "tpuvsr.engine.dispatch"
INFLIGHT = "tpuvsr.engine.inflight"
HOST_SYNC = "tpuvsr.engine.host_sync"
CHECKPOINT = "tpuvsr.engine.checkpoint"
INIT = "tpuvsr.engine.init"
GRAPH_BUILD = "tpuvsr.engine.graph_build"
PAGE_IN = "tpuvsr.engine.page_in"
PAGE_OUT = "tpuvsr.engine.page_out"
BOUNDARY = "tpuvsr.engine.boundary"
FINISH = "tpuvsr.engine.finish"

#: host span -> phase key of the ``tpuvsr-metrics/1`` document
ENGINE_SPANS = {
    CHECK: "check",             # the run's root frame: what no span below names
    BUILD: "compile",           # first call of a fresh jit
    DISPATCH: "dispatch",       # enqueue of a built program
    INFLIGHT: "inflight",       # blocked wait on the oldest ticket
    HOST_SYNC: "host_sync",     # scalar pulls, lvl_buf reads, edge drains
    CHECKPOINT: "checkpoint",   # level-boundary snapshot write
    INIT: "init",               # Init registration + buffer allocation
    GRAPH_BUILD: "graph_build",  # liveness: behavior-graph construction
    PAGE_IN: "page_in",         # paged engine: one frontier page, host -> device
    PAGE_OUT: "page_out",       # paged engine: the next buffer's pages, device -> host
    BOUNDARY: "boundary",       # last collect of a level (paged: a chunk) -> next launch
    FINISH: "finish",           # an engine's _finish, up to RunObserver.finish
}

BUILD_CACHE_READ = "tpuvsr.engine.build.cache_read"
BUILD_CACHE_DECOMPRESS = "tpuvsr.engine.build.cache_decompress"
BUILD_EXECUTABLE_LOAD = "tpuvsr.engine.build.executable_load"
INIT_STATES = "tpuvsr.engine.init.states"
INIT_FINGERPRINT = "tpuvsr.engine.init.fingerprint"
INIT_DEVICE = "tpuvsr.engine.init.device"
CHECKPOINT_PULL = "tpuvsr.engine.checkpoint.pull"
CHECKPOINT_WRITE = "tpuvsr.engine.checkpoint.write"
CHECKPOINT_DURABLE = "tpuvsr.engine.checkpoint.durable"

#: part of a host phase -> (phase key, part key)
ENGINE_PARTS = {
    # a read-back from the persistent cache, timed where JAX reads
    BUILD_CACHE_READ: ("compile", "cache_read"),
    BUILD_CACHE_DECOMPRESS: ("compile", "cache_decompress"),
    BUILD_EXECUTABLE_LOAD: ("compile", "executable_load"),
    INIT_STATES: ("init", "states"),            # the interpreter's share
    INIT_FINGERPRINT: ("init", "fingerprint"),  # fp_batch up to its pull
    INIT_DEVICE: ("init", "device"),            # table, insert, buffers
    CHECKPOINT_PULL: ("checkpoint", "pull"),    # pointers, rows, table
    CHECKPOINT_WRITE: ("checkpoint", "write"),  # the .npz payloads
    # CRCs, manifest, fsyncs, renames
    CHECKPOINT_DURABLE: ("checkpoint", "durable"),
}

#: the parts ``builds.py`` times inside JAX's cache read: no
#: ``RunObserver.part`` opens them
READ_BACK_PARTS = (BUILD_CACHE_READ, BUILD_CACHE_DECOMPRESS,
                   BUILD_EXECUTABLE_LOAD)

JOB = "tpuvsr.service.job"
LOAD_SPEC = "tpuvsr.service.load_spec"
BUILD_ENGINE = "tpuvsr.service.build_engine"
RUN = "tpuvsr.service.run"
SETTLE = "tpuvsr.service.settle"

#: the service worker's spans (profile only; no engine phase)
SERVICE_SPANS = (JOB, LOAD_SPEC, BUILD_ENGINE, RUN, SETTLE)

GUARD_MATRIX = "tpuvsr.level.guard_matrix"
COMPACT = "tpuvsr.level.compact"
EXPAND = "tpuvsr.level.expand"
CANON = "tpuvsr.level.canon"
FINGERPRINT = "tpuvsr.level.fingerprint"
FPSET_INSERT = "tpuvsr.level.fpset_insert"
PACK_SCATTER = "tpuvsr.level.pack_scatter"
INVARIANTS = "tpuvsr.level.invariants"
EDGE_EMIT = "tpuvsr.level.edge_emit"
SHARD_BUCKET = "tpuvsr.shard.bucket"
SHARD_ALL_TO_ALL = "tpuvsr.shard.all_to_all"

#: ``jax.named_scope`` names of the level program's stages
LEVEL_STAGES = (GUARD_MATRIX, COMPACT, EXPAND, CANON, FINGERPRINT,
                FPSET_INSERT, PACK_SCATTER, INVARIANTS, EDGE_EMIT,
                SHARD_BUCKET, SHARD_ALL_TO_ALL)


def build_phase(fresh):
    """The span of a jitted call: ``BUILD`` for the first call of a
    fresh jit (it traces, lowers and compiles or loads synchronously),
    ``DISPATCH`` for the enqueue of a built program."""
    return BUILD if fresh else DISPATCH
