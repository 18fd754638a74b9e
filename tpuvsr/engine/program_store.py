"""A persistent store of traced programs, beside the compile cache.

XLA's persistent cache spares a process the compile of a program it
has compiled before, but only once the process has rebuilt the very
same module: traced the Python, lowered the jaxpr.  For the level
program of the BFS engines that is most of what a small job costs on
a warm cache.  This store keeps the program's ``jax.export``
(StableHLO and its calling convention, no executable) under the
directory ``registry.ensure_compile_cache()`` returns, and a process
that finds it there lowers a wrapper around it in under a second
instead.  The wrapper's module is the same on both paths, in every
process, so XLA's cache finds the executable that the process which
stored the program compiled.  XLA's cache stays the only store of
executables; nothing here is pickled, and deleting the directory is
always safe.

Who comes through here: every build of ``DeviceBFS._level``, which
``PagedBFS`` shares: the CLI, ``chip_smoke.py``, the benchmark's
windows, and the served path, whose engines
``resilience/supervisor.py`` ``_make_engine`` constructs anew for each
job.  Since ISSUE 50 ``ShardedBFS``'s ``shard_map`` step does too, on a
mesh of one process (``ShardedBFS._step_key_doc``: the mesh's axis and
shape are part of the key, and the export says for how many devices
it was made); a mesh of several processes builds its step as before.

**The key covers every input of the trace**, or the store would hand
back a wrong program and a wrong count.  It is a digest of:

- every ``*.py`` under ``tpuvsr/`` (``source_digest``: any change of
  code is a miss);
- the jax and jaxlib versions, the platform and ``device_kind``,
  ``jax_enable_x64``;
- every ``TPUVSR_*`` environment variable, except those that name a
  run or a place and that no trace reads (``RUN_SCOPED_ENV``: the
  trace-context triple a worker exports around each job, the profile
  directory, the spool, the host name);
- what the engine says its trace reads (``DeviceBFS._level_key_doc``;
  ``ShardedBFS._step_key_doc`` has the mesh, the bucket and the
  deadlock switch where that has the chunk and the hash mode):
  the spec's digest and its module's AST, the engine's class, the
  kernel and the codec (class and every plain attribute, so ``R, V, M,
  MAX_OPS, NHDR``, ``perms``, timer and restart limits, the lane
  tables), the pruned actions, ``tile``, ``chunk_tiles``, ``commit``,
  ``hash_mode``, the expansion caps and multipliers, the upper-case
  constants of ``device_bfs`` and ``fpset`` (``EXPAND_BLOCK``,
  ``COMMIT_PIECE``, ...), the invariant names, the pack, canon, bounds
  and POR manifests, edge emission, the debug flag;
- the types of the program's arguments (the capacities live there).

A kernel, codec or engine whose class the source digest does not
determine (``describe`` meets a class that is not a module-level class
of the ``tpuvsr`` package: one defined in a test file, or inside a
function, where it closes over values nobody can see) is never stored
or loaded: its programs are built as before and read ``bypass``.  That
is the one branch, and it depends on what the code can observe.

An entry is ``sha256(payload) + payload``, written to a temporary file
and renamed.  An entry that is missing, cut short, unreadable to this
jax or made for other argument types is a miss that overwrites it;
a directory that cannot be written is a miss every time.  Neither
fails a job.
"""

from __future__ import annotations

import hashlib
import json
import os
import time

import numpy as np

import jax
import jax.export

from ..models import registry
from ..obs import builds
from ..obs.journal import TRACE_ENV_KEYS

PACKAGE = __name__.split(".")[0]
PACKAGE_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: ``TPUVSR_*`` variables that name one run or one place, and that
#: only host code reads: a worker exports a new trace triple around
#: every job, so a key that held them would never be found again
RUN_SCOPED_ENV = frozenset(TRACE_ENV_KEYS) | {
    "TPUVSR_PROFILE", "TPUVSR_SPOOL", "TPUVSR_HOST"}

_DIGEST_BYTES = hashlib.sha256().digest_size


class Uncovered(Exception):
    """`describe` met a class whose source the key does not cover."""


def source_digest(root):
    """sha256 over every ``*.py`` under `root`, by relative path."""
    h = hashlib.sha256()
    paths = []
    for where, dirs, files in os.walk(root):
        dirs[:] = sorted(d for d in dirs if d != "__pycache__")
        paths += [os.path.join(where, f) for f in files
                  if f.endswith(".py")]
    for path in sorted(paths):
        with open(path, "rb") as f:
            body = f.read()
        h.update(os.path.relpath(path, root).encode() + b"\0")
        h.update(str(len(body)).encode() + b"\0" + body)
    return h.hexdigest()


def _class_name(cls):
    """`cls` by module and qualified name; `Uncovered` unless that
    name finds it again inside the package (a class made in a function
    body or outside ``tpuvsr`` is not what its source file says)."""
    covered = (cls.__module__.split(".")[0] == PACKAGE
               and "<locals>" not in cls.__qualname__)
    if not covered:
        raise Uncovered(f"{cls.__module__}.{cls.__qualname__}")
    return f"{cls.__module__}.{cls.__qualname__}"


def describe(value, _open=None):
    """`value` as JSON-able plain data that is equal whenever the
    values are, in every process: arrays by type and content, sets and
    dicts sorted, an object of a covered class by its class and the
    plain data of its attributes.  Callables are left out: what they
    compute is their source.  Raises `Uncovered` (see above)."""
    seen = set() if _open is None else _open
    if value is None or isinstance(value, (bool, int, float, str)):
        return value
    if isinstance(value, (np.ndarray, np.generic, jax.Array)):
        a = np.asarray(value)
        return ["array", str(a.dtype), list(a.shape),
                hashlib.sha256(np.ascontiguousarray(a).tobytes())
                .hexdigest()]
    if isinstance(value, (list, tuple)):
        return [describe(v, seen) for v in value]
    if isinstance(value, (set, frozenset)):
        return ["set"] + sorted(
            (describe(v, seen) for v in value),
            key=lambda d: json.dumps(d, sort_keys=True))
    if isinstance(value, dict):
        items = [[describe(k, seen), describe(v, seen)]
                 for k, v in value.items()]
        return ["dict"] + sorted(
            items, key=lambda kv: json.dumps(kv[0], sort_keys=True))
    if isinstance(value, type):
        return ["class", _class_name(value)]
    if callable(value):
        return None
    name = _class_name(type(value))
    if id(value) in seen:       # a cycle: the object is being described
        return ["again", name]
    attrs = dict(getattr(value, "__dict__", {}))
    for cls in type(value).__mro__:
        for slot in getattr(cls, "__slots__", ()):
            if hasattr(value, slot):
                attrs[slot] = getattr(value, slot)
    seen.add(id(value))
    try:
        return ["object", name, describe(attrs, seen)]
    finally:
        seen.discard(id(value))


def module_constants(*modules):
    """The upper-case plain globals of `modules` (``COMMIT_PIECE``,
    ``_CLAIM_BARRIER``): what a trace reads of a module besides its
    source, which a test may have patched."""
    return {f"{m.__name__}.{k}": v for m in modules
            for k, v in sorted(vars(m).items())
            if k.lstrip("_").isupper()
            and isinstance(v, (bool, int, float, str, tuple))}


def process_doc():
    """What a trace reads of the process: the code, jax, the device,
    the environment."""
    import jaxlib
    device = registry.device_doc()
    return {"source": source_digest(PACKAGE_ROOT),
            "jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "platform": device["platform"],
            "device_kind": device["device_kind"],
            "x64": bool(jax.config.jax_enable_x64),
            "env": {k: v for k, v in sorted(os.environ.items())
                    if k.startswith("TPUVSR_")
                    and k not in RUN_SCOPED_ENV}}


def store_directory():
    return os.path.join(registry.ensure_compile_cache(), "tpuvsr-export")


def _signature(args):
    """The types of `args`: what one specialization of a program is
    made for (hashable; `describe`-able through `_signature_doc`)."""
    leaves, tree = jax.tree.flatten(args)
    return tree, tuple(jax.api_util.shaped_abstractify(a) for a in leaves)


def _signature_doc(signature):
    tree, avals = signature
    return [str(tree), [[list(a.shape), str(a.dtype), bool(a.weak_type)]
                        for a in avals]]


def program_key(doc, signature):
    """The store's key for the program that `doc` (everything its trace
    reads) makes for arguments of `signature`."""
    text = json.dumps([doc, _signature_doc(signature)], sort_keys=True)
    return hashlib.sha256(text.encode()).hexdigest()


def _entry_path(key):
    return os.path.join(store_directory(), key + ".jaxexport")


def load(key, signature):
    """The stored program of `key`, or None: no entry, an entry cut
    short or damaged, one this jax cannot read, or one made for other
    argument types or another platform."""
    try:
        with open(_entry_path(key), "rb") as f:
            blob = f.read()
    except OSError:
        return None
    digest, payload = blob[:_DIGEST_BYTES], blob[_DIGEST_BYTES:]
    if hashlib.sha256(payload).digest() != digest:
        return None
    try:
        exported = jax.export.deserialize(bytearray(payload))
    except Exception:   # noqa: BLE001 - whatever an old entry raises
        return None
    tree, avals = signature
    # a call's arguments are (positional, keyword): the level program
    # takes positional ones only
    if (exported.in_tree != jax.tree.structure((tree.unflatten(avals), {}))
            or [(a.shape, a.dtype) for a in exported.in_avals]
            != [(a.shape, a.dtype) for a in avals]
            or jax.default_backend() not in exported.platforms):
        return None
    return exported


def save(key, payload):
    """Write `payload` (a serialized program) under `key`, atomically;
    False where the directory cannot be written."""
    path = _entry_path(key)
    tmp = f"{path}.{os.getpid()}.tmp"
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(tmp, "wb") as f:
            f.write(hashlib.sha256(payload).digest() + payload)
        os.replace(tmp, path)
    except OSError:
        return False
    return True


class StoredProgram:
    """``jax.jit(make(), donate_argnums=...)`` through the store.

    Called, it runs the program: the first call with arguments of new
    types builds one (a hit, a miss or a bypass; module docstring) and
    keeps it for this object's later calls.  Nothing is kept beyond
    the object: a new engine always reads the file, so the first job
    of a process costs what it costs.  ``trace`` and ``lower`` are
    those of the direct jit: the program as traced now, never the
    stored one.

    `key_doc` is everything the trace of ``make()`` reads besides its
    arguments' types and `process_doc`, or None where that cannot be
    told (bypass)."""

    def __init__(self, make, name, donate_argnums, key_doc):
        self._make = make
        self._name = name
        self._donate = tuple(donate_argnums)
        self._key_doc = key_doc
        self._direct_jit = None
        self._programs = {}     # signature -> compiled program

    @property
    def _direct(self):
        if self._direct_jit is None:
            self._direct_jit = jax.jit(self._make(),
                                       donate_argnums=self._donate)
        return self._direct_jit

    def trace(self, *args):
        return self._direct.trace(*args)

    def lower(self, *args):
        return self._direct.lower(*args)

    def __call__(self, *args):
        signature = _signature(args)
        program = self._programs.get(signature)
        if program is None:
            program = self._programs[signature] = self.lowered_for(
                signature, args).compile()
        return program(*args)

    def lowered_for(self, signature, args):
        """The program for `args` (of types `signature`), lowered and
        ready for its backend stage, with the calling thread's build
        meter told how it came out of the store."""
        if self._key_doc is None:
            lowered = self._direct.lower(*args)
            builds.export_store("bypass")
            return lowered
        key = program_key([process_doc(), self._key_doc], signature)
        clock = time.perf_counter
        outcome, store_s, t0 = "hit", 0.0, clock()
        exported = load(key, signature)
        if exported is None:
            outcome = "miss"
            traced = jax.export.export(self._direct)(*args)
            t0 = clock()
            payload = bytes(traced.serialize())
            save(key, payload)
            store_s, t0 = clock() - t0, clock()
            # both paths run the wrapper of what `load` reads back:
            # the module XLA's cache is asked for is the same
            exported = jax.export.deserialize(bytearray(payload))

        # named as the direct program is: the XLA module, the profile
        # and the `build` event keep the name they had
        def program(*a):
            return exported.call(*a)
        program.__name__ = self._name
        lowered = jax.jit(program,
                          donate_argnums=self._donate).lower(*args)
        builds.export_store(outcome, load_s=clock() - t0,
                            store_s=store_s)
        return lowered
