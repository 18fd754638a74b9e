"""Model registry: which reference modules have a compiled device
kernel, and how to build one from a bound spec.

The engines (device_bfs, device_sim, sharded_bfs) are kernel-agnostic:
they consume the kernel interface (action_names, lane tables, guard/
action fns, step_all, fingerprint*, invariant_fn) and the codec
interface (encode/decode/zero_state/pad_msgs/MSG_KEYS/shape).  This
module is the one place that maps a module name to an implementation.

Every module in the reference corpus has a compiled kernel, built as a
subclass tower that mirrors the specs' own progression: VSR stands
alone (recv-set quorums, client table, RestartEmpty); ST03 is the base
of the analysis family (bag-tombstone quorums, AnyDest, state
transfer) -> A01/I01 (assume/increment view modes, packed entries,
ResendSVC) and AS04 (app-state executor, recv_dvc slots) -> RR05
(crash recovery) -> AL05 (async-log prefix survival) and CP06
(checkpointing, NoOp GC, dual-mode replies).
"""

from __future__ import annotations

import os

import numpy as np

import jax

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def ensure_compile_cache():
    """Persistent-compilation-cache setup, shared by every engine entry
    point (device_bfs, device_sim, sharded_bfs, make_model) and the
    tests.

    The level kernels take minutes to compile; persisting the binaries
    lets CLI, tests, scripts and chip_smoke.py share one cache.  When
    ``JAX_COMPILATION_CACHE_DIR`` is set JAX already keeps its cache
    there and nothing is set in code; otherwise the cache is the fixed
    ``<checkout>/.jax_cache`` (the path is part of the cache key, so it
    must not move).  The cache's reads are timed where they happen
    (``obs/builds.install_read_back_seam``).  Returns the directory in
    use."""
    from ..obs import builds
    builds.install_read_back_seam()
    if not jax.config.jax_compilation_cache_dir:
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(REPO, ".jax_cache"))
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          5.0)
    return jax.config.jax_compilation_cache_dir


def device_doc():
    """The backend this process really has, as JAX reports it: stamped
    on the CLI log and result, ``run_start`` and ``job_started``."""
    devs = jax.devices()
    return {"platform": devs[0].platform,
            "device_kind": devs[0].device_kind,
            "device_count": len(devs)}


def ensure_debug_flags():
    """Opt-in numerical debugging for device-engine runs:
    ``TPUVSR_DEBUG_NANS=1`` enables jax_debug_nans (every dispatch
    checks outputs) and tells the engines to assert on kernel overflow
    flags instead of only surfacing them as growth events.  Returns
    True when debug mode is active."""
    if os.environ.get("TPUVSR_DEBUG_NANS") != "1":
        return False
    if not jax.config.jax_debug_nans:
        jax.config.update("jax_debug_nans", True)
    return True


def value_perm_table(spec, codec, fold_symmetry=True):
    """spec.symmetry_perms (ModelValue maps) -> [P, V+1] id table with
    the identity first (kernels take the min over rows).  With
    ``fold_symmetry=False`` only the identity row is emitted — the
    ISSUE 11 mode where the engine's CanonSpec (engine/canon.py) owns
    orbit reduction by state canonicalization instead of the kernel's
    min-over-permuted-hashes fold (one relabel-and-compare network per
    state beats P full-state hashes, and ``-symmetry off`` becomes a
    real A/B lever)."""
    V = codec.shape.V
    rows = [np.arange(V + 1, dtype=np.int32)]
    if fold_symmetry:
        for p in spec.symmetry_perms:
            row = np.arange(V + 1, dtype=np.int32)
            for mv_from, mv_to in p.items():
                row[codec.value_id[mv_from]] = codec.value_id[mv_to]
            rows.append(row)
    return np.stack(rows)


def has_device_model(spec) -> bool:
    """True if a compiled device kernel exists for this module AND the
    bound constants fit its dense layout (e.g. the VSR layout refuses
    ClientCount != 1)."""
    from ..core.values import TLAError
    try:
        codec_cls, _ = _resolve(spec.module.name)
        codec_cls(spec.ev.constants)
        return True
    except (KeyError, TLAError):
        return False


def make_model(spec, max_msgs=None, fold_symmetry=True):
    """Build (codec, kernel) for a bound spec.

    With TPUVSR_COMPILED=1 the kernel's guard/action/invariant fns are
    compiled from the spec AST (lower/compile.py) instead of using the
    hand-written kernel — the hand kernel stays the differential
    oracle (tests/test_lower.py).

    ``fold_symmetry=False`` builds the kernel with an identity-only
    permutation table: its fingerprints hash the state AS GIVEN, and
    symmetry reduction (when the cfg declares it) is the caller's job
    via engine/canon.py's pre-fingerprint canonicalization — the
    ISSUE 11 engine mode.  Direct kernel users (device_sim, the
    liveness graph, kernel tests) keep the historical folded default."""
    ensure_compile_cache()
    if os.environ.get("TPUVSR_COMPILED") == "1":
        from ..core.values import TLAError
        from ..lower.compile import make_compiled_model
        try:
            return make_compiled_model(spec, max_msgs=max_msgs,
                                       fold_symmetry=fold_symmetry)
        except TLAError as e:
            # modules beyond the lowerer's current layout surface
            # (I01/AS04/recovery-era vars) degrade to the hand kernel
            import sys
            print(f"[tpuvsr] TPUVSR_COMPILED=1: {spec.module.name} "
                  f"not yet lowerable ({e}); using the hand kernel",
                  file=sys.stderr)
    codec_cls, kern_cls = _resolve(spec.module.name)
    codec = codec_cls(spec.ev.constants, max_msgs=max_msgs)
    return codec, kern_cls(codec, perms=value_perm_table(
        spec, codec, fold_symmetry=fold_symmetry))


def _resolve(name):
    if name == "VSR":
        from .vsr import VSRCodec
        from .vsr_kernel import VSRKernel
        return VSRCodec, VSRKernel
    if name == "VR_STATE_TRANSFER":
        from .st03 import ST03Codec
        from .st03_kernel import ST03Kernel
        return ST03Codec, ST03Kernel
    if name == "VR_APP_STATE":
        from .as04 import AS04Codec
        from .as04_kernel import AS04Kernel
        return AS04Codec, AS04Kernel
    if name == "VR_ASSUME_NEWVIEWCHANGE":
        from .a01 import A01Codec
        from .a01_kernel import A01Kernel
        return A01Codec, A01Kernel
    if name == "VR_INC_RESEND":
        from .i01 import I01Codec
        from .i01_kernel import I01Kernel
        return I01Codec, I01Kernel
    if name == "VR_REPLICA_RECOVERY":
        from .rr05 import RR05Codec
        from .rr05_kernel import RR05Kernel
        return RR05Codec, RR05Kernel
    if name == "VR_REPLICA_RECOVERY_ASYNC_LOG":
        from .al05 import AL05Codec
        from .al05_kernel import AL05Kernel
        return AL05Codec, AL05Kernel
    if name == "VR_REPLICA_RECOVERY_CP":
        from .cp06 import CP06Codec
        from .cp06_kernel import CP06Kernel
        return CP06Codec, CP06Kernel
    raise KeyError(name)
