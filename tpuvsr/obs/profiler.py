"""JAX profiler hooks, gated on ``TPUVSR_PROFILE=DIR``.

With the env var set, an engine run lies inside one profiler session
writing to DIR, and every host phase (``RunObserver.span``) is also a
``jax.profiler.TraceAnnotation`` from the fixed vocabulary of
``spans.py`` — so the ``.xplane.pb`` shows ``tpuvsr.engine.build`` /
``.dispatch`` / ``.inflight`` / ``.host_sync`` / ``.checkpoint`` on the
device trace's clock instead of runtime span names.

Whether profiling is on is decided ONCE per run (``RunObserver.start``)
or per job (the service worker), by ``annotation_factory``: with it
off, a span never reaches this module.
"""

from __future__ import annotations

import os
from contextlib import contextmanager


def profile_dir():
    """The profile output directory, or None when profiling is off."""
    return os.environ.get("TPUVSR_PROFILE") or None


def annotation_factory():
    """``jax.profiler.TraceAnnotation`` when profiling is on, else
    None.  Callers keep the answer for the run or the job."""
    if not profile_dir():
        return None
    try:
        import jax.profiler as _prof
        return _prof.TraceAnnotation
    except Exception:                           # pragma: no cover
        return None


def _start_session(directory):
    """Open the session with the Python tracer OFF (with it on, the
    Python frames of a build drown the trace: millions of events per
    traced level program) and the host tracer at level 2, which keeps
    TraceAnnotation spans and the runtime's own."""
    import jax.profiler as _prof
    opts = _prof.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    _prof.start_trace(directory, profiler_options=opts)
    return _prof.stop_trace


@contextmanager
def profile_trace(directory=None, log=None):
    """Wrap a fixpoint loop in a profiler session.

    `directory` defaults to ``$TPUVSR_PROFILE``; with neither set this
    is a transparent no-op."""
    directory = directory or profile_dir()
    if not directory:
        yield False
        return
    os.makedirs(directory, exist_ok=True)
    try:
        stop = _start_session(directory)
    except Exception as e:                      # noqa: BLE001
        # a session is already active (the caller owns it, as the
        # benchmark harness does, or a previous run leaked one): the
        # spans land in that session; carry on instead of killing
        # the run
        if log:
            log(f"profiler session not opened ({e}); continuing")
        yield False
        return
    if log:
        log(f"profiling to {directory} (TPUVSR_PROFILE)")
    try:
        yield True
    finally:
        try:
            stop()
        except Exception:                       # noqa: BLE001
            pass
