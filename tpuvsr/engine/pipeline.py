"""Pipelined dispatch window shared by the BFS engines (ISSUE 4).

The synchronous engine loops ran ``dispatch -> block_until_ready ->
device_get(control scalars) -> handle`` — the device idled through a
full host round-trip (plus journal/metrics/spill bookkeeping) after
EVERY level-kernel dispatch, which is most of the runtime where a
host round-trip is slow.  This module keeps a bounded
window of K dispatches in flight instead:

* **launch** enqueues a dispatch and returns its (asynchronous) output
  structure immediately; the engine chains the control scalars the
  next dispatch needs (``start_t`` / ``nn`` for the level kernel)
  straight off that structure as DEVICE arrays, so filling the window
  costs zero host syncs;
* **collect** blocks on the OLDEST in-flight dispatch only, then pulls
  its control scalars; the host handles them (journal, metrics,
  growth decisions, spill compaction, checkpoint staging) while the
  K-1 newer dispatches keep the device busy;
* **drain** discards every still-in-flight ticket without accumulating
  its deltas.  That is SAFE and exact because of the level kernel's
  pause protocol: a dispatch chained after a paused one re-attempts
  the same tile, commits nothing, and re-fails identically (committed
  lanes dedup against the FPSet), and a dispatch chained after the
  level's last tile runs no tile and hands its buffers on as they
  came.  It is not free: the level program's stage 1 — unpacking a
  whole chunk's rows and every guard over them — stands before the
  tile loop and runs all the same (15-18.5 ms at the defect widths,
  PERF.md §5).  So the tickets behind a pause, a stop, or a
  level end are replays/no-ops whose host-visible deltas must NOT be
  double-counted — dropping them keeps counts, level sizes and traces
  bit-identical to the synchronous (K=1) path.  (`PagedBFS` launches
  nothing past a page's end since ISSUE 38: its window runs over the
  PAGES of a level, what is queued is the next page, and only what
  stood behind a pause is dropped — `engine/paged_bfs.py`.)

Window semantics: ``window=1`` reproduces today's behavior exactly,
including the phase accounting (the dispatch blocks inside the
``dispatch``/``compile`` timer, the scalar pull inside ``host_sync``).
With ``window>1`` the enqueue cost lands in ``dispatch``/``compile``,
the blocking wait on the oldest ticket in a new ``inflight`` phase,
and the scalar pull in ``host_sync`` — the phases stay disjoint and
still sum to the run's wall-clock (tpuvsr/obs/SCHEMA.md).

The window also keeps the run's **unfed clock** (``Metrics.unfed``,
the document's ``phases_unfed``): it runs while every dispatch the
host has launched is known to be done — from the window's making (an
engine makes it as its run starts, so ``init`` counts), after the
collect that empties the queue, after a ``drain`` — and stops when
the next enqueue returns, so a build with nothing in flight is unfed
time under ``compile``.  It never runs while the host is blocked on
device work (``collect``'s wait, ``wait``).  At window 1 ``launch``
blocks to completion, so the clock restarts when it returns.  Known
bias: the replays ``drain`` drops still run, so unfed over-reads by
the device time of at most ``pipeline_replays`` dispatches.  The real
chunks a budget stop drops keep the device fed: that drain leaves the
clock stopped.

Drained-but-unconsumed replay dispatches still run on device (they
were already enqueued); their FPSet inserts are idempotent, so only
the end-of-run occupancy gauge can read marginally high after a
time-budget stop.  Engines drain the window at every level boundary,
so rescue checkpoints (resilience supervisor / PreemptionGuard) never
race an in-flight dispatch.
"""

from __future__ import annotations

from collections import deque

from ..obs import spans


class DispatchPipeline:
    """A bounded window of in-flight jitted dispatches.

    ``ready(out)`` must return a device array of the dispatch output to
    block on (the control-scalar leaf every engine already syncs on).
    One instance rides one engine run, from its start: making it
    starts the unfed clock (module docstring).
    """

    def __init__(self, window, obs, ready):
        self.window = max(1, int(window))
        self.obs = obs
        self._ready = ready
        self._q = deque()            # outputs of the dispatches in flight
        obs.gauge("pipeline_depth", self.window)
        obs.metrics.unfed_start()

    @property
    def in_flight(self):
        return len(self._q)

    def has_room(self):
        return len(self._q) < self.window

    def launch(self, fn, *args, fresh=False, **attrs):
        """Enqueue ``fn(*args)``; returns the (async) output structure.

        The first dispatch after a (re)jit compiles synchronously at
        call time and is charged to the ``compile`` phase; at window 1
        the dispatch also blocks to completion here (synchronous-path
        parity).  `attrs` (``depth=``) ride the span.  The first
        launch after ``RunObserver.boundary`` closes that span."""
        obs = self.obs
        obs.end_boundary()
        with obs.span(spans.build_phase(fresh), **attrs):
            out = fn(*args)
            obs.metrics.unfed_stop()        # the device has work
            if self.window == 1:
                self._ready(out).block_until_ready()
                obs.metrics.unfed_start()   # and has done it
        obs.count("dispatches")
        self._q.append(out)
        return out

    def collect(self, pull):
        """Block on the OLDEST in-flight dispatch, pull its control
        scalars with ``pull(out)``, and return ``(out, scalars)``."""
        out = self._q.popleft()
        obs = self.obs
        if self.window > 1:
            with obs.span(spans.INFLIGHT):
                self._ready(out).block_until_ready()
            if not self._q:
                obs.metrics.unfed_start()
        with obs.span(spans.HOST_SYNC):
            sc = pull(out)
        return out, sc

    def wait(self, tip):
        """Block on a device array the queue no longer holds (the chain
        tip behind a ``drain``), under ``inflight``: the device is
        working, so the unfed clock stops for the wait."""
        obs = self.obs
        obs.metrics.unfed_stop()
        with obs.span(spans.INFLIGHT):
            tip.block_until_ready()
        if not self._q:
            obs.metrics.unfed_start()

    def drain(self, reason="replay"):
        """Discard every still-in-flight ticket.  Returns the number of
        tickets dropped.

        ``reason="replay"`` (a pause, a stop, a level end): everything
        behind is a replay/no-op whose deltas must not be re-counted
        (module docstring), filed under ``pipeline_replays``.
        ``reason="budget"`` (the time-budget stop of the default path):
        the tickets hold REAL chunks that the run throws away, filed
        under ``budget_dropped_dispatches``."""
        n = len(self._q)
        if n:
            self.obs.count("budget_dropped_dispatches"
                           if reason == "budget" else "pipeline_replays",
                           n)
            self._q.clear()
        if reason != "budget":
            # a dropped replay still runs: the known bias (module
            # docstring); a dropped real chunk is work, not idling
            self.obs.metrics.unfed_start()
        return n
