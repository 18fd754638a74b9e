"""tpuvsr.service — the federated verification dispatch service.

The composition layer (ISSUE 6 tentpole, ROADMAP item 3) that turns
the CLI tool into a long-running dispatcher: everything a job service
needs was already built as parts — supervised resumable runs (PR 3),
elastic reshardable checkpoints (PR 5), the exit-75 preemption
contract, JSONL journals (PR 2), and the speclint admission gate
(PR 1) — and this package composes them, after the "AI-Orchestrated
Proof Dispatch" architecture of *Federated Formal Verification*
(arxiv 2606.02019):

* **queue.py** — durable on-disk job queue: append-only fsync'd JSONL
  spool + atomic ``O_CREAT|O_EXCL`` claim files, job states
  ``queued -> admitted -> running -> {done, violated, failed,
  preempted-requeued}`` (+ ``cancelled``), crash recovery that turns
  a dead worker's claims back into claimable jobs WITH their rescue
  checkpoints attached;
* **scheduler.py** — device pool + greedy bin-pack by requested
  device count, live elastic shrink/grow of sharded runs through the
  PR 5 reshard-on-load resume path, and the cpu-vs-tpu placement
  advisory (``compare_bench`` cross-backend logic);
* **worker.py** — one process hosting many jobs under
  ``resilience.run_supervised`` (library mode): per-job journals and
  metrics docs, speclint admission before any device time, outcome ->
  terminal-state mapping through the ONE exit-code table
  (``tpuvsr/exitcodes.py``);
* **api.py** — the ``serve`` / ``submit`` / ``status`` / ``cancel``
  CLI verbs; per-job journal tail + metrics doc are the query
  surface (the trace-artifact-as-API posture of arxiv 2404.16075).

Tier-1: the whole service runs on the stub harness
(``tpuvsr/testing.py``) — see ``scripts/serve_demo.py`` and
``tests/test_service.py``.
"""

from __future__ import annotations

from .queue import (CLAIMABLE, HEARTBEAT_TIMEOUT, LEGAL, STATES,
                    TERMINAL, Job, JobQueue, QueueError)
from .scheduler import (Decision, DevicePool, Scheduler,
                        advise_backend, detect_tpu_devices,
                        pow2_floor)
from .worker import JobObserver, Worker, result_summary, \
    trace_to_jsonable

__all__ = [
    "Job", "JobQueue", "QueueError", "STATES", "TERMINAL", "CLAIMABLE",
    "LEGAL", "HEARTBEAT_TIMEOUT", "DevicePool", "Scheduler",
    "Decision", "advise_backend",
    "detect_tpu_devices", "pow2_floor", "Worker",
    "JobObserver",
    "result_summary", "trace_to_jsonable",
]
