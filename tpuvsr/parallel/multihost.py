"""Multi-host (DCN-tier) support for the sharded BFS engine.

The single-host story shards the frontier + FPSet over a device mesh
and exchanges states with one in-level ``all_to_all`` over ICI
(parallel/sharded_bfs.py).  Scaling past one host keeps the same SPMD
program — the mesh simply spans processes, and XLA routes the mesh
collectives over the cross-host fabric (DCN; gloo/TCP on the CPU
backend used for testing, per-host TPU slices over real DCN in
production).  TLC's analog is its distributed mode (unused by the
reference, which prescribes vertical scale — README:20); this tier is
what lets the flagship defect-config BFS outgrow one host's HBM.

What multi-process changes for the HOST program (and what this module
provides):

* every process runs the same driver loop (SPMD discipline) — control
  decisions must be taken on values all processes agree on;
* a globally-sharded ``jax.Array`` is only partially addressable from
  any one process, so ``np.asarray(global_arr)`` raises — host pulls
  must first reshard to fully-replicated (``replicate_to_host``);
* host->device scatters of globally-identical host data must go
  through ``jax.make_array_from_callback`` so each process only
  touches its addressable shards (``put_sharded`` / ``put_replicated``).

``jax.distributed`` is initialized from environment variables
(TPUVSR_MH_COORD/NPROC/PID) so the same worker entrypoint serves any
process count, and ``launch()`` spawns a local multi-process pack with
the CPU/gloo backend — the test harness for the DCN tier on a machine
with no second host.
"""

from __future__ import annotations

import os
import subprocess
import sys

import numpy as np

ENV_COORD = "TPUVSR_MH_COORD"
ENV_NPROC = "TPUVSR_MH_NPROC"
ENV_PID = "TPUVSR_MH_PID"


def init_from_env():
    """Initialize jax.distributed when the multi-host env vars are set.
    Must run before the backend is touched.  Returns the process id
    (0 when not multi-process)."""
    coord = os.environ.get(ENV_COORD)
    if not coord:
        return 0
    nproc = int(os.environ[ENV_NPROC])
    pid = int(os.environ[ENV_PID])
    import jax
    jax.distributed.initialize(coordinator_address=coord,
                               num_processes=nproc, process_id=pid)
    return pid


def is_multiprocess():
    import jax
    return jax.process_count() > 1


def put_sharded(arr, sharding):
    """Host ndarray (identical on every process) -> global array with
    the given sharding; each process populates only its shards."""
    import jax
    arr = np.asarray(arr)
    return jax.make_array_from_callback(arr.shape, sharding,
                                        lambda idx: arr[idx])


def make_replicator(mesh):
    """Returns pull(global_arr) -> host ndarray of the FULL value,
    valid on every process: reshards to fully-replicated (a broadcast
    over the mesh fabric — DCN across hosts) and reads the now locally
    addressable copy.  Single-process, np.asarray is already enough and
    the collective is skipped."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    if not is_multiprocess():
        return lambda garr: np.asarray(garr)
    rep = NamedSharding(mesh, P())
    gather = jax.jit(lambda x: x, out_shardings=rep)

    def pull(garr):
        return np.asarray(gather(garr))

    return pull


def _free_port():
    import socket
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def launch(worker_argv, nproc=2, local_devices=4, port=None,
           timeout=1800, extra_env=None):
    """Spawn `nproc` local worker processes forming one multi-process
    JAX job over the CPU/gloo backend (the DCN-tier test harness).
    Each worker runs `worker_argv` with the TPUVSR_MH_* env set; the
    worker is expected to call init_from_env() first thing.  Returns
    (returncodes, outputs).

    `port=None` picks a free coordinator port (a fixed default could
    collide with a concurrent multihost job and hang both until
    timeout); `timeout` is one shared deadline across the whole pack,
    not per-process (ADVICE r4)."""
    if port is None:
        port = _free_port()
    import time as _time
    deadline = _time.monotonic() + timeout
    procs = []
    for pid in range(nproc):
        env = dict(os.environ)
        env.update({
            "JAX_PLATFORMS": "cpu",
            "JAX_NUM_CPU_DEVICES": str(local_devices),
            "JAX_CPU_COLLECTIVES_IMPLEMENTATION": "gloo",
            ENV_COORD: f"127.0.0.1:{port}",
            ENV_NPROC: str(nproc),
            ENV_PID: str(pid),
        })
        # the baked-in XLA_FLAGS force a host device count; strip so
        # JAX_NUM_CPU_DEVICES is authoritative per process
        flags = env.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" in flags:
            env["XLA_FLAGS"] = " ".join(
                t for t in flags.split()
                if "xla_force_host_platform_device_count" not in t)
        env.update(extra_env or {})
        procs.append(subprocess.Popen(
            worker_argv, env=env, text=True,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT))
    rcs, outs = [], []
    for p in procs:
        try:
            out, _ = p.communicate(
                timeout=max(1.0, deadline - _time.monotonic()))
        except subprocess.TimeoutExpired:
            p.kill()
            out, _ = p.communicate()
            out = (out or "") + "\n[TIMEOUT]"
        rcs.append(p.returncode)
        outs.append(out or "")
    return rcs, outs
