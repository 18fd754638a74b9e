"""The device gate: a run measures only on a TPU that the peaks table
knows, with exactly the chips the cell asks for."""

import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))


def peaks_for(kind):
    with open(os.path.join(BENCH, "peaks.json")) as f:
        table = json.load(f)
    if kind not in table:
        sys.exit(f"benchmark: device kind {kind!r} is not in peaks.json; "
                 f"add it with its source, there is no default")
    return table[kind]


def require_devices(chips, rehearse=False):
    """(devices, peaks) or exit non-zero before any work.  A rehearsal
    takes whatever JAX has (the CPU here) and has no peaks."""
    import jax
    devs = jax.devices()
    if rehearse:
        return devs, None
    if devs[0].platform != "tpu" or len(devs) != chips:
        sys.exit(f"benchmark: need {chips} TPU device(s); JAX gives "
                 f"{len(devs)} x {devs[0].platform} "
                 f"({devs[0].device_kind})")
    return devs, peaks_for(devs[0].device_kind)


def peak_bytes(devs):
    """Peak bytes in use on the fullest device, 0 where the backend
    reports none."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use") or 0)
               for d in devs)


def device_doc(devs):
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak_bytes(devs)}
