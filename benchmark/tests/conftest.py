"""Tests of the benchmark's own code.  Run them by hand on the CPU:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q

They are not part of the repository's tier-1 suite (tests/)."""

import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.dirname(BENCH))


@pytest.fixture
def run_cell(capsys):
    """Drive run.main past the chip gate (--rehearse: the small
    configuration on this backend) and return its last line."""
    import run

    def drive(workload, seconds=4, seed=7, trace=0):
        run.main(["--workload", workload, "--seed", str(seed),
                  "--seconds", str(seconds), "--trace", str(trace),
                  "--rehearse"])
        out = capsys.readouterr().out.strip().splitlines()
        return json.loads(out[-1]), [json.loads(x) for x in out[:-1]]
    return drive
