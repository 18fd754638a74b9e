"""The supervised retry on the real VSR kernel, from committed files:
`Supervisor(engine="device")` is what every served check job runs
under (`service/worker.py`), and tier-1 otherwise drives its ladder
on the stub kernel only.  One injected fault at level 6 of the small
check; the levels through depth 10 must equal the pin whichever way
the run came back.
"""

import pytest

from tpuvsr.obs import read_journal
from tpuvsr.resilience import faults
from tpuvsr.resilience.supervisor import Supervisor, clear_preemption

FAULT_LEVEL, END_DEPTH = 6, 10


@pytest.fixture(autouse=True)
def _clean_fault_state():
    yield
    faults.clear()
    clear_preemption()


@pytest.mark.parametrize("fault", ["kill", "oom"])
def test_supervised_native_run_is_exact(small_native, small_pin,
                                        tmp_path, fault):
    ck, jp = str(tmp_path / "ck"), str(tmp_path / "j.jsonl")
    sup = Supervisor(small_native, engine="device", checkpoint_path=ck,
                     journal_path=jp, backoff_base=0.0,
                     sleep=lambda s: None)
    faults.install(f"{fault}@level={FAULT_LEVEL}")
    out = sup.run_to_outcome(max_depth=END_DEPTH)
    if fault == "kill":
        # a preemption is the caller's to retry (the service requeues
        # the job): the rescue snapshot is at the boundary the signal
        # was seen at, and the second call resumes from it
        assert out.state == "preempted-requeued"
        assert out.rescue["path"] == ck
        assert out.rescue["depth"] == FAULT_LEVEL
        out = sup.run_to_outcome(max_depth=END_DEPTH,
                                 resume_from=out.rescue["path"])
        assert not sup.degrades
    else:
        # an OOM is the supervisor's: half the tile, from the newest
        # snapshot, without the caller seeing anything but the result
        assert sup.degrades == [("tile", 128, 64)]
        assert sup.engine.tile == 64
    assert out.state == "done" and sup.attempts == 2
    res = out.result
    pin = small_pin[:END_DEPTH + 1]
    assert res.ok and res.error == f"depth limit {END_DEPTH} reached"
    assert res.levels == pin and res.distinct_states == sum(pin)
    events = read_journal(jp)
    kinds = [e["event"] for e in events]
    assert kinds.count("fault") == 1
    assert kinds.count("retry") == (fault == "oom")
    assert kinds.count("rescue_checkpoint") == (fault == "kill")
    starts = [e for e in events if e["event"] == "run_start"]
    assert [e["resumed"] for e in starts] == [False, True]
    done = [e["depth"] for e in events if e["event"] == "level_done"]
    # the killed run ends level 6 before it leaves; the OOM strikes as
    # level 6 starts, so the retry runs it
    assert done == list(range(1, END_DEPTH + 1))
