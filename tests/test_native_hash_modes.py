"""The two hashes of a successor give the same 128 bits, on the native
kernels at the shapes the benchmark's cells run, from committed files
alone (ISSUE 52).

`DeviceBFS` hashes whole successors at its defaults since ISSUE 52
(`kern.fingerprint`); until then it reconstituted the fingerprint from
the parent's parts and the rows an action touched
(`kern.fingerprint_incremental`, still what `hash_mode="incremental"`
builds).  The same FPSet contents, probe sequences and committed rows
on both sides of that switch rest on the two being one function of the
successor: held here lane by lane on states walked from Init through
the kernel's own actions, where the interpreter-differential files
that hold it (`test_incremental_fingerprint_matches_full`, eight) want
the reference corpus.
"""

import os

import pytest

from tests.conftest import assert_incremental_fp_matches
from tests.test_native_guard_tables import _walk
from tpuvsr.engine.spec import load_spec

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark", "configs")
# shape -> (module, cfg, max_msgs as its cell runs it)
SHAPES = {
    "defect": ("VSR", "vsr-defect.cfg", 32),
    "st03-r5": ("VR_STATE_TRANSFER", "vr-state-transfer-r5.cfg", 48),
    "cp06": ("VR_REPLICA_RECOVERY_CP", "vr-replica-recovery-cp.cfg", 24),
}
SAMPLE = 48         # walked states hashed both ways, every lane of each


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_incremental_hash_equals_full_hash_on_walked_states(shape):
    module, cfg, max_msgs = SHAPES[shape]
    spec = load_spec(module, os.path.join(CONFIGS, cfg))
    codec, kern, _inv = spec.model(max_msgs)
    walked = _walk(spec, codec, kern, seed=5200)
    # the deepest of the walk: the fullest bags, the most touched slots
    compared = assert_incremental_fp_matches(
        codec, kern, walked[-SAMPLE:], encoded=True)
    assert compared >= 4 * SAMPLE
