"""Growth of what the kernel is built from, under `DeviceBFS.run` on
the real VSR kernel: the per-action expansion caps (a new level
program on the same kernel) and the message table (a new codec, a new
kernel, new `trace_once` stages, re-packed buffers).  See
test_native_growth_buffers.py.
"""

import pytest

from tests.conftest import check_native_growth
from tpuvsr.engine import device_bfs


@pytest.mark.parametrize("what", ["expand", "msgs"])
def test_native_growth_rebuild_is_exact(small_native, small_pin,
                                        tmp_path, monkeypatch, what):
    journal = str(tmp_path / "j.jsonl")
    if what == "expand":
        # the caps start at CAP_START lanes a state, sized to grow
        # nothing in this check; at 2, a full tile overflows them
        monkeypatch.setattr(device_bfs, "CAP_START", 2)
        eng = check_native_growth(small_native, small_pin,
                                  "expand_buffer", journal)
        # grown to the need the guard matrix counted, with headroom
        assert max(eng.expand_caps) > 2 * eng.tile
    else:
        eng = check_native_growth(small_native, small_pin,
                                  "message_table", journal, max_msgs=8)
        assert eng.codec.shape.MAX_MSGS == 16
