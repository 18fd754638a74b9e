"""Growth of the two device tables under `DeviceBFS.run` on the real
VSR kernel: the fingerprint set and the next-frontier buffer.  The
benchmark's cells are sized to grow nothing, and say that the next
depth forces a rebuild; on the stub kernel a rebuild costs nothing and
cannot go wrong in a kernel's shapes.  (The kernel's own two growths
are in test_native_growth_kernel.py: a file each keeps either under
two minutes of builds.)
"""

import pytest

from tests.conftest import check_native_growth


@pytest.mark.parametrize("counter,engine_kw", [
    # doubles up past half load: 1,194 states at depth 7
    ("fpset", {"fpset_capacity": 1 << 11}),
    # a tile commits while `sum(caps)` = 8,832 rows are free: 9,728
    # rows hold level 7 and pause inside level 8
    ("next_buffer", {"next_capacity": 9728}),
], ids=["fpset", "next"])
def test_native_growth_rebuild_is_exact(small_native, small_pin,
                                        tmp_path, counter, engine_kw):
    check_native_growth(small_native, small_pin, counter,
                        str(tmp_path / "j.jsonl"), **engine_kw)
