"""The control of `correct`: the program with one stated guarantee
broken — exact deduplication — by the step that would tempt a later PR:
narrower fingerprints.  The configurations state 128-bit fingerprints
in the FPSet; the control keeps `bits` of them and leaves the timed
path otherwise as it is.  States whose narrow fingerprints collide are
merged, so level sizes fall short of the oracle's and `correct` has to
come out false.

The program has no switch for this and gets none: the control wraps the
kernel that `tpuvsr.models.registry.make_model` hands to every engine
(DeviceBFS and ShardedBFS built directly, and the served path's), for
the length of a `with` block, in this process only.
"""

import contextlib


@contextlib.contextmanager
def narrow_fingerprints(bits):
    """Every kernel built inside the block keeps `bits` (1..32) bits of
    each fingerprint."""
    import jax.numpy as jnp

    from tpuvsr.models import registry

    mask = jnp.asarray([(1 << bits) - 1, 0, 0, 0], jnp.uint32)

    class Narrow:
        def __init__(self, kern):
            self._kern = kern

        def __getattr__(self, name):
            return getattr(self._kern, name)

        def fingerprint(self, st):
            return self._kern.fingerprint(st) & mask

        def fingerprint_incremental(self, *args):
            return self._kern.fingerprint_incremental(*args) & mask

        def fingerprint_batch(self, batch):
            return self._kern.fingerprint_batch(batch) & mask

    real = registry.make_model

    def make_model(*args, **kw):
        codec, kern = real(*args, **kw)
        return codec, Narrow(kern)

    registry.make_model = make_model
    try:
        yield
    finally:
        registry.make_model = real
