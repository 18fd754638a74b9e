"""`CP06Kernel`'s guards as tables of the state (ISSUE 47) against each
action's own enabling, from committed files alone.

Every guard of `CP06Kernel._guard_fns()` is ``guard_x_table(st)`` read
at a lane; the action bodies are untouched (each computes its ``en`` a
lane, from the module's cited lines) and are the oracle.  A lane a
guard loses is a state the checker loses, so the sample is (a) every
state tests/test_native_cp06.py walks (the reference's levels 0-5 and
the crafted subtree) and (b) planted variations where breadth-first
order is thin: `Crash`'s SendOnce (the GetCheckpoint record live, as a
tombstone, and off in one column or plane), the garbage-collected
branch of `ReceiveGetState` / `ReceiveRecoveryMsg` / `SendDVC` (every
NoOp prefix against every ``last_cp`` lane and commit number), AnyDest
against a named dest.  Each kind has to hold enabled lanes and blocked
ones.  And (c) no table guard does work a lane: under the lane vmap
its largest intermediate is the table's own.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tests.test_native_cp06 import (  # noqa: F401  (fixtures)
    MAX_MSGS, _crafted_start, constants, model, ref_run, spec, subtree,
    to_tlc)
from tpuvsr.models.cp06 import M_GETCP, M_NEWCP, M_RECOVERY
from tpuvsr.models.cp06_kernel import ACTION_NAMES, CP06Kernel
from tpuvsr.models.guard_tables import table_lanes
from tpuvsr.models.st03 import (ANYDEST, M_GETSTATE, M_SVC, NORMAL,
                                VIEWCHANGE)
from tpuvsr.models.vsr import (H_COMMIT, H_CP, H_DEST, H_FIRST, H_FLAG,
                               H_LNV, H_OP, H_SRC, H_TYPE, H_VIEW, H_X)

BATCH = 256
# what slot KG holds where replica i's Crash would put its GetCheckpoint
EXACT = ("live", "tombstone")
NEAR = ("type", "dest", "src", "view", "op", "commit", "first", "lnv",
        "x", "flag", "cp_number", "entry", "m_log", "m_cp")
CRASH_KINDS = ("none",) + EXACT + NEAR
KG, K_GS, K_REC, K_CP, K_SVC = 20, 16, 17, 18, 19      # free slots


def _put(st, k, count=1, entry=0, log=None, cp=None, **cols):
    """Overwrite bag slot k with one record (header columns by name)."""
    col = dict(type=H_TYPE, view=H_VIEW, op=H_OP, commit=H_COMMIT,
               dest=H_DEST, src=H_SRC, first=H_FIRST, lnv=H_LNV, x=H_X,
               flag=H_FLAG, cp_number=H_CP)
    st["m_present"][k], st["m_count"][k] = 1, count
    st["m_hdr"][k] = 0
    for name, v in cols.items():
        st["m_hdr"][k, col[name]] = v
    st["m_entry"][k] = entry
    st["m_log"][k] = 0 if log is None else log
    st["m_cp"][k] = 0 if cp is None else cp


def _crash_variations(kern, base):
    """[R x (commit 0..MAX_OPS) x kind] states whose bag holds no
    GetCheckpoint record but the planted one, with the crash left."""
    out = []
    for i in range(kern.R):
        for commit in range(kern.MAX_OPS + 1):
            for kind in CRASH_KINDS:
                st = {k: v.copy() for k, v in base.items()}
                st["aux_restart"][...] = 0
                st["commit"][i] = commit
                st["op"][i] = max(commit, st["op"][i])
                rec = dict(type=M_GETCP, dest=ANYDEST, src=i + 1)
                extra = {}
                if kind == "type":
                    rec["type"] = M_NEWCP
                elif kind == "dest":
                    rec["dest"] = 1 + (i + 1) % kern.R
                elif kind == "src":
                    rec["src"] = 1 + (i + 1) % kern.R
                elif kind in ("view", "op", "commit", "first", "lnv", "x",
                              "flag", "cp_number"):
                    rec[kind] = 1
                elif kind == "entry":
                    extra["entry"] = 1
                elif kind == "m_log":
                    extra["log"] = [0, 1]
                elif kind == "m_cp":
                    extra["cp"] = [1, 0]
                if kind != "none":
                    _put(st, KG, count=0 if kind == "tombstone" else 1,
                         **rec, **extra)
                out.append(st)
    return out


def _gc_variations(kern, base):
    """Replica i with a NoOp prefix of n, commit c and op 2, Normal or
    in a view change; in the bag a GetState and a GetCheckpoint record
    (AnyDest or addressed to i) and a Recovery record asking from
    ``m_op``, and for the view change an SVC tombstone or none.
    Returns (states, their (i, n, c, m_op, named, vc) rows)."""
    R, P, NOOP = kern.R, kern.MAX_OPS, kern.NOOP
    out, rows = [], []
    for i in range(R):
        for n in range(P + 1):
            for c in range(P + 1):
                for m_op in range(P + 1):
                    for named in (False, True):
                        for vc in (False, True):
                            st = {k: v.copy() for k, v in base.items()}
                            other = 1 + (i + 1) % R
                            view = int(st["view"][i])
                            st["status"][i] = VIEWCHANGE if vc else NORMAL
                            st["no_prog"][i] = 0
                            st["sent_dvc"][i] = 0
                            st["op"][i], st["commit"][i] = P, c
                            st["log"][i] = [NOOP] * n + [1] * (P - n)
                            st["app"][i] = [1] * c + [0] * (P - c)
                            dest = i + 1 if named else ANYDEST
                            _put(st, K_GS, type=M_GETSTATE, view=view,
                                 op=m_op, dest=dest, src=other)
                            _put(st, K_CP, type=M_GETCP, dest=dest,
                                 src=other)
                            _put(st, K_REC, type=M_RECOVERY, x=1, op=m_op,
                                 dest=i + 1, src=other)
                            if named:       # the quorum of SendDVC
                                _put(st, K_SVC, count=0, type=M_SVC,
                                     view=view, dest=i + 1, src=other)
                            out.append(st)
                            rows.append((i, n, c, m_op, named, vc))
    return out, np.asarray(rows)


def _scrambled(kern, dense, seed):
    """Walked states with the columns the guards read redrawn at
    random (statuses, views, numbers, the receive-set and no-progress
    bits; of every other bag record the type, addressing, view, op,
    nonce and count): states no run reaches, on which a guard and its
    action are still the same function, and in which every conjunct of
    the inherited guards is met both ways."""
    rng = np.random.default_rng(seed)
    R, P = kern.R, kern.MAX_OPS
    out = []
    for base in dense:
        st = {k: v.copy() for k, v in base.items()}
        for key, hi in (("status", 4), ("view", 4), ("op", P + 1),
                        ("commit", P + 1), ("no_prog", 2),
                        ("sent_dvc", 2), ("sent_sv", 2),
                        ("rec_number", 3), ("rec", 2), ("rec_view", 4),
                        ("rec_has_log", 2), ("dvc", 2),
                        ("peer_op", P + 1), ("log", kern.NOOP + 1),
                        ("aux_acked", 3), ("aux_svc", 3),
                        ("aux_restart", 2)):
            redraw = rng.random(st[key].shape) < 0.4
            st[key] = np.where(redraw, rng.integers(0, hi, st[key].shape),
                               st[key]).astype(np.int32)
        for k in np.flatnonzero(st["m_present"]):
            if rng.random() < 0.5:
                continue
            st["m_count"][k] = rng.integers(0, 3)
            for col, draw in ((H_TYPE, rng.integers(1, M_NEWCP + 1)),
                              (H_DEST, rng.choice([1, 2, 3, ANYDEST])),
                              (H_SRC, rng.integers(1, R + 1)),
                              (H_VIEW, rng.integers(0, 4)),
                              (H_OP, rng.integers(0, P + 1)),
                              (H_X, rng.integers(0, 3))):
                if rng.random() < 0.5:
                    st["m_hdr"][k, col] = draw
        out.append(st)
    return out


@pytest.fixture(scope="module")
def sample(spec, model, constants, ref_run, subtree):
    """(dense planes [N, ...], {part: slice of N}, the GC rows)."""
    codec, kern = model
    _roots, walked, _by_action = subtree
    walked = [s for level in ref_run["levels"][:6] for s in level] + walked
    assert len(walked) > 2386 + 300
    dense = [codec.encode(to_tlc(s, spec)) for s in walked]
    base = codec.encode(to_tlc(_crafted_start(constants), spec))
    assert not base["m_present"][K_GS:].any()
    crash = _crash_variations(kern, base)
    gc, gc_rows = _gc_variations(kern, base)
    scrambled = _scrambled(kern, dense[::3], seed=4700)
    parts, every = {}, []
    for name, states in (("walked", dense), ("crash", crash), ("gc", gc),
                         ("scrambled", scrambled)):
        parts[name] = slice(len(every), len(every) + len(states))
        every += states
    return ({k: np.stack([s[k] for s in every]) for k in every[0]},
            parts, gc_rows)


@pytest.fixture(scope="module")
def both(model, sample):
    """both(action) -> ([N, L] guard, [N, L] the action's own en)."""
    _codec, kern = model
    planes, _parts, _rows = sample
    n = next(iter(planes.values())).shape[0]
    take = np.arange(-(-n // BATCH) * BATCH) % n      # one program
    done = {}

    def run(action):
        if action not in done:
            a = kern.action_names.index(action)
            guard, act = kern._guard_fns()[a], kern._action_fns()[a]
            lanes = jnp.arange(kern._lane_count(action), dtype=jnp.int32)

            @jax.jit
            @jax.vmap
            def fn(st):
                return (jax.vmap(lambda ln: guard(st, ln))(lanes),
                        jax.vmap(lambda ln: act(st, ln)[1])(lanes))
            g, e = [], []
            for lo in range(0, take.size, BATCH):
                gi, ei = fn({k: jnp.asarray(v[take[lo:lo + BATCH]])
                             for k, v in planes.items()})
                g.append(np.asarray(gi))
                e.append(np.asarray(ei))
            done[action] = (np.concatenate(g)[:n], np.concatenate(e)[:n])
        return done[action]
    return run


def test_every_guard_is_a_table(model):
    _codec, kern = model
    assert type(kern) is CP06Kernel
    assert ACTION_NAMES == tuple(kern.action_names)
    # each is this class's own table, not one inherited from ST03
    for guard in kern._guard_fns():
        assert guard.table in vars(CP06Kernel).values()
    assert table_lanes(kern) == kern.n_lanes == 812


@pytest.mark.parametrize("action", ACTION_NAMES)
def test_table_guard_equals_the_actions_enabling(action, both, sample):
    _planes, parts, _rows = sample
    g, e = both(action)
    assert g.shape == e.shape and g.dtype == e.dtype == np.bool_
    bad = np.argwhere(g != e)
    where = {name: int(((bad[:, 0] >= s.start) & (bad[:, 0] < s.stop)).sum())
             for name, s in parts.items()}
    assert not len(bad), (where, bad[:10])
    # the sample meets the guard both ways (NoProgressChangeLimit is 0
    # in this cfg: its eight lanes are never enabled)
    assert not e.all() and e.any() == (action != "NoProgressChange")


def test_crash_is_blocked_by_exactly_its_own_get_checkpoint(
        both, sample, model):
    _codec, kern = model
    _planes, parts, _rows = sample
    C = kern.MAX_OPS + 1
    _g, e = both("Crash")
    e = e[parts["crash"]].reshape(kern.R, C, len(CRASH_KINDS), kern.R, C)
    for i in range(kern.R):
        mine = e[i, :, :, i, :]                     # [commit, kind, cp]
        none = mine[:, CRASH_KINDS.index("none")]
        # Crash keeps any checkpoint 0..commit
        assert (none == np.tril(np.ones((C, C), bool))).all()
        for kind in EXACT:                          # SendOnce blocks
            assert not mine[:, CRASH_KINDS.index(kind)].any(), kind
        for kind in NEAR:       # one column or plane off blocks nothing
            assert (mine[:, CRASH_KINDS.index(kind)] == none).all(), kind
    # another source's record blocks that source, not this one
    src = CRASH_KINDS.index("src")
    assert not e[0, :, src, 1].any() and e[0, :, src, 2].any()


def test_gc_branch_has_every_prefix_against_every_checkpoint_lane(
        both, sample, model):
    _codec, kern = model
    _planes, parts, rows = sample
    R, P = kern.R, kern.MAX_OPS
    C = P + 1
    cps = np.arange(C)
    i, n, c, m_op, named, vc = rows.T
    window = (cps > n[:, None]) & (cps <= c[:, None])   # HGC+1..commit
    at_i = np.arange(len(rows)), i
    # SendDVC: the tombstone is the quorum, the window the lanes
    e = both("SendDVC")[1][parts["gc"]].reshape(-1, R, C)[at_i]
    want = window & (vc & named)[:, None]
    assert (e == want).all() and want.any() and (~want).any()
    # ReceiveGetState at (slot, i): behind a GC'd position a checkpoint
    # of the window, else the log suffix on lane 0
    e = both("ReceiveGetState")[1][parts["gc"]].reshape(
        -1, kern.M, R, C)[:, K_GS][at_i]
    gced = m_op < n
    want = np.where(gced[:, None], window, cps == 0) \
        & (~vc & (m_op < P))[:, None]           # Normal, and ahead of m
    assert (e == want).all()
    assert want[gced].any() and want[~gced].any() and (~want).any()
    # ReceiveRecoveryMsg at its slot: the Normal primary behind a GC'd
    # position replies with a checkpoint, anyone else on lane 0
    e = both("ReceiveRecoveryMsg")[1][parts["gc"]].reshape(
        -1, kern.M, C)[:, K_REC]
    planes = sample[0]
    view = planes["view"][parts["gc"]][at_i]
    prim = (1 + (view - 1) % R) == i + 1
    assert prim.any() and (~prim).any()
    want = np.where((prim & gced)[:, None], window, cps == 0) \
        & ~vc[:, None]
    assert (e == want).all() and want[prim & gced].any()


@pytest.mark.parametrize("action", ["ReceiveGetState",
                                    "ReceiveGetCheckpointMsg"])
def test_anydest_reaches_every_replica_but_its_source(action, both,
                                                      sample, model):
    _codec, kern = model
    _planes, parts, rows = sample
    slot = K_GS if action == "ReceiveGetState" else K_CP
    e = both(action)[1][parts["gc"]].reshape(
        -1, kern.M, kern.R, kern.MAX_OPS + 1)[:, slot].any(-1)  # [N, R]
    i, named = rows[:, 0], rows[:, 4].astype(bool)
    src = (i + 1) % kern.R
    hot = np.arange(kern.R) == i[:, None]
    assert not e[np.arange(len(rows)), src].any()
    assert not e[named][~hot[named]].any() and e[named][hot[named]].any()
    # a third replica answers an AnyDest record, never a named one
    third = ~hot & (np.arange(kern.R) != src[:, None])
    assert e[~named][third[~named]].any()


def _sizes(jaxpr):
    """The size of every equation output of a jaxpr and of the jaxprs
    nested in its equations' parameters."""
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            yield int(np.prod(v.aval.shape))
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (list, tuple)) else (p,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _sizes(sub)


# the guards that scanned the bag a lane (SendOnce, the tombstones)
SCANNED = ("SendDVC", "SendGetState", "Crash")


@pytest.mark.parametrize("action", ACTION_NAMES)
def test_no_table_guard_works_a_lane(action, model, sample):
    """Under the lane vmap a guard's largest intermediate is its
    table's own (or the [L] it reads off it): nothing is computed a
    lane.  No table makes anything larger than the bag's header plane
    or itself; the three that scanned the bag a lane stay under
    ``L x M`` and their oracles still scan (``L x M x NHDR``)."""
    _codec, kern = model
    planes, _parts, _rows = sample
    st = {k: jnp.asarray(v[0]) for k, v in planes.items()}
    a = kern.action_names.index(action)
    L = kern._lane_count(action)
    lanes = jnp.arange(L, dtype=jnp.int32)

    def largest(fn):
        return max(_sizes(jax.make_jaxpr(fn)(st).jaxpr))

    def over_lanes(fn):
        return lambda s: jax.vmap(lambda ln: fn(s, ln))(lanes)

    guard = kern._guard_fns()[a]

    def table(s):
        return guard.table(kern, s)
    assert table(st).size == L
    own = largest(table)
    # SendGetState holds each Prepare against each slot: [k, k']
    assert own <= (kern.M * kern.M if action == "SendGetState"
                   else max(L, kern.M * kern.NHDR))
    assert largest(over_lanes(guard)) <= max(own, L)
    if action in SCANNED:
        assert own < L * kern.M * kern.NHDR
        en = over_lanes(lambda s, ln: kern._action_fns()[a](s, ln)[1])
        assert largest(en) >= L * kern.M * kern.NHDR


def test_a_runs_record_counts_the_table_lanes(spec):
    """Gauge ``guard_table_lanes``: set on the host, from the guards
    the kernel hands the engines, by the one owner of the lever
    gauges; 0 on a kernel with no table."""
    from tpuvsr.engine.checked import CheckedModel
    from tpuvsr.obs.metrics import Metrics
    from tpuvsr.testing import stub_device_engine
    model = CheckedModel(spec)
    model.build(MAX_MSGS)
    doc = Metrics()
    model.gauges(doc, 3, 2, (0, 0, 0))
    assert doc.gauges["guard_table_lanes"] == 812
    res = stub_device_engine().run()
    assert res.metrics["gauges"]["guard_table_lanes"] == 0
