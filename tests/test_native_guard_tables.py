"""`VSRKernel`'s and `ST03Kernel`'s guards as tables of the state
(ISSUE 48) against each action's own enabling, from committed files
alone; after tests/test_native_cp06_guard_tables.py, which holds
`CP06Kernel`'s.

Every guard of ``kern._guard_fns()`` is ``guard_x_table(st)`` read at
a lane; the action bodies are untouched (each computes its ``en`` a
lane, from the module's cited lines) and are the oracle.  A lane a
guard loses is a state the checker loses, so at each shape a cell runs
(`VSRKernel` at the defect, the shipped and the shipped-restart cfg,
`ST03Kernel` at its cell's) the sample is (a) states walked breadth
first from the init state through the kernel's own actions, (b)
planted ones, a record of the bag turned one column at a time (its
type, its count down to the tombstone, its dest to every replica, to
AnyDest and out of range, the dest replica's view below / at / above
the record's, its status through every value, its op and commit
around the record's) and (c) scrambled ones, the columns the guards
read redrawn at random.  And no table guard does work a lane: under
the lane vmap it is its table and one gather.
"""

import collections
import hashlib
import itertools
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tpuvsr.engine.spec import load_spec
from tpuvsr.models import rr05, st03, vsr
from tpuvsr.models.al05_kernel import ACTION_NAMES as AL05_ACTIONS
from tpuvsr.models.guard_tables import table_lanes
from tpuvsr.models.st03_kernel import ACTION_NAMES as ST03_ACTIONS
from tpuvsr.models.vsr import (H_COMMIT, H_DEST, H_FIRST, H_OP, H_SRC,
                               H_TYPE, H_VIEW, H_X, T_EXEC)
from tpuvsr.models.vsr_kernel import ACTION_NAMES as VSR_ACTIONS

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIGS = os.path.join(REPO, "benchmark", "configs")
# shape -> (module, cfg, max_msgs as its cell runs it, lanes a state)
SHAPES = {
    "defect": ("VSR", "vsr-defect.cfg", 32, 475),
    "shipped": ("VSR", "vsr-shipped.cfg", 32, 472),
    "restart": ("VSR", "vsr-shipped-restart.cfg", 32, 472),
    "st03": ("VR_STATE_TRANSFER", "vr-state-transfer.cfg", 24, 314),
    # ISSUE 53: AL05Kernel's ten own tables over ST03's (AS04's and
    # RR05's conjuncts, the four recovery guards)
    "al05": ("VR_REPLICA_RECOVERY_ASYNC_LOG",
             "vr-replica-recovery-async-log.cfg", 24, 374),
}
ACTIONS = {"VSR": VSR_ACTIONS, "VR_STATE_TRANSFER": ST03_ACTIONS,
           "VR_REPLICA_RECOVERY_ASYNC_LOG": AL05_ACTIONS}
CASES = [(shape, action) for shape, (module, *_cell) in SHAPES.items()
         for action in ACTIONS[module]]
BATCH = 256
WALK_BATCH = 64                 # states a level the walk expands
WALK_LEVELS = 6
WALKED = 600                    # states of the walk kept


def _walk(spec, codec, kern, seed):
    """States reached breadth first from the init state through
    ``kern.step_batch`` (one program: every level is padded to
    WALK_BATCH rows, a level larger than that is expanded from a
    random WALK_BATCH of its states); of the last level, the fullest
    bags, a random WALKED less the levels before it."""
    rng = np.random.default_rng(seed)
    (init,) = spec.init_states()
    front, seen, out = [codec.encode(init)], set(), []
    for _level in range(WALK_LEVELS):
        out += front
        head = [front[i] for i in rng.permutation(len(front))[:WALK_BATCH]]
        rows = [head[i % len(head)] for i in range(WALK_BATCH)]
        succs, en = kern.step_batch(
            {k: jnp.asarray(np.stack([s[k] for s in rows]))
             for k in rows[0]})
        en = np.asarray(en)[:len(head)]
        succs = {k: np.asarray(v) for k, v in succs.items()}
        front = []
        for n, lane in zip(*np.nonzero(en)):
            st = {k: v[n, lane] for k, v in succs.items()}
            key = b"".join(st[k].tobytes() for k in sorted(st))
            if key not in seen and not st["err"]:
                seen.add(key)
                front.append(st)
    keep = rng.permutation(len(front))[:max(WALKED - len(out), 0)]
    return out + [front[i] for i in keep]


def _family(kern):
    """What `_planted` and `_scrambled` need of a kernel family: its
    message types, its statuses, the dests no replica has."""
    if hasattr(kern, "guard_restart_empty"):
        return dict(types=range(1, vsr.M_RECOVERYRESP + 1),
                    statuses=(vsr.NORMAL, vsr.VIEWCHANGE, vsr.RECOVERING),
                    dests=(-1, 0, kern.R + 1), recovering=vsr.RECOVERING)
    if hasattr(kern, "crash_limit"):    # the analysis family's recovery
        return dict(types=range(1, rr05.M_RECOVERYRESP + 1),
                    statuses=(st03.NORMAL, st03.VIEWCHANGE,
                              st03.STATETRANSFER, rr05.RECOVERING),
                    dests=(st03.ANYDEST, 0, kern.R + 1),
                    recovering=rr05.RECOVERING)
    return dict(types=range(1, st03.M_NEWSTATE + 1),
                statuses=(st03.NORMAL, st03.VIEWCHANGE, st03.STATETRANSFER),
                dests=(st03.ANYDEST, 0, kern.R + 1))


def _planted(kern, walked, seed):
    """From walked states with a live record, that record (slot k, to
    replica i) turned one column at a time, the others as walked:
    every type x the count (tombstone, live) x replica i's view below /
    at / above the record's x its status; the dest over every replica,
    AnyDest and out of range; the replica's op and commit and the
    record's first_op around the record's op; for the families that
    have them, the replica out of progress and its recovery nonce at
    and off the record's."""
    rng = np.random.default_rng(seed)
    fam = _family(kern)
    live = [s for s in walked if (s["m_count"] > 0).sum() >= 2]
    assert len(live) >= 6
    out = []
    for n in rng.permutation(len(live))[:6]:
        base = live[n]
        for k in rng.permutation(np.flatnonzero(base["m_count"] > 0))[:2]:
            i = int(np.clip(base["m_hdr"][k, H_DEST] - 1, 0, kern.R - 1))

            def turned(**cols):
                st = {key: v.copy() for key, v in base.items()}
                for name, v in cols.items():
                    if name in ("type", "dest"):
                        st["m_hdr"][k, {"type": H_TYPE,
                                        "dest": H_DEST}[name]] = v
                    elif name == "first":
                        st["m_hdr"][k, H_FIRST] = v
                    elif name == "count":
                        st["m_count"][k] = v
                    else:
                        st[name][i] = v
                out.append(st)
            hv, hop = (int(base["m_hdr"][k, c]) for c in (H_VIEW, H_OP))
            for t, c, dv, s in itertools.product(
                    fam["types"], (0, 1), (-1, 0, 1), fam["statuses"]):
                turned(type=t, count=c, view=max(hv + dv, 0), status=s)
            for t in fam["types"]:
                for d in (*range(1, kern.R + 1), *fam["dests"]):
                    turned(type=t, dest=d)
                for dop in (-2, -1, 0, 1):
                    op = max(hop + dop, 0)
                    for status in fam["statuses"][::2]:
                        turned(type=t, op=op, commit=max(op - 1, 0),
                               first=op + 1, view=hv, status=status)
            if "no_prog" in base:
                for t in fam["types"]:
                    turned(type=t, no_prog=1)
            if "rec_number" in base:
                for x in (0, 1):
                    turned(type=vsr.M_RECOVERYRESP,
                           status=fam["recovering"],
                           rec_number=int(base["m_hdr"][k, H_X]) + x)
    return out


def _scrambled(kern, walked, seed):
    """Walked states with the columns the guards read redrawn at
    random (of the replicas: statuses, views, numbers, flags and
    receive-sets; of every other bag record: the count, type,
    addressing, view, numbers and nonce): states no run reaches, on
    which a guard and its action are still the same function, and in
    which every conjunct is met both ways."""
    rng = np.random.default_rng(seed)
    R, P = kern.R, kern.MAX_OPS
    fam = _family(kern)
    ntype = max(fam["types"])
    out = []
    for base in walked:
        st = {k: v.copy() for k, v in base.items()}
        for key, hi in (("status", len(fam["statuses"])), ("view", 4),
                        ("op", P + 1),
                        ("commit", P + 1), ("log_len", P + 1),
                        ("no_prog", 2), ("np_ctr", 2), ("sent_dvc", 2),
                        ("sent_sv", 2), ("svc", 2), ("dvc", 2),
                        ("peer_op", P + 1), ("rec_number", 3), ("rec", 2),
                        ("rec_has_log", 2), ("aux_acked", 3),
                        ("aux_svc", 4), ("aux_restart", 2)):
            if key in st:
                redraw = rng.random(st[key].shape) < 0.4
                st[key] = np.where(
                    redraw, rng.integers(0, hi, st[key].shape),
                    st[key]).astype(np.int32)
        if "ct" in st:
            st["ct"][:, 0, T_EXEC] = rng.integers(0, 2, R)
        for k in np.flatnonzero(st["m_present"]):
            if rng.random() < 0.5:
                continue
            st["m_count"][k] = rng.integers(0, 3)
            for col, draw in (
                    (H_TYPE, rng.integers(1, ntype + 1)),
                    (H_DEST, rng.choice([*range(1, R + 1), *fam["dests"]])),
                    (H_SRC, rng.integers(0, R + 2)),
                    (H_VIEW, rng.integers(0, 4)),
                    (H_OP, rng.integers(0, P + 1)),
                    (H_COMMIT, rng.integers(0, P + 1)),
                    (H_FIRST, rng.integers(0, P + 2)),
                    (H_X, rng.integers(0, 3))):
                if rng.random() < 0.5:
                    st["m_hdr"][k, col] = draw
        out.append(st)
    return out


@pytest.fixture(scope="module")
def world():
    """world(shape) -> (kernel, dense planes [N, ...], {part: slice})."""
    done = {}

    def build(shape):
        if shape not in done:
            module, cfg, max_msgs, n_lanes = SHAPES[shape]
            spec = load_spec(module, os.path.join(CONFIGS, cfg))
            codec, kern, _inv = spec.model(max_msgs)
            assert kern.n_lanes == n_lanes
            walked = _walk(spec, codec, kern, seed=4800)
            assert len(walked) >= 400
            parts, every = {}, []
            for name, states in (
                    ("walked", walked),
                    ("planted", _planted(kern, walked, seed=4801)),
                    ("scrambled", _scrambled(kern, walked, seed=4802))):
                parts[name] = slice(len(every), len(every) + len(states))
                every += states
            done[shape] = (kern, {k: np.stack([s[k] for s in every])
                                  for k in every[0]}, parts)
        return done[shape]
    return build


def _both(kern, planes, action):
    """([N, L] guard, [N, L] the action's own en) over all L lanes."""
    n = next(iter(planes.values())).shape[0]
    take = np.arange(-(-n // BATCH) * BATCH) % n          # one program
    a = list(kern.action_names).index(action)
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
    return np.concatenate(g)[:n], np.concatenate(e)[:n]


@pytest.mark.parametrize("shape,action", CASES)
def test_table_guard_equals_the_actions_enabling(shape, action, world):
    kern, planes, parts = world(shape)
    g, e = _both(kern, planes, action)
    assert g.shape == e.shape and g.dtype == e.dtype == np.bool_
    assert g.shape[1] == kern._lane_count(action)
    bad = np.argwhere(g != e)
    where = {name: int(((bad[:, 0] >= s.start) & (bad[:, 0] < s.stop)).sum())
             for name, s in parts.items()}
    assert not len(bad), (where, bad[:10])
    # the sample meets the guard both ways, but where the cfg's
    # constants shut the action (RestartEmptyLimit = 0,
    # NoProgressChangeLimit = 0): those lanes are never enabled
    shut = {"RestartEmpty": not getattr(kern.shape, "restart_limit", 1),
            "NoProgressChange": not getattr(kern.shape, "np_limit", 1)}
    assert not e.all() and e.any() == (not shut.get(action, False))
    # ... and on walked states alone wherever breadth-first order
    # reaches the action at all
    assert not e[parts["walked"]].all()


def _counts(jaxpr, into):
    """Equations by primitive, and the largest output, of a jaxpr and
    of the jaxprs nested in its equations' parameters."""
    for eqn in jaxpr.eqns:
        into[eqn.primitive.name] += 1
        into["largest"] = max([into["largest"]] + [
            int(np.prod(v.aval.shape)) for v in eqn.outvars])
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (list, tuple)) else (p,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _counts(sub, into)
    return into


@pytest.mark.parametrize("shape,action", CASES)
def test_no_table_guard_works_a_lane(shape, action, world):
    """Under the lane vmap a guard is its table and the read at the
    lane: one gather more than the table's own (a table's are static
    column picks), no scatter or dynamic slice more, nothing larger
    than the table makes or the [L] read off it.  The oracle does
    gather a lane."""
    kern, planes, _parts = world(shape)
    st = {k: jnp.asarray(v[0]) for k, v in planes.items()}
    a = list(kern.action_names).index(action)
    L = kern._lane_count(action)
    lanes = jnp.arange(L, dtype=jnp.int32)
    guard, act = kern._guard_fns()[a], kern._action_fns()[a]

    def count(fn):
        return _counts(jax.make_jaxpr(fn)(st).jaxpr,
                       collections.Counter(largest=0))

    def over_lanes(fn):
        return lambda s: jax.vmap(lambda ln: fn(s, ln))(lanes)

    assert guard.table(kern, st).size == L
    own, read = count(lambda s: guard.table(kern, s)), count(
        over_lanes(guard))
    assert read["gather"] == own["gather"] + 1
    for prim in ("scatter", "dynamic_slice", "dynamic_update_slice",
                 "while", "cond"):
        assert read[prim] == own[prim], prim
    assert read["largest"] <= max(own["largest"], L)
    # no table makes anything larger than the state's largest plane or
    # itself, but SendGetState's SendOnce: each Prepare against each
    # slot [k, k'] (and, in VSR, rDest)
    plane = max(v.size for v in st.values())
    cube = kern.M * kern.M * (kern.R if L == kern.M * kern.R else 1)
    assert own["largest"] <= (cube if action == "SendGetState"
                              else max(L, plane))
    en = count(over_lanes(lambda s, ln: act(s, ln)[1]))
    if action not in ("RestartEmpty", "NoProgressChange"):  # lane-free
        assert en["gather"] > read["gather"] or en["largest"] > L * kern.M


# ---------------------------------------------------------------------
# the gauge follows the functions
# ---------------------------------------------------------------------
def _kernel(module, cfg, max_msgs):
    spec = load_spec(module, os.path.join(CONFIGS, cfg))
    _codec, kern, _inv = spec.model(max_msgs)
    return spec, kern


def test_gauge_counts_the_lanes_of_the_guards_that_are_tables(world):
    """``guard_table_lanes`` reads the functions `_guard_fns` hands
    the engines (`lanes_of`'s mark), so it is the whole of a kernel
    whose every guard is a table and less on a subclass by exactly the
    lanes of the guards it overrides a lane, whatever it inherits."""
    from tpuvsr.engine.checked import CheckedModel
    from tpuvsr.models.as04 import AS04Codec
    from tpuvsr.models.as04_kernel import AS04Kernel
    from tpuvsr.models.vsr_kernel import VSRKernel
    from tpuvsr.obs.metrics import Metrics
    from tpuvsr.testing import stub_device_engine
    for shape, (_module, _cfg, _m, n_lanes) in SHAPES.items():
        kern = world(shape)[0]
        assert table_lanes(kern) == kern.n_lanes == n_lanes
    spec, cp06 = _kernel("VR_REPLICA_RECOVERY_CP",
                         "vr-replica-recovery-cp.cfg", 24)
    assert table_lanes(cp06) == cp06.n_lanes == 812
    assert not hasattr(cp06, "GUARD_TABLES")
    assert not hasattr(VSRKernel, "GUARD_TABLES")
    # AS04 overrides two guards a lane: ReceiveMatchingSVC (M lanes;
    # its inherited half reads ST03's table) and SendSV (R lanes)
    st = world("st03")[0]
    as04 = AS04Kernel(AS04Codec(st.codec.constants, max_msgs=st.M))
    over = [n for n, g in zip(as04.action_names, as04._guard_fns())
            if not hasattr(g, "table")]
    assert over == ["ReceiveMatchingSVC", "SendSV"]
    assert table_lanes(as04) == as04.n_lanes - as04.M - as04.R > 0
    # ... and a run's record carries it: set on the host by the one
    # owner of the lever gauges; 0 on a kernel with no table
    model = CheckedModel(spec)
    model.build(24)
    doc = Metrics()
    model.gauges(doc, 3, 2, (0, 0, 0))
    assert doc.gauges["guard_table_lanes"] == 812
    res = stub_device_engine().run()
    assert res.metrics["gauges"]["guard_table_lanes"] == 0


# sha256 of `CP06Kernel`'s lowered guard matrix at its cell's shape
# (every guard under the engines' two vmaps, one program, 128 rows),
# taken on the parent's tree (18d61e4) before its helpers moved down to
# `ST03Kernel`
CP06_GUARD_MATRIX = (
    "b7a4f6c94101c1312fed1461e4a55a5e4209de93bd57f51f1be81d4a20668e81")


def test_cp06s_guard_matrix_is_the_parents():
    spec, kern = _kernel("VR_REPLICA_RECOVERY_CP",
                         "vr-replica-recovery-cp.cfg", 24)
    (init,) = spec.init_states()
    batch = {k: jax.ShapeDtypeStruct((128,) + np.asarray(v).shape,
                                     jnp.int32)
             for k, v in kern.codec.encode(init).items()}
    lanes = [jnp.arange(kern._lane_count(n), dtype=jnp.int32)
             for n in kern.action_names]

    def mat(b):
        return [jax.vmap(lambda st, g=g, ln=ln: jax.vmap(
            lambda x: g(st, x))(ln))(b)
            for g, ln in zip(kern._guard_fns(), lanes)]
    text = jax.jit(mat).lower(batch).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == CP06_GUARD_MATRIX
