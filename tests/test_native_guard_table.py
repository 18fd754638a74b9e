"""SendGetState's guard (VSRKernel.guard_send_get_state_table) against
the action's own enabledness, from committed files alone.

The guard is one [M, R] table a state; the action body
(act_send_get_state: a full-record bag scan through _bag_send_once)
is untouched and is the oracle.  No other test the driver runs sees
this guard true: vsr-small (|Values| = 1) never has a Prepare two ops
ahead of its replica, and the trace walk goes through the actions.  A
lane the guard loses is a state the checker loses, so the sample has
to hold enabled lanes, lanes blocked by SendOnce (a live record and a
tombstone) and every near miss of the blocking record.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpuvsr.engine.spec import load_spec
from tpuvsr.frontend.trace_parse import parse_trace_file
from tpuvsr.models.vsr import (H_COMMIT, H_DEST, H_FIRST, H_LNV, H_OP,
                               H_SRC, H_TYPE, H_VIEW, H_X, M_GETSTATE,
                               M_PREPARE, M_PREPAREOK, NENT, NORMAL,
                               RECOVERING, VIEWCHANGE)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFECT_CFG = os.path.join(REPO, "examples", "VSR_defect.cfg")
TRACE = os.path.join(REPO, "examples", "found_violation_trace.txt")

# what is planted where the GetState record of the target lane would go
EXACT = ("live", "tombstone")
NEAR = ("type", "view", "op", "dest", "src", "commit", "x", "first",
        "lnv", "entry", "log", "log_len", "has_log")
KINDS = ("none",) + EXACT + NEAR
DRAWS = 36                      # x 16 kinds = 576 variations


@pytest.fixture(scope="module")
def spec():
    return load_spec("VSR", DEFECT_CFG)


def _parents(spec, M):
    """The trace's parent states whose bag fits M slots, dense."""
    codec, kern, _ = spec.model(M)
    entries = parse_trace_file(TRACE, spec)[:-1]
    assert len(entries) == 29
    fit = [e for e in entries if len(e.state["messages"].items) <= M]
    assert any(e.position == 15 for e in fit)   # SendGetState's parent
    return kern, [codec.encode(e.state) for e in fit]


def _put(st, k, hdr, count=1, entry=None):
    """Overwrite bag slot k with one canonical record (or empty it)."""
    st["m_present"][k] = 0 if hdr is None else 1
    st["m_count"][k] = 0 if hdr is None else count
    st["m_hdr"][k] = 0 if hdr is None else hdr
    st["m_entry"][k] = 0 if entry is None else entry
    st["m_log"][k] = 0
    st["m_log_len"][k] = 0
    st["m_has_log"][k] = 0


def _variations(kern, parents, seed):
    """DRAWS x KINDS states and, for each, the lane the plant aims at.

    A draw fixes a parent, the Prepare's dest r with its status / view
    / op / commit / log_len, a Prepare (view and op around the
    replica's) in slot kp and a slot kg; two draws of three satisfy
    every conjunct but SendOnce by construction.  A kind fixes what
    slot kg holds: nothing, the GetState record lane (kp, rdest) would
    send (live or as a tombstone), or that record off in one column."""
    rng = np.random.default_rng(seed)
    R, M, V, MAX_OPS = kern.R, kern.M, kern.V, kern.MAX_OPS
    out, target = [], []
    for j in range(DRAWS):
        base = parents[(7 * j + 14) % len(parents)]
        good = j % 3 != 2
        r = int(rng.integers(1, R + 1))
        i = r - 1
        rdest = int(rng.choice([d for d in range(1, R + 1) if d != r]))
        if good:
            status = NORMAL
            view = int(rng.choice([v for v in (1, 2, 3)
                                   if 1 + (v - 1) % R != r]))
            op = int(rng.integers(0, MAX_OPS - 1))
            pview, pop, count = view + 1, op + 2, 1
        else:
            status = int(rng.choice([NORMAL, NORMAL, VIEWCHANGE,
                                     RECOVERING]))
            view = int(rng.integers(1, 4))
            op = int(rng.integers(0, MAX_OPS))
            pview = view + int(rng.integers(-1, 3))
            pop = op + 1 + int(rng.integers(-1, 3))
            count = int(rng.integers(0, 2))
        commit = int(rng.integers(0, op + 1))
        log_len = int(rng.integers(0, MAX_OPS + 1)) if j % 4 == 0 else op
        trunc = min(commit, log_len)
        kp, kg = (int(k) for k in rng.choice(M, size=2, replace=False))
        entry = np.array([pview, 1 + j % V, 1, 1], np.int32)
        for kind in KINDS:
            st = {k: np.array(v) for k, v in base.items()}
            st["status"][i], st["view"][i] = status, view
            st["op"][i], st["commit"][i] = op, commit
            st["log_len"][i] = log_len
            st["log"][i] = 0
            for n in range(log_len):
                st["log"][i, n] = (1, 1 + n % V, 1, n + 1)
            hdr = np.zeros(kern.NHDR, np.int32)
            hdr[[H_TYPE, H_VIEW, H_OP, H_COMMIT, H_DEST, H_SRC]] = (
                M_PREPARE, pview, pop, commit, r, 1 + r % R)
            _put(st, kp, hdr, count=count, entry=entry)
            g = np.zeros(kern.NHDR, np.int32)
            g[[H_TYPE, H_VIEW, H_OP, H_DEST, H_SRC]] = (
                M_GETSTATE, pview, trunc, rdest, r)
            step = int(rng.choice([-1, 1]))
            if kind == "none":
                g = None
            elif kind == "type":
                g[H_TYPE] = M_PREPAREOK      # the same four columns
            elif kind in ("view", "op", "dest", "src"):
                g[{"view": H_VIEW, "op": H_OP, "dest": H_DEST,
                   "src": H_SRC}[kind]] += step
            elif kind in ("commit", "x", "first", "lnv"):
                g[{"commit": H_COMMIT, "x": H_X, "first": H_FIRST,
                   "lnv": H_LNV}[kind]] = 1
            _put(st, kg, g, count=0 if kind == "tombstone" else 1)
            if kind == "entry":
                st["m_entry"][kg, j % NENT] = 1
            elif kind == "log":
                st["m_log"][kg, j % MAX_OPS, j % NENT] = 1
            elif kind in ("log_len", "has_log"):
                st["m_" + kind][kg] = 1
            out.append(st)
            target.append(kp * R + rdest - 1)
    return ({k: np.stack([s[k] for s in out]) for k in out[0]},
            np.asarray(target).reshape(DRAWS, len(KINDS)))


def _guard_and_action(kern, batch):
    """([N, L] guard, [N, L] the action's own en) over all L lanes."""
    lanes = jnp.arange(kern._lane_count("SendGetState"), dtype=jnp.int32)

    @jax.jit
    @jax.vmap
    def both(st):
        return (jax.vmap(lambda ln: kern.guard_send_get_state(st, ln))(
                    lanes),
                jax.vmap(lambda ln: kern.act_send_get_state(st, ln)[1])(
                    lanes))
    g, a = both({k: jnp.asarray(v) for k, v in batch.items()})
    return np.asarray(g), np.asarray(a)


@pytest.mark.parametrize("M", [16, 32])
def test_guard_equals_action_on_trace_parents(spec, M):
    kern, parents = _parents(spec, M)
    g, a = _guard_and_action(
        kern, {k: np.stack([p[k] for p in parents]) for k in parents[0]})
    assert g.shape == (len(parents), M * kern.R) and (g == a).all()
    # entry 15 of the trace is the state SendGetState fires in
    assert g.any(axis=1).sum() >= 1


@pytest.mark.parametrize("M", [16, 32])
def test_guard_equals_action_on_planted_variations(spec, M):
    kern, parents = _parents(spec, M)
    batch, target = _variations(kern, parents, seed=3200 + M)
    assert target.size >= 300
    g, a = _guard_and_action(kern, batch)
    assert (g == a).all(), np.argwhere(g != a)[:10]
    # the oracle at the lane each plant aims at, [draw, kind]
    at = a[np.arange(target.size), target.reshape(-1)].reshape(
        target.shape)
    none = at[:, KINDS.index("none")]
    assert a.sum() >= 20 and none.sum() >= 20
    live = none & ~at[:, KINDS.index("live")]
    tomb = none & ~at[:, KINDS.index("tombstone")]
    assert live.sum() == tomb.sum() == none.sum()   # SendOnce blocks
    for kind in NEAR:
        # one column off and the record blocks nothing at that lane
        assert (at[:, KINDS.index(kind)] == none).all(), kind
        assert at[:, KINDS.index(kind)].any(), kind


def _avals(jaxpr):
    """Every equation output of a jaxpr and of the jaxprs nested in
    its equations' parameters (pjit, custom_jvp, cond, while ...)."""
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            yield v.aval
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (list, tuple)) else (p,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _avals(sub)


@pytest.mark.parametrize("M", [16, 32])
def test_guard_holds_no_bag_scan_under_the_lane_axis(spec, M):
    """The largest intermediate of the L-lane guard is the [M, M, R]
    plane; a bag scan a lane ([L, M, NHDR], what the action's
    SendOnce makes and the guard made before) is what must not come
    back."""
    kern, parents = _parents(spec, M)
    st = {k: jnp.asarray(v) for k, v in parents[-1].items()}
    L = kern._lane_count("SendGetState")
    lanes = jnp.arange(L, dtype=jnp.int32)
    scan, plane = L * M * kern.NHDR, M * M * kern.R

    def largest(fn):
        jaxpr = jax.make_jaxpr(
            lambda s: jax.vmap(lambda ln: fn(s, ln))(lanes))(st)
        return max(int(np.prod(av.shape)) for av in _avals(jaxpr.jaxpr))

    assert largest(kern.guard_send_get_state) <= plane < scan
    # the walk sees what it has to: the oracle does scan a lane
    assert largest(lambda s, ln: kern.act_send_get_state(s, ln)[1]) >= scan
