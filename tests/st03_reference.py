"""What the VR_STATE_TRANSFER tests of the native door share
(tests/test_native_st03.py at ReplicaCount = 3,
tests/test_native_st03_r5.py at 5): the plain reference and its quorum
counts (benchmark/tools, imported by path), a reference `State` as the
TLC-valued dict a codec encodes, the state-by-state comparison of the
kernel with the reference, and a bounded exploration from a crafted
state.  Nothing here names a ReplicaCount."""

import os
import sys

import numpy as np

import jax
import jax.numpy as jnp

from tpuvsr.core.values import FnVal, mk_record

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmark", "tools"))
import quorum_counts  # noqa: E402
import state_transfer_reference as reference  # noqa: E402

MODULE = "VR_STATE_TRANSFER"
# `ST03Kernel.COMMIT_STATS`, and each entry under the name
# `quorum_counts.commit_stats` gives it (the peak of one state is its
# slots)
STATS = ("state_transfer_states", "bag_slots", "bag_tombstones",
         "bag_peak", "quorum_waiting_states", "svc_quorum_waiting_states")
HOST_STATS = tuple(n.replace("bag_peak", "bag_slots") for n in STATS)


def to_tlc(state, spec):
    """A reference `State` as the TLC-valued dict the codec encodes."""
    c = spec.cfg.constants
    value = {v.name: v for v in c["Values"]}
    reps = range(1, len(state.rep_status) + 1)

    def fn(values, conv=lambda x: x):
        return FnVal((r, conv(values[r - 1])) for r in reps)

    def log(entries, first=1):
        return FnVal((first + i, mk_record(operation=value[v]))
                     for i, v in enumerate(entries))

    def msg(m):
        f = dict(type=c[m.type], view_number=m.view_number,
                 dest=c["AnyDest"] if m.dest == reference.ANY_DEST
                 else m.dest, source=m.source)
        for k in ("op_number", "commit_number", "last_normal_vn",
                  "first_op"):
            if getattr(m, k) is not None:
                f[k] = getattr(m, k)
        if m.message is not None:
            f["message"] = mk_record(operation=value[m.message])
        if m.log is not None:
            f["log"] = log(m.log, m.first_op or 1)
        return mk_record(**f)

    return {
        "replicas": frozenset(reps),
        "rep_status": fn(state.rep_status, lambda s: c[s]),
        "rep_view_number": fn(state.rep_view_number),
        "rep_op_number": fn(state.rep_op_number),
        "rep_commit_number": fn(state.rep_commit_number),
        "rep_last_normal_view": fn(state.rep_last_normal_view),
        "rep_log": fn(state.rep_log, log),
        "rep_peer_op_number": fn(
            state.rep_peer_op_number,
            lambda row: FnVal((p, row[p - 1]) for p in reps)),
        "rep_sent_dvc": fn(state.rep_sent_dvc),
        "rep_sent_sv": fn(state.rep_sent_sv),
        "no_progress": fn(state.no_progress),
        "no_progress_ctr": state.no_progress_ctr,
        "messages": FnVal((msg(m), n) for m, n in state.messages),
        "aux_svc": state.aux_svc,
        "aux_client_acked": FnVal((value[v], a)
                                  for v, a in state.aux_client_acked),
    }


def make_compare(spec, model, constants, batch_rows):
    """compare(states): every state's kernel successors, as sets per
    action name, equal the reference's; every guard equals its
    action's enabling; every cfg invariant's kernel function equals
    the reference's and every entry of `commit_stats` the reference's
    count (`quorum_counts.commit_stats`); the codec round trips.
    Returns the actions that fired."""
    codec, kern = model
    names = kern.action_names
    lane_action = np.asarray(kern.lane_action)
    guards = kern._guard_fns()
    assert tuple(n for n, _how in kern.COMMIT_STATS) == STATS

    def guard_lanes(st):
        return jnp.concatenate([
            jax.vmap(lambda ln, g=g: g(st, ln))(
                jnp.arange(kern._lane_count(n), dtype=jnp.int32))
            for n, g in zip(names, guards)])
    guard_batch = jax.jit(jax.vmap(guard_lanes))
    inv_names = list(spec.cfg.invariants)
    inv_batch = jax.jit(jax.vmap(lambda st: jnp.stack(
        [kern.invariant_fn([n])(st) for n in inv_names])))
    stat_batch = jax.jit(jax.vmap(kern.commit_stats))

    def run(states):
        fired = set()
        for lo in range(0, len(states), batch_rows):
            part = states[lo:lo + batch_rows]
            tlc = [to_tlc(s, spec) for s in part]
            dense = [codec.encode(t) for t in tlc]
            dense += [dense[-1]] * (batch_rows - len(part))  # one program
            batch = {k: np.stack([d[k] for d in dense]) for k in dense[0]}
            succs, en = kern.step_batch(batch)
            en = np.asarray(en)
            assert np.array_equal(np.asarray(guard_batch(batch)), en)
            ok = np.asarray(inv_batch(batch))
            stats = np.asarray(stat_batch(batch))
            succs = {k: np.asarray(v) for k, v in succs.items()}
            for i, state in enumerate(part):
                assert codec.decode(dense[i]) == tlc[i]
                got = set()
                for lane in np.flatnonzero(en[i]):
                    assert succs["err"][i, lane] == 0
                    got.add((names[lane_action[lane]], reference.from_tlc(
                        codec.decode({k: v[i, lane]
                                      for k, v in succs.items()}),
                        constants)))
                want = set(reference.successors(state, constants))
                assert got == want, (state, sorted(
                    a for a, _ in got ^ want))
                fired |= {a for a, _ in want}
                assert list(ok[i]) == [
                    reference.INVARIANT_FNS[n](state, constants)
                    for n in inv_names], state
                host = quorum_counts.commit_stats(state, constants)
                assert list(stats[i]) == [int(host[n])
                                          for n in HOST_STATS], state
        return fired
    return run


def explore(start, constants, steps, follow=None):
    """Breadth-first on the VIEW from `start` for `steps` levels by
    the reference: (states, by_action) with by_action[name] the
    (state, successor) pairs of every binding met.  `follow(depth,
    action)` False keeps a successor out of the next level (it is
    still in `by_action`)."""
    seen, frontier, states = {start[:reference.N_VIEW]}, [start], [start]
    by_action = {}
    for depth in range(steps):
        nxt = []
        for s in frontier:
            for action, succ in reference.successors(s, constants):
                by_action.setdefault(action, []).append((s, succ))
                view = succ[:reference.N_VIEW]
                if view in seen or (follow and not follow(depth, action)):
                    continue
                seen.add(view)
                nxt.append(succ)
        frontier = nxt
        states += nxt
    return states, by_action
