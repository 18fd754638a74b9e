import os
import sys

# Tests run on a virtual 8-device CPU mesh so sharded code paths are
# exercised without an accelerator (the chip is checked by
# chip_smoke.py, never by this suite).  The program itself never picks
# a backend, so the suite pins it from outside, before jax is imported
# anywhere.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# persistent compilation cache: the big jitted level/step kernels take
# minutes to compile on CPU; share the program's one cache
# (JAX_COMPILATION_CACHE_DIR when set, else <checkout>/.jax_cache)
from tpuvsr.models.registry import ensure_compile_cache  # noqa: E402
ensure_compile_cache()

import pytest  # noqa: E402

REFERENCE = "/root/reference/vsr-revisited/paper"


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running differential tests")


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMALL_CFG = os.path.join(REPO, "examples", "VSR_small.cfg")


@pytest.fixture(scope="session")
def small_native():
    """The kernel-native small check, from committed files alone (no
    reference mount, no interpreter)."""
    from tpuvsr.engine.spec import load_spec
    return load_spec("VSR", SMALL_CFG)


@pytest.fixture(scope="session")
def small_pin():
    """Its pinned level sizes, depth 0 to the fixpoint at 24."""
    import json
    with open(os.path.join(REPO, "scripts",
                           "pinned_levels_small.json")) as f:
        return json.load(f)["level_sizes"]


@pytest.fixture
def empty_store(tmp_path, monkeypatch):
    """An empty store of traced programs of this test's own
    (engine/program_store.py): its first build of a program is a miss,
    whatever the checkout's store holds."""
    from tpuvsr.engine import program_store
    path = str(tmp_path / "store")
    monkeypatch.setattr(program_store, "store_directory", lambda: path)
    return path


def check_native_growth(spec, pin, counter, journal, depth=8, **engine_kw):
    """`DeviceBFS.run` on the native small check with one capacity
    undersized by `engine_kw`: the growth `grow_<counter>` fires, the
    level program is rebuilt, and the level sizes equal the pin
    through `depth` all the same.  Every growth is a build of the
    level program, most of a case's time: a case is sized to grow
    once."""
    from tpuvsr.engine.device_bfs import DeviceBFS
    from tpuvsr.obs import RunObserver, read_journal
    eng = DeviceBFS(spec, **engine_kw)
    res = eng.run(max_depth=depth, obs=RunObserver(journal_path=journal))
    c = res.metrics["counters"]
    assert c["grows"] == c[f"grow_{counter}"] >= 1, c
    assert res.ok and res.error == f"depth limit {depth} reached"
    assert res.levels == list(eng.level_sizes) == pin[:depth + 1]
    assert res.distinct_states == sum(pin[:depth + 1])
    # a level ended after the growth: the sizes above include levels
    # that the rebuilt program produced
    events = [e["event"] for e in read_journal(journal)]
    assert "level_done" in events[events.index("grow"):]
    return eng


def state_key(st):
    """Hashable identity of a full interpreter state dict."""
    return frozenset(st.items())


def explore_states(spec, limit):
    """Collect up to `limit` distinct reachable states in BFS order."""
    seen = {}
    frontier = []
    for st in spec.init_states():
        k = state_key(st)
        if k not in seen:
            seen[k] = st
            frontier.append(st)
    while frontier and len(seen) < limit:
        nxt = []
        for st in frontier:
            for _a, succ in spec.successors(st):
                k = state_key(succ)
                if k not in seen:
                    seen[k] = succ
                    nxt.append(succ)
                    if len(seen) >= limit:
                        return list(seen.values())
        frontier = nxt
    return list(seen.values())


def vsr_spec(values=("v1",), timer=1, restarts=0, symmetry=False,
             invariants=None):
    """The root VSR spec under its shipped cfg with test-size constant
    overrides — the one canonical copy of this boilerplate."""
    from tpuvsr.core.values import ModelValue
    from tpuvsr.engine.spec import SpecModel
    from tpuvsr.frontend.cfg import parse_cfg_file
    from tpuvsr.frontend.parser import parse_module_file
    mod = parse_module_file(f"{REFERENCE}/VSR.tla")
    cfg = parse_cfg_file(f"{REFERENCE}/VSR.cfg")
    cfg.constants["Values"] = frozenset(ModelValue(v) for v in values)
    cfg.constants["StartViewOnTimerLimit"] = timer
    cfg.constants["RestartEmptyLimit"] = restarts
    if not symmetry:
        cfg.symmetry = None
    if invariants is not None:
        cfg.invariants = invariants
    return SpecModel(mod, cfg)


def interp_succs(spec, st):
    """Per-action successor-state-key sets from the interpreter."""
    out = {}
    for action, succ in spec.successors(st):
        out.setdefault(action.name, set()).add(state_key(succ))
    return out


def kernel_succs(kern, codec, st):
    """Per-action successor-state-key sets from a device kernel
    (encode -> step_batch -> decode)."""
    import numpy as np
    dense = codec.encode(st)
    succs, enabled = kern.step_batch(
        {k: np.asarray(v)[None] for k, v in dense.items()})
    enabled = np.asarray(enabled)[0]
    succs = {k: np.asarray(v)[0] for k, v in succs.items()}
    out = {}
    for lane in np.nonzero(enabled)[0]:
        d = {k: v[lane] for k, v in succs.items()}
        assert int(d["err"]) == 0, \
            f"kernel error flag {int(d['err'])} on lane {lane}"
        name = kern.action_names[kern.lane_action[lane]]
        out.setdefault(name, set()).add(state_key(codec.decode(d)))
    return out


def assert_kernel_matches(spec, codec, kern, states):
    """The exact successor multiset per action produced by the kernel
    must equal the interpreter's, for every given state — the standing
    differential harness every device kernel is held to."""
    for n, st in enumerate(states):
        want = interp_succs(spec, st)
        got = kernel_succs(kern, codec, st)
        assert set(want) == set(got), (
            f"state {n}: enabled action sets differ: "
            f"interp-only={set(want) - set(got)}, "
            f"kernel-only={set(got) - set(want)}")
        for name in want:
            assert want[name] == got[name], \
                f"state {n}: successors differ for action {name}"


def interp_level_sizes(spec, depth):
    """Exact per-level frontier sizes of the interpreter BFS to a fixed
    depth — the level-count oracle for state spaces too large for a
    fixpoint run."""
    seen = set()
    frontier = []
    for st in spec.init_states():
        k = spec.view_value(st)
        if k not in seen:
            seen.add(k)
            frontier.append(st)
    sizes = [len(frontier)]
    for _ in range(depth):
        nxt = []
        for st in frontier:
            for _a, succ in spec.successors(st):
                k = spec.view_value(succ)
                if k not in seen:
                    seen.add(k)
                    nxt.append(succ)
        frontier = nxt
        sizes.append(len(frontier))
    return sizes


def interp_levels_fixpoint(spec):
    """Interpreter BFS to fixpoint: (nonempty level sizes, total
    distinct, diameter) — the engine-parity oracle for small configs."""
    seen = set()
    frontier = []
    for st in spec.init_states():
        k = spec.view_value(st)
        if k not in seen:
            seen.add(k)
            frontier.append(st)
    sizes = [len(frontier)]
    depth = 0
    while frontier:
        depth += 1
        nxt = []
        for st in frontier:
            for _a, succ in spec.successors(st):
                k = spec.view_value(succ)
                if k not in seen:
                    seen.add(k)
                    nxt.append(succ)
        frontier = nxt
        if nxt:
            sizes.append(len(nxt))
    return sizes, len(seen), depth


def assert_incremental_fp_matches(codec, kern, states, encoded=False):
    """The O(touched) incremental fingerprint must equal the full-state
    recompute on every enabled lane of the given states (interpreter
    values, or with `encoded` the codec's dense planes).  Returns the
    number of enabled lanes compared."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    def both(st):
        parts = kern.parent_parts(st)
        outs = []
        for name, fn in zip(kern.action_names, kern._action_fns()):
            lanes = jnp.arange(kern._lane_count(name), dtype=jnp.int32)

            def lane_eval(lane, fn=fn, name=name):
                succ, en = fn(kern.seed_touch(st), lane)
                ri = kern.lane_replica(name, st, lane)
                inc = kern.fingerprint_incremental(succ, ri, parts, st)
                full = kern.fingerprint(
                    {k: v for k, v in succ.items()
                     if not k.startswith("_")})
                return inc, full, en
            outs.append(jax.vmap(lane_eval)(lanes))
        return tuple(jnp.concatenate([o[i] for o in outs])
                     for i in range(3))

    both_j = jax.jit(both)
    compared = 0
    for st in states:
        dense = {k: np.asarray(v) for k, v in
                 (st if encoded else codec.encode(st)).items()}
        inc, full, en = both_j(dense)
        en = np.asarray(en)
        assert (np.asarray(inc)[en] == np.asarray(full)[en]).all()
        compared += int(en.sum())
    return compared


def assert_guards_match_actions(codec, kern, states):
    """The cheap guard pass must agree with the action fns' own `en`
    on every lane of every given state."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    gfns = kern._guard_fns()
    afns = kern._action_fns()

    @jax.jit
    def all_en(dense):
        outs_g, outs_a = [], []
        for name, g, a in zip(kern.action_names, gfns, afns):
            lanes = jnp.arange(kern._lane_count(name), dtype=jnp.int32)
            outs_g.append(jax.vmap(lambda ln, g=g: g(dense, ln))(lanes))
            outs_a.append(jax.vmap(
                lambda ln, a=a: a(dense, ln)[1])(lanes))
        return jnp.concatenate(outs_g), jnp.concatenate(outs_a)

    for st in states:
        dense = {k: jnp.asarray(v) for k, v in codec.encode(st).items()}
        g, a = all_en(dense)
        assert (np.asarray(g) == np.asarray(a)).all()


def reference_available():
    return os.path.isdir(REFERENCE)


requires_reference = pytest.mark.skipif(
    not reference_available(), reason="reference corpus not mounted")
