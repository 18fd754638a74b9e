"""Device-driven behavior-graph construction for liveness checking.

STREAMED single pass (ISSUE 15, the default): the behavior graph flows
OUT of the safety BFS itself.  The fused commit's stage 3 already
holds (source gid, action id, successor fingerprint) for every enabled
lane, fresh *and* duplicate — the edge-emission mode
(``PagedBFS(edges=True)``) resolves those fingerprints to gids on
device through the gid-valued FPSet (``fpset.store_gids`` /
``lookup_gids``, the duplicate hit returning the stored winner's gid)
and appends (src gid, action, dst gid) triples to a device append
buffer, drained into the incremental host CSR builder
(``engine/spill.EdgeCSR``, with a disk tier for graphs past the RAM
budget) at chunk boundaries.  Graph construction cost beyond the
safety BFS collapses to the drains plus one CSR assembly — the
``graph_overhead_ratio`` gauge — instead of a second full expansion
of every retained level (BENCH_r05 `i01-v2t1`: 4,063 s of re-expansion
vs 2,872 s of BFS; the Trifecta paper, arxiv 2211.07216, frames
exactly this TLC bottleneck).

TWO-PASS (``mode="two-pass"``, kept as the bit-identity oracle the
streamed path is checked against, and the A/B leg of
``scripts/liveness_speedup.py``):

  pass 1  enumerate all reachable states with the paged BFS engine
          (``PagedBFS(retain_levels=True)``);
  pass 2  re-expand every level tile-by-tile through a jitted EDGE
          pass — the level kernel's guard + compaction + fingerprint
          phases (the engine's hash, whole successors at its defaults),
          minus FPSet insert/scatter — resolving
          successor fingerprints through a separately built gid FPSet.

The two paths produce the SAME CSR modulo edge order within one
source's segment (both preserve commit order per source; the streamed
path interleaves actions per tile where the re-expansion batches by
action), identical verdicts and identical cycle traces — asserted by
``tests/test_device_liveness.py``.

Both modes retain the dense level blocks (``retain_levels=True``) —
property-leaf predicates evaluate on device over whole blocks, and
lasso traces decode states lazily.  Edge rows, the gid column and the
retained blocks all ride the rescue-checkpoint seam, so a SIGTERM'd
temporal run resumes to a bit-identical CSR and verdict.

The graph object plugs into ``liveness_check(spec, graph=...)``
unchanged: it quacks like the (states, edges, inits) triple via
``states`` (lazy decode), ``edges`` and ``inits`` attributes.

Liveness requires SYMMETRY off (A01 cfg:22-24), which also makes the
device fingerprint exact VIEW identity (single permutation); 128-bit
fingerprint collisions are the same vanishing risk the BFS engine
accepts (fpset.py docstring).
"""

from __future__ import annotations

import time

import numpy as np

import jax
import jax.numpy as jnp

from ..core.values import TLAError
from ..models.vsr import ERR_BAG_OVERFLOW
from .paged_bfs import PagedBFS

I32 = jnp.int32


class _LazyStates:
    """List-like view of the graph's states: decodes dense rows on
    demand and memoizes (property evaluation touches every state once;
    trace reconstruction a handful more)."""

    def __init__(self, graph):
        self.g = graph
        self._cache = {}

    def __len__(self):
        return self.g.n

    def __getitem__(self, sid):
        st = self._cache.get(sid)
        if st is None:
            st = self.g.codec.decode(self.g.dense_row(sid))
            self._cache[sid] = st
        return st


class DeviceGraph:
    """Behavior graph built by the device engines (states, edges,
    inits), with batched device predicate evaluation where possible."""

    def __init__(self, spec, tile_size=64, chunk_tiles=16,
                 max_states=None, log=None, engine=None, result=None,
                 mode="stream", edge_spill_dir=None,
                 checkpoint_path=None, checkpoint_every=None,
                 resume_from=None, obs=None, **eng_kwargs):
        """Pass a finished ``engine`` (a PagedBFS constructed with
        retain_levels=True whose run() returned ``result``) to reuse an
        enumeration that already happened — e.g. the CLI's safety BFS —
        instead of re-running it; a reused engine that ran with
        ``edges=True`` hands over its streamed CSR directly.

        ``mode`` picks the construction path: ``"stream"`` (default —
        the single-pass ISSUE 15 architecture) or ``"two-pass"`` (the
        historical retained-levels + re-expansion body, kept as the
        bit-identity oracle)."""
        if spec.symmetry_perms:
            raise TLAError("liveness checking requires SYMMETRY off "
                           "(reference cfg guidance, A01 cfg:22-24)")
        if mode not in ("stream", "two-pass"):
            raise ValueError(f"mode must be 'stream' or 'two-pass' "
                             f"(got {mode!r})")
        self.spec = spec
        t0 = time.time()
        if engine is not None:
            if result is None or not engine.retain_levels:
                raise ValueError("engine reuse needs retain_levels=True "
                                 "and the run's CheckResult")
            eng, res = engine, result
            # the handed-over run decides the mode: a sink means the
            # edges already streamed out of its commit
            mode = ("stream"
                    if getattr(eng, "edge_sink", None) is not None
                    else "two-pass")
        else:
            eng = PagedBFS(spec, tile_size=tile_size,
                           chunk_tiles=chunk_tiles, retain_levels=True,
                           edges=(mode == "stream"),
                           edge_spill_dir=edge_spill_dir,
                           **eng_kwargs)
            res = eng.run(max_states=max_states, log=log,
                          checkpoint_path=checkpoint_path,
                          checkpoint_every=checkpoint_every,
                          resume_from=resume_from, obs=obs)
        self.mode = mode
        if res.error is not None:
            raise TLAError(
                f"device liveness graph: BFS did not reach fixpoint "
                f"({res.error})")
        if not res.ok:
            raise TLAError(
                f"device liveness graph: safety violation "
                f"{res.violated_invariant} during state enumeration "
                f"(check invariants before properties)")
        self.eng = eng
        self.codec, self.kern = eng.codec, eng.kern
        self.n = res.distinct_states
        self.inits = list(range(eng.level_sizes[0]))
        self.blocks = eng.level_blocks
        self._block_base = np.cumsum(
            [0] + [b["status"].shape[0] for b in self.blocks])
        if self._block_base[-1] != self.n:
            raise TLAError(
                "device liveness graph: retained level blocks cover "
                f"{int(self._block_base[-1])} of {self.n} states — the "
                "engine was resumed from a checkpoint mid-enumeration; "
                "build the graph from a fresh (non-resumed) run")
        self.states = _LazyStates(self)
        self.bfs_elapsed = res.elapsed
        self.distinct_states = self.n
        self.states_generated = res.states_generated

        if mode == "stream":
            # the edges already streamed out of the fused commit —
            # all that is left is assembling the CSR arrays
            self.csr = eng.edge_sink.finalize(self.n)
            eng.edge_sink.drop()
        else:
            self._build_fp_index()
            self.csr = self._build_edges(log)
        self._edges_list = None
        self.build_elapsed = time.time() - t0
        # graph construction cost beyond the safety BFS itself, as a
        # fraction of the BFS wall-clock (the ISSUE 15 acceptance
        # gauge: ~100%+ under two-pass re-expansion, <= 25% streamed).
        # Clamped at 0 for resumed runs whose bfs_elapsed is
        # cumulative across the recover chain while build_elapsed is
        # this process's only
        bfs_s = max(self.bfs_elapsed, 1e-9)
        self.graph_overhead_ratio = round(
            max(0.0, self.build_elapsed - self.bfs_elapsed) / bfs_s, 4)
        # emission rate over the whole construction wall clock.  Under
        # engine hand-over (the CLI path) build_elapsed is only the
        # finalize sliver, so take the larger of the two clocks —
        # matching the SCHEMA.md "over the BFS wall clock" definition
        # instead of gauging finalize-timing noise
        self.edges_per_s = round(
            int(self.csr[1].shape[0])
            / max(self.build_elapsed, self.bfs_elapsed, 1e-9), 1)
        if log:
            log(f"device behavior graph ({mode}): {self.n} states, "
                f"{int(self.csr[1].shape[0])} edges in "
                f"{self.build_elapsed:.1f}s "
                f"(BFS {self.bfs_elapsed:.1f}s, graph overhead "
                f"{100 * self.graph_overhead_ratio:.0f}%)")

    # -- state access --------------------------------------------------
    def dense_row(self, sid):
        b = int(np.searchsorted(self._block_base, sid, side="right")) - 1
        i = sid - self._block_base[b]
        return {k: v[i] for k, v in self.blocks[b].items()}

    # -- fingerprint -> gid --------------------------------------------
    def _build_fp_index(self, batch=8192):
        """Device-resident gid-valued FPSet over all graph states: the
        fp->gid map pass 2 queries on device (fpset.insert_gids)."""
        from .fpset import empty_table, insert_gids
        cap = 1 << max(12, int(np.ceil(np.log2(max(self.n, 1) * 4))))
        self._gid_table = empty_table(cap)
        self._gid_vals = jnp.full((cap,), -1, jnp.int32)
        gid = 0
        insert = jax.jit(insert_gids, donate_argnums=(0, 1))
        zero = self.codec.zero_state()
        for blk in self.blocks:
            nb = blk["status"].shape[0]
            for off in range(0, nb, batch):
                m = min(batch, nb - off)
                # fixed-width padded batches: one compile for the whole
                # index build regardless of block sizes
                part = {k: np.zeros((batch,) + np.shape(zero[k]),
                                    np.int32) for k in zero}
                for k in part:
                    part[k][:m] = blk[k][off:off + m]
                fps = self.kern.fingerprint_batch(
                    {k: jnp.asarray(v) for k, v in part.items()})
                mask = jnp.arange(batch) < m
                gids = jnp.arange(gid, gid + batch, dtype=jnp.int32)
                self._gid_table, self._gid_vals, ovf, fresh = insert(
                    self._gid_table, self._gid_vals, fps, gids, mask)
                if bool(ovf):
                    raise TLAError("gid FPSet probe overflow (grow cap)")
                if int(fresh) != m:
                    raise TLAError(
                        "duplicate fingerprint across level blocks "
                        "(engine invariant broken)")
                gid += m

    # -- edge pass -----------------------------------------------------
    def _make_edge_pass(self):
        """Jitted: one tile of states -> (fp, src row, action id, ok)
        for every enabled lane, via per-action guard compaction and
        the engine's fingerprint stage (the level kernel's phases 1-2
        with recording instead of FPSet insertion)."""
        kern = self.eng.kern
        T = self.eng.tile
        # the engine's trace-once fingerprint stage (liveness runs
        # with symmetry off, so it hashes the state as generated)
        fp_stage = self.eng._fp_stage
        incremental = self.eng._fp_incremental
        caps = [min(T * kern._lane_count(nm),
                    max(64, T * self.eng.expand_mults[a]))
                for a, nm in enumerate(kern.action_names)]

        def edge_pass(tile, n_valid):
            valid = jnp.arange(T, dtype=I32) < n_valid
            parts = (jax.vmap(kern.parent_parts)(tile)
                     if incremental else None)
            out_fp, out_src, out_aid, out_ok = [], [], [], []
            ovf = jnp.asarray(False)
            err_any = jnp.asarray(0, I32)
            for aid, (name, fn, guard) in enumerate(
                    zip(kern.action_names, kern._action_fns(),
                        kern._guard_fns())):
                L_a = kern._lane_count(name)
                TL = T * L_a
                E_a = caps[aid]
                lanes = jnp.arange(L_a, dtype=I32)
                en = jax.vmap(lambda st: jax.vmap(
                    lambda ln: guard(st, ln))(lanes))(tile)
                en = en & valid[:, None]
                ovf = ovf | (en.sum() > E_a)
                (sel,) = jnp.nonzero(en.reshape(TL), size=E_a,
                                     fill_value=TL)
                sel_ok = sel < TL
                pidx = jnp.clip(sel // L_a, 0, T - 1).astype(I32)
                lane_sel = (sel % L_a).astype(I32)
                st_sel = {k: v[pidx] for k, v in tile.items()}
                if incremental:
                    parts_sel = jax.tree_util.tree_map(
                        lambda v: v[pidx], parts)

                    def one(st, parts_one, lane, fn=fn, name=name):
                        succ, en1 = fn(kern.seed_touch(st), lane)
                        ri = kern.lane_replica(name, st, lane)
                        fp = fp_stage(succ, ri, parts_one, st)
                        return fp, en1, succ["err"]
                    fp, en1, errv = jax.vmap(one)(st_sel, parts_sel,
                                                  lane_sel)
                else:
                    def one(st, lane, fn=fn):
                        succ, en1 = fn(st, lane)
                        clean = {k: v for k, v in succ.items()
                                 if not k.startswith("_")}
                        return fp_stage(clean), en1, clean["err"]
                    fp, en1, errv = jax.vmap(one)(st_sel, lane_sel)
                ok = en1 & sel_ok
                err_any = err_any | jnp.where(
                    ok, errv, 0).max(initial=0)
                out_fp.append(fp)
                out_src.append(pidx)
                out_aid.append(jnp.full((E_a,), aid, I32))
                out_ok.append(ok)
            return (jnp.concatenate(out_fp),
                    jnp.concatenate(out_src),
                    jnp.concatenate(out_aid),
                    jnp.concatenate(out_ok), ovf, err_any)
        return jax.jit(edge_pass)

    def _build_edges(self, log=None):
        """Pass 2 -> CSR (indptr[n+1], action_id[m], tid[m]): fp->gid
        resolution happens on device (lookup_gids); host work is array
        concatenation plus one argsort."""
        from .fpset import lookup_gids
        T = self.eng.tile
        edge_pass = self._make_edge_pass()
        lookup = jax.jit(lookup_gids)
        zero = self.codec.zero_state()
        src_parts, aid_parts, tid_parts = [], [], []
        for bi, blk in enumerate(self.blocks):
            base = int(self._block_base[bi])
            nb = blk["status"].shape[0]
            for off in range(0, nb, T):
                n_t = min(T, nb - off)
                tile = {k: np.zeros((T,) + np.shape(zero[k]), np.int32)
                        for k in zero}
                for k in tile:
                    tile[k][:n_t] = blk[k][off:off + n_t]
                fp, src, aid, ok, ovf, err = edge_pass(
                    {k: jnp.asarray(v) for k, v in tile.items()},
                    jnp.asarray(n_t, I32))
                tid = lookup(self._gid_table, self._gid_vals, fp, ok)
                tid, src, aid, ok, ovf, err = jax.device_get(
                    (tid, src, aid, ok, ovf, err))
                if bool(ovf):
                    raise TLAError(
                        "edge pass compaction overflow — pass 1 should "
                        "have calibrated expand_mults (engine bug)")
                if int(err):
                    kind = ("bag overflow"
                            if int(err) & ERR_BAG_OVERFLOW else
                            "slot error")
                    raise TLAError(
                        f"edge pass produced lane error ({kind}) on a "
                        f"successor pass 1 accepted (engine bug)")
                okm = np.asarray(ok)
                tids = np.asarray(tid)[okm]
                if (tids < 0).any():
                    raise TLAError(
                        "edge pass reached a state the BFS never "
                        "recorded (fingerprint mismatch)")
                src_parts.append(base + off
                                 + np.asarray(src)[okm].astype(np.int64))
                aid_parts.append(np.asarray(aid)[okm])
                tid_parts.append(tids)
        src = np.concatenate(src_parts) if src_parts else \
            np.zeros(0, np.int64)
        aid = np.concatenate(aid_parts) if aid_parts else \
            np.zeros(0, np.int32)
        tid = np.concatenate(tid_parts) if tid_parts else \
            np.zeros(0, np.int32)
        order = np.argsort(src, kind="stable")
        src, aid, tid = src[order], aid[order], tid[order]
        indptr = np.zeros(self.n + 1, np.int64)
        np.cumsum(np.bincount(src, minlength=self.n), out=indptr[1:])
        return indptr, aid, tid

    @property
    def edges(self):
        """List-of-lists [(action_name, tid)] view of the CSR arrays,
        materialized on first access (small graphs / legacy callers;
        the fair-SCC machinery reads .csr directly)."""
        if self._edges_list is None:
            indptr, aid, tid = self.csr
            names = self.kern.action_names
            self._edges_list = [
                [(names[int(aid[j])], int(tid[j]))
                 for j in range(indptr[u], indptr[u + 1])]
                for u in range(self.n)]
        return self._edges_list

    # -- batched predicate evaluation ----------------------------------
    def _run_batched(self, pred):
        fn = jax.jit(jax.vmap(pred))
        out = np.empty(self.n, bool)
        for bi, blk in enumerate(self.blocks):
            base = int(self._block_base[bi])
            nb = blk["status"].shape[0]
            vals = np.asarray(fn({k: jnp.asarray(v)
                                  for k, v in blk.items()}))
            out[base:base + nb] = vals
        return out

    def batch_predicate(self, name):
        """Evaluate a named predicate with a device kernel over all
        states; returns a bool array [n] or None if no kernel exists."""
        if name in getattr(self.kern, "INVARIANT_FNS", {}):
            return self._run_batched(self.kern.invariant_fn([name]))
        d = self.spec.module.defs.get(name)
        if d is not None and not d.params:
            return self.batch_expr(d.body, {})
        return None

    def batch_expr(self, expr, bindings):
        """Evaluate an arbitrary property-leaf expression over all
        states through the AST lowerer (available when the kernel is
        compiled-from-AST, lower/compile.py), with `bindings` mapping
        quantifier-bound names to static values.  Returns a bool array
        [n], or None when no lowerer exists or the expression uses a
        construct the lowerer cannot compile — callers fall back to the
        interpreter."""
        from ..lower.compile import Env, Lowerer, LowerError, d_static
        low = getattr(self.kern, "lowerer", None)
        if low is None:
            # hand kernels share the layout family; a lowerer over the
            # same codec serves predicate-only compilation
            try:
                low = Lowerer(self.spec, self.codec, self.kern)
            except Exception:  # noqa: BLE001 — unsupported family
                return None
            self.kern.lowerer = low

        def pred(st):
            env = Env({n: d_static(v) for n, v in bindings.items()})
            v = low.expr(expr, env, st)
            if v.kind == "static":
                return jnp.asarray(bool(v.v))
            return jnp.asarray(low.as_bool(v), bool)

        try:
            return self._run_batched(pred)
        except (LowerError, KeyError, AttributeError, TypeError,
                IndexError):
            # any lowering failure (including builtin exceptions from
            # encoding/field tables) means "no device evaluation" —
            # the caller falls back to the interpreter, matching the
            # pre-lowerer behavior
            return None
