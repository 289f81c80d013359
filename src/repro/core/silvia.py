"""SILVIA base transformation pass -- paper Algorithm 1 on jaxpr BBs.

    C   <- getCandidates(BB)
    BB* <- BB
    for c in C: BB* <- moveUsesALAP(c, BB*)      # here: one global ALAP pass
    T   <- getTuples(C)                          # legality + canPack + full
    for T in T: BB* <- replaceTuple(T, packTuple(T), BB*)
    (then dead-code elimination)

Derived passes override `get_candidates`, `can_pack`, `is_tuple_full` and
`pack_tuple`, exactly mirroring the paper's class structure (sec. 3).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Sequence

from repro.core import ir


@dataclasses.dataclass
class Candidate:
    """A packable pattern rooted at one equation.

    covered:   indices of ALL eqns consumed by packing this candidate
               (a single add for SILVIAAdd; a whole MAD tree for SILVIAMuladd).
    reads:     vars (or literals) the packed implementation will read
               (narrow value sources -- the original converts become dead).
    root_vars: output vars whose uses must be rewired to the packed results.
    meta:      pass-specific payload (widths, leaves, shared operands ...).
    """
    root: int
    covered: frozenset
    reads: tuple
    root_vars: tuple
    meta: Any = None


@dataclasses.dataclass
class Tuple_:
    cands: list
    last_def: int      # max position of any read's definition
    first_use: int     # min position of any external use of any root var
    defs: set = dataclasses.field(default_factory=set)   # vars defined by
    reads: set = dataclasses.field(default_factory=set)  # covered eqns


class BBContext:
    """Analysis state for one basic block (one jaxpr body).

    `eqns` is a schedule of ITEMS (ir.EqnItem / ir.PackedItem) rather than
    raw jaxpr equations: a packing rewrite splices packed items in via
    `patch()` and the analysis state (def/use, widths) is repaired locally,
    so one context survives the whole pass pipeline and the rewritten BB is
    re-emitted (retraced) only once at the end.
    """

    def __init__(self, closed):
        self.closed = closed
        self.eqns = ir.alap_schedule(ir.items_of(closed),
                                     closed.jaxpr.outvars)
        self.outvars = closed.jaxpr.outvars
        self.def_idx, self.use_idxs = ir.defs_uses(self.eqns, self.outvars)
        self.widths = ir.WidthAnalysis(self.eqns, self.outvars)
        self.patches = 0        # in-place packing rewrites applied

    @property
    def dirty(self) -> bool:
        """True when the schedule diverged from closed.jaxpr.eqns and the
        caller must emit_closed_jaxpr(closed, ctx.eqns) to materialize."""
        return self.patches > 0

    def _avail_vars(self) -> set:
        avail = set(self.def_idx)
        avail.update(v for v in self.closed.jaxpr.invars)
        avail.update(v for v in self.closed.jaxpr.constvars)
        return avail

    def patch(self, items: list) -> None:
        """Splice a rewritten (packed + DCE'd) item schedule in WITHOUT
        re-emitting the jaxpr: re-ALAP over the items, rebuild the (cheap)
        def/use maps, and rebind the width analysis pruning only memo
        entries whose vars died -- the incremental alternative to the old
        whole-BB invalidation (ROADMAP carried item)."""
        self.eqns = ir.alap_schedule(items, self.outvars)
        self.def_idx, self.use_idxs = ir.defs_uses(self.eqns, self.outvars)
        self.widths.rebind(self.eqns, self.outvars, self._avail_vars())
        self.patches += 1

    def pos_of_def(self, v) -> int:
        """Schedule position of v's defining eqn (-1 for invars/consts)."""
        if ir.is_literal(v):
            return -1
        return self.def_idx.get(v, -1)

    def last_def(self, reads: Sequence) -> int:
        return max([self.pos_of_def(v) for v in reads], default=-1)

    def first_external_use(self, root_vars: Sequence, covered: frozenset) -> int:
        first = ir.OUT_SENTINEL
        for v in root_vars:
            for u in self.use_idxs.get(v, []):
                if u == ir.OUT_SENTINEL or u not in covered:
                    first = min(first, u)
        return first

    def interval(self, cand: Candidate) -> tuple[int, int]:
        return (self.last_def(cand.reads),
                self.first_external_use(cand.root_vars, cand.covered))


class SILVIA:
    """Base pass.  run() applies Algorithm 1 to one ClosedJaxpr."""

    name = "silvia"
    # paper sec. 3.5.1 leaves II-aware tuple filtering to future work;
    # setting filter_ii=True drops tuples whose super-node would create a
    # new critical cycle in a loop body (requires loop_info from the
    # enclosing scan -- supplied by the pass pipeline).
    filter_ii = False

    # -- hooks for derived passes (paper sec. 3: blue functions) ------------
    def get_candidates(self, ctx: BBContext) -> list[Candidate]:
        raise NotImplementedError

    def can_pack(self, tup: Tuple_, cand: Candidate, ctx: BBContext) -> bool:
        return True

    def is_tuple_full(self, tup: Tuple_) -> bool:
        raise NotImplementedError

    def tuple_viable(self, tup: Tuple_) -> bool:
        """Is a (possibly partial) tuple worth packing?  Default: >= 2."""
        return len(tup.cands) >= 2

    def pack_tuple(self, tup: Tuple_, ctx: BBContext) -> ir.PackedItem:
        raise NotImplementedError

    # -- Algorithm 1 ---------------------------------------------------------
    def get_tuples(self, cands: list[Candidate], ctx: BBContext) -> list[Tuple_]:
        """Greedy in-schedule-order grouping under (a) independence +
        (b) insertion-point existence + (c) operation-specific constraints.

        Interval intersection (last_def < first_use pairwise-merged) implies
        candidate independence (paper sec. 3.2.1)."""
        open_tuples: list[Tuple_] = []
        closed: list[Tuple_] = []
        used_eqns: set[int] = set()

        def defs_of(cand: Candidate) -> set:
            out = set()
            for i in cand.covered:
                for v in ctx.eqns[i].outvars:
                    if not ir.is_drop_var(v):
                        out.add(v)
            return out

        def reads_of(cand: Candidate) -> set:
            return {v for v in cand.reads if not ir.is_literal(v)}

        for cand in sorted(cands, key=lambda c: c.root):
            if cand.covered & used_eqns:
                continue
            last_def, first_use = ctx.interval(cand)
            if last_def >= first_use:
                continue  # no room even alone (pre-ALAP Fig. 4a situation)
            c_defs, c_reads = defs_of(cand), reads_of(cand)
            placed = False
            for tup in open_tuples:
                new_ld = max(tup.last_def, last_def)
                new_fu = min(tup.first_use, first_use)
                if new_ld >= new_fu:
                    continue  # no common insertion point
                # paper condition (a): candidates must not depend on each
                # other.  Interval intersection handles transitive paths;
                # DIRECT def->use between candidates is checked explicitly.
                if (c_reads & tup.defs) or (tup.reads & c_defs):
                    continue
                if not self.can_pack(tup, cand, ctx):
                    continue
                tup.cands.append(cand)
                tup.last_def, tup.first_use = new_ld, new_fu
                tup.defs |= c_defs
                tup.reads |= c_reads
                used_eqns |= cand.covered
                placed = True
                if self.is_tuple_full(tup):
                    open_tuples.remove(tup)
                    closed.append(tup)
                break
            if not placed:
                tup = Tuple_([cand], last_def, first_use, c_defs, c_reads)
                used_eqns |= cand.covered
                open_tuples.append(tup)
        closed.extend(t for t in open_tuples if self.tuple_viable(t))
        return closed

    def run_ctx(self, ctx: BBContext, loop_info=None) -> dict:
        """Apply Algorithm 1 against a shared BBContext, rewriting IN PLACE
        via ctx.patch() (no retrace).  Returns the stats dict; the caller
        checks ctx.dirty / ctx.patches to decide whether to re-emit.

        loop_info: optional (num_consts, num_carry) when this BB is a scan
        body -- enables the II-aware tuple filter (sec. 3.5.1)."""
        cands = self.get_candidates(ctx)
        stats = {"candidates": len(cands), "tuples": 0, "packed_ops": 0,
                 "ii_dropped": 0}
        if not cands:
            return stats
        tuples = self.get_tuples(cands, ctx)
        if tuples and self.filter_ii and loop_info is not None:
            tuples, dropped = self._filter_ii_tuples(tuples, ctx, ctx.closed,
                                                     loop_info)
            stats["ii_dropped"] = dropped
        tuples = self._drop_cyclic_tuples(tuples, ctx)
        if not tuples:
            return stats
        stats["tuples"] = len(tuples)
        stats["packed_ops"] = sum(len(t.cands) for t in tuples)
        # replaceTuple: splice packed items in at a valid insertion point,
        # drop covered eqns, then DCE.
        consumed: set[int] = set()
        inserts: dict[int, list[ir.PackedItem]] = {}
        for tup in tuples:
            item = self.pack_tuple(tup, ctx)
            pos = tup.first_use if tup.first_use != ir.OUT_SENTINEL else len(ctx.eqns)
            inserts.setdefault(pos, []).append(item)
            for c in tup.cands:
                consumed |= c.covered
        items: list = []
        for i, it in enumerate(ctx.eqns):
            for ins in inserts.get(i, []):
                items.append(ins)
            if i not in consumed:
                items.append(it)
        for ins in inserts.get(len(ctx.eqns), []):
            items.append(ins)
        ctx.patch(ir.dce_items(items, ctx.outvars))
        return stats

    def run(self, closed, loop_info=None, cache=None) -> tuple[Any, dict]:
        """Apply the pass to one ClosedJaxpr; returns (new_closed, stats).

        Compatibility wrapper over run_ctx for single-pass callers: builds
        (or fetches from `cache`, an ir.AnalysisCache) the BBContext, packs
        in place, and emits a fresh ClosedJaxpr only if this call packed
        something."""
        if cache is None:
            ctx = BBContext(closed)
        else:
            ctx = cache.get_or_build(closed.jaxpr, lambda: BBContext(closed))
        before = ctx.patches
        stats = self.run_ctx(ctx, loop_info=loop_info)
        if ctx.patches == before:
            return closed, stats
        return ir.emit_closed_jaxpr(closed, ctx.eqns), stats

    def _drop_cyclic_tuples(self, tuples, ctx) -> list:
        """Keep tuples in order while their packed items leave the BB
        acyclic.  get_tuples checks each tuple against the ORIGINAL
        schedule only, so tuple A may read a value tuple B defines while B
        reads one A defines (e.g. {d = a-a, g = a-e} and {e = a+a,
        f = d+a}); the later tuple of such a pair is not packed."""
        deps = ir.dependencies(ctx.eqns, ctx.outvars)
        kept: list[Tuple_] = []
        groups: list[frozenset] = []
        for tup in tuples:
            cover = frozenset().union(*(c.covered for c in tup.cands))
            if ir.merged_acyclic(deps, groups + [cover]):
                kept.append(tup)
                groups.append(cover)
        return kept

    def _filter_ii_tuples(self, tuples, ctx, closed, loop_info):
        """Drop tuples whose packed super-node raises II_min (Fig. 5).

        The DDG is built over the ALAP-scheduled eqn order (ctx.eqns) with
        loop-carried distance-1 edges from scan carry outputs to carry
        inputs."""
        from repro.core import ddg as ddg_mod
        num_consts, num_carry = loop_info
        jaxpr = closed.jaxpr
        eqns = ctx.eqns
        n = len(eqns)
        lats = [1] * n
        edges = []
        for i, eqn in enumerate(eqns):
            for v in eqn.invars:
                if not ir.is_literal(v) and v in ctx.def_idx:
                    edges.append((ctx.def_idx[v], i, 0))
        for ci in range(num_carry):
            v_out = jaxpr.outvars[ci]
            if ir.is_literal(v_out) or v_out not in ctx.def_idx:
                continue
            v_in = jaxpr.invars[num_consts + ci]
            for u in ctx.use_idxs.get(v_in, []):
                if u != ir.OUT_SENTINEL:
                    edges.append((ctx.def_idx[v_out], u, 1))
        g = ddg_mod.DDG(lats, sorted(set(edges)))
        base_ii = g.ii_min()
        kept, dropped = [], 0
        for tup in tuples:
            group = sorted(set().union(*[c.covered for c in tup.cands]))
            if g.with_merged(group).ii_min() > base_ii:
                dropped += 1
            else:
                kept.append(tup)
        return kept, dropped
