"""Basic-block model over jaxprs for the SILVIA passes.

LLVM IR (the paper's substrate) and jaxprs line up closely: a jaxpr is
straight-line SSA where control flow lives inside higher-order primitives
(`scan`, `cond`, `while`, `pjit`), so a jaxpr body *is* a basic block.  This
module provides what Algorithm 1 needs on that substrate:

* def-use chains over the equation list (`defs_uses`),
* ALAP scheduling (`alap_schedule`) -- the generalization of the paper's
  `moveUsesALAP`: every equation is placed as late as its uses allow, which
  maximizes the last-definition -> first-use interval of every candidate at
  once,
* width inference (`WidthAnalysis`) -- the analogue of relying on the HLS
  frontend's width minimization: bit widths are traced through
  `convert_element_type`, broadcasts and `silvia_width_hint` metadata,
* the schedule-item representation used to splice packed calls in and
  candidates out, plus `emit_closed_jaxpr` to rebuild a functionally
  equivalent ClosedJaxpr (the paper's BB -> BB* rewrite), and
* dead-code elimination over schedule items (paper sec. 3.4).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Sequence

import numpy as np

import jax
from jax.extend import core as jex_core

Literal = jex_core.Literal
ClosedJaxpr = jex_core.ClosedJaxpr


def is_literal(v) -> bool:
    return isinstance(v, Literal)


def is_drop_var(v) -> bool:
    return type(v).__name__ == "DropVar"


# ---------------------------------------------------------------------------
# def-use chains
# ---------------------------------------------------------------------------

OUT_SENTINEL = 1 << 60  # "position" of the BB's outvars


def defs_uses(eqns: Sequence, outvars: Sequence):
    """Return (def_idx, use_idxs): var -> defining eqn index / list of using
    eqn indices.  Uses by the BB outputs appear as OUT_SENTINEL."""
    def_idx: dict[Any, int] = {}
    use_idxs: dict[Any, list[int]] = {}
    for i, eqn in enumerate(eqns):
        for v in eqn.invars:
            if not is_literal(v):
                use_idxs.setdefault(v, []).append(i)
        for v in eqn.outvars:
            if not is_drop_var(v):
                def_idx[v] = i
    for v in outvars:
        if not is_literal(v):
            use_idxs.setdefault(v, []).append(OUT_SENTINEL)
    return def_idx, use_idxs


# ---------------------------------------------------------------------------
# ALAP scheduling (generalized moveUsesALAP)
# ---------------------------------------------------------------------------

def alap_schedule(eqns: Sequence, outvars: Sequence) -> list:
    """Reorder equations so each is placed as late as possible while
    preserving data dependencies; equations with effects keep their relative
    order (the analogue of the paper's conservative treatment of calls that
    may alias memory).  Stable: ties resolve to original order."""
    n = len(eqns)
    if n == 0:
        return list(eqns)
    consumers = dependencies(eqns, outvars)
    # ALAP level: each eqn sits at min(consumer levels) - 1; eqns consumed
    # only by the BB outputs sit at level n.  Stable sort by (level,
    # original index) realizes the latest legal schedule.
    level = [n] * n
    order = _topo_order(consumers, n)
    for i in reversed(order):
        for j in consumers[i]:
            level[i] = min(level[i], level[j] - 1)
    idx = sorted(range(n), key=lambda i: (level[i], i))
    return [eqns[i] for i in idx]


def dependencies(eqns: Sequence, outvars: Sequence) -> list:
    """consumers[i] = eqn indices that must come after eqn i (data
    dependencies, plus program order among effectful eqns)."""
    def_idx, _ = defs_uses(eqns, outvars)
    consumers: list[set[int]] = [set() for _ in range(len(eqns))]
    prev_effectful = None
    for i, eqn in enumerate(eqns):
        for v in eqn.invars:
            if not is_literal(v) and v in def_idx:
                consumers[def_idx[v]].add(i)
        if eqn.effects:
            if prev_effectful is not None:
                consumers[prev_effectful].add(i)
            prev_effectful = i
    return consumers


def _kahn(consumers, n) -> list:
    """Topological order of nodes 0..n-1; shorter than n iff there is a
    cycle."""
    indeg = [0] * n
    for i in range(n):
        for j in consumers[i]:
            indeg[j] += 1
    stack = [i for i in range(n) if indeg[i] == 0]
    out = []
    while stack:
        i = stack.pop()
        out.append(i)
        for j in consumers[i]:
            indeg[j] -= 1
            if indeg[j] == 0:
                stack.append(j)
    return out


def _topo_order(consumers, n):
    out = _kahn(consumers, n)
    assert len(out) == n, "dependency cycle in jaxpr (impossible)"
    return out


def merged_acyclic(consumers: list, groups) -> bool:
    """Does a schedule with these `dependencies` stay acyclic when each
    group of eqn indices is merged into one node?  Packing a tuple
    replaces its covered eqns with one packed item, so two tuples that
    each read a value the other defines cannot both be packed, even though
    each tuple alone is legal."""
    node = list(range(len(consumers)))
    for g, members in enumerate(groups):
        for i in members:
            node[i] = len(consumers) + g
    n = len(consumers) + len(groups)
    merged: list[set[int]] = [set() for _ in range(n)]
    for i, cons in enumerate(consumers):
        for j in cons:
            if node[i] != node[j]:
                merged[node[i]].add(node[j])
    return len(_kahn(merged, n)) == n


# ---------------------------------------------------------------------------
# width inference
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Width:
    bits: int
    signed: bool
    value_src: Any   # var (or literal) holding the same VALUES, narrowest dtype
    match_src: Any   # var for shared-operand identity (traces through broadcast)


_INT_BITS = {"int4": 4, "uint4": 4, "int8": 8, "uint8": 8,
             "int16": 16, "uint16": 16, "int32": 32, "uint32": 32,
             "int64": 64, "uint64": 64, "bool": 1}


def dtype_bits(dtype) -> int | None:
    return _INT_BITS.get(np.dtype(dtype).name if np.dtype(dtype).name in _INT_BITS
                         else str(dtype), None)


def _literal_width(val) -> tuple[int, bool]:
    if isinstance(val, (bool, np.bool_)):
        return 1, False
    if isinstance(val, (int, np.integer)):
        v = int(val)
        mag = v if v >= 0 else -v - 1
        return mag.bit_length() + 1, True
    if isinstance(val, np.ndarray) and val.dtype.kind in "iu":
        b = dtype_bits(val.dtype)
        return (b if b is not None else 64), val.dtype.kind == "i"
    return 64, True


class WidthAnalysis:
    """Lazy width inference over a BB's equations."""

    def __init__(self, eqns: Sequence, outvars: Sequence):
        self.def_idx, _ = defs_uses(eqns, outvars)
        self.eqns = eqns
        self._memo: dict[Any, Width] = {}

    def width_of(self, v) -> Width:
        if is_literal(v):
            bits, signed = _literal_width(v.val)
            return Width(bits, signed, v, v)
        if v in self._memo:
            return self._memo[v]
        w = self._compute(v)
        self._memo[v] = w
        return w

    def rebind(self, eqns: Sequence, outvars: Sequence, avail: set) -> None:
        """Re-point the analysis at a PATCHED item schedule (a packing
        rewrite of the same BB) without discarding the memo.

        Packing is value-preserving and keeps the root output vars, so a
        memoized width stays correct as long as the vars it references are
        still live: entries whose subject or value/match source was DCE'd
        away are pruned (a later pass must not emit a read of a var that
        no longer has a definition); everything else is carried over --
        this is what makes patching ~free next to a full rebuild."""
        self.eqns = eqns
        self.def_idx, _ = defs_uses(eqns, outvars)

        def live(v):
            return is_literal(v) or v in avail

        self._memo = {v: w for v, w in self._memo.items()
                      if v in avail and live(w.value_src)
                      and live(w.match_src)}

    def _leaf(self, v) -> Width:
        b = dtype_bits(v.aval.dtype)
        signed = np.dtype(v.aval.dtype).kind != "u" if b is not None else True
        return Width(b if b is not None else 999, signed, v, v)

    def _compute(self, v) -> Width:
        i = self.def_idx.get(v)
        if i is None:
            return self._leaf(v)
        eqn = self.eqns[i]
        name = eqn.primitive.name
        if name == "convert_element_type":
            inw = self.width_of(eqn.invars[0])
            out_bits = dtype_bits(eqn.params["new_dtype"])
            if out_bits is not None and out_bits >= inw.bits:
                # widening conversion preserves values -> keep narrow source
                return Width(inw.bits, inw.signed, inw.value_src, inw.match_src)
            return self._leaf(v)
        if name == "silvia_width_hint":
            inw = self.width_of(eqn.invars[0])
            return Width(min(eqn.params["width"], inw.bits),
                         eqn.params["signed"], eqn.invars[0], inw.match_src)
        if name == "broadcast_in_dim":
            inw = self.width_of(eqn.invars[0])
            # broadcast replicates values: identity for matching, but the
            # VALUE source is the broadcasted var itself (shape matters).
            return Width(inw.bits, inw.signed, v, inw.match_src)
        if name == "and":
            # masking with a constant bounds the width
            for a, b in ((eqn.invars[0], eqn.invars[1]),
                         (eqn.invars[1], eqn.invars[0])):
                if is_literal(b) and isinstance(b.val, (int, np.integer)) and int(b.val) >= 0:
                    inw = self.width_of(a)
                    return Width(min(inw.bits, int(b.val).bit_length()),
                                 False, v, v)
            return self._leaf(v)
        return self._leaf(v)


# ---------------------------------------------------------------------------
# shared per-BB analysis cache
# ---------------------------------------------------------------------------

class AnalysisCache:
    """Identity-keyed cache of per-BB analysis state (BBContext).

    The SILVIA passes run as an ordered pipeline over the same BB: a pass
    that finds nothing to rewrite returns the *same* ClosedJaxpr object, so
    the next pass can reuse the ALAP schedule, def/use maps and width
    analysis instead of rebuilding them.  A pass that does rewrite emits a
    fresh jaxpr object, which misses here -- that identity change IS the
    invalidation: every distinct BB version is analyzed exactly once.

    Entries keep a strong reference to their jaxpr so CPython cannot recycle
    the id() while the entry is live.

    `patched` counts in-place schedule patches (BBContext.patch): a packing
    rewrite that used to cost a full re-emit + re-analysis but now only
    splices the item schedule and locally repairs def/use + width state.
    The pass pipeline increments it; patched >> builds is the incremental
    re-analysis proof (tests/test_pipeline_cache.py).
    """

    def __init__(self):
        self._entries: dict[int, tuple[Any, Any]] = {}
        self.builds = 0
        self.hits = 0
        self.patched = 0

    def get_or_build(self, jaxpr, build: Callable[[], Any]):
        ent = self._entries.get(id(jaxpr))
        if ent is not None and ent[0] is jaxpr:
            self.hits += 1
            return ent[1]
        self.builds += 1
        val = build()
        self._entries[id(jaxpr)] = (jaxpr, val)
        return val

    def rebuild(self, jaxpr, build: Callable[[], Any]):
        """Force-build a pristine entry, replacing whatever was cached.

        Needed when a cached context was PATCHED past `jaxpr` by a previous
        pipeline walk (e.g. a different pass list sharing this cache): the
        entry no longer describes the un-rewritten BB, so the new walk must
        start from a fresh analysis."""
        self.builds += 1
        val = build()
        self._entries[id(jaxpr)] = (jaxpr, val)
        return val

    def evict(self):
        """Drop cached contexts, keep counters.  Entries are only reusable
        within one pipeline walk (every new trace makes fresh jaxpr
        objects), so callers evict between walks to bound memory."""
        self._entries.clear()

    def clear(self):
        self._entries.clear()
        self.builds = 0
        self.hits = 0
        self.patched = 0


# ---------------------------------------------------------------------------
# schedule items + emit
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class EqnItem:
    eqn: Any

    @property
    def invars(self):
        return self.eqn.invars

    @property
    def outvars(self):
        return self.eqn.outvars

    @property
    def effects(self):
        return self.eqn.effects

    @property
    def primitive(self):
        return self.eqn.primitive

    @property
    def params(self):
        return self.eqn.params


class _PackedPrimitive:
    """Duck-type stand-in so schedule items are uniform: passes and the
    width analysis probe `item.primitive.name`, and a packed call must look
    like an opaque equation (its name matches no packable pattern, so a
    later pass never tries to re-pack it)."""
    name = "silvia_packed"
    multiple_results = True


_PACKED_PRIM = _PackedPrimitive()


@dataclasses.dataclass
class PackedItem:
    """A packed-operation call replacing a tuple of candidates.

    build(invals) -> list of output values bound to `outvars` (the original
    candidates' root output vars, so downstream uses are rewired for free).
    """
    build: Callable[[list], list]
    in_vars: list           # Vars/Literals the packed call reads
    out_vars: list          # original root vars its results replace
    describe: str = "packed"

    @property
    def invars(self):
        return self.in_vars

    @property
    def outvars(self):
        return self.out_vars

    @property
    def effects(self):
        return ()

    @property
    def primitive(self):
        return _PACKED_PRIM

    @property
    def params(self):
        return {}


def dce_items(items: list, outvars: Sequence) -> list:
    """Backward liveness over schedule items (paper sec. 3.4 DCE)."""
    live = {v for v in outvars if not is_literal(v)}
    keep = [False] * len(items)
    for i in range(len(items) - 1, -1, -1):
        it = items[i]
        if it.effects or any((not is_drop_var(v)) and v in live for v in it.outvars):
            keep[i] = True
            for v in it.invars:
                if not is_literal(v):
                    live.add(v)
    return [it for i, it in enumerate(items) if keep[i]]


def emit_fn(closed: ClosedJaxpr, items: list):
    """Build a python callable evaluating the item schedule (flat in/out)."""
    jaxpr = closed.jaxpr

    def read(env, v):
        return v.val if is_literal(v) else env[v]

    def fn(*flat_args):
        env = {}
        for v, c in zip(jaxpr.constvars, closed.consts):
            env[v] = c
        for v, a in zip(jaxpr.invars, flat_args):
            env[v] = a
        for it in items:
            if isinstance(it, EqnItem):
                eqn = it.eqn
                outs = eqn.primitive.bind(
                    *[read(env, v) for v in eqn.invars], **eqn.params)
                if not eqn.primitive.multiple_results:
                    outs = [outs]
            else:
                outs = it.build([read(env, v) for v in it.in_vars])
            for ov, o in zip(it.outvars, outs):
                if not is_drop_var(ov):
                    env[ov] = o
        return [read(env, v) for v in jaxpr.outvars]

    return fn


def emit_closed_jaxpr(closed: ClosedJaxpr, items: list) -> ClosedJaxpr:
    """Rebuild a ClosedJaxpr from a transformed item schedule (BB -> BB*)."""
    fn = emit_fn(closed, items)
    return jax.make_jaxpr(fn)(*closed.in_avals)


def items_of(closed: ClosedJaxpr) -> list:
    return [EqnItem(e) for e in closed.jaxpr.eqns]
