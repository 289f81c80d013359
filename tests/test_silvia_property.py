"""Property-based tests: SILVIA must preserve semantics on ARBITRARY
straight-line narrow-integer programs, and packing must never reduce the
operation density.

The generator builds random programs over int8 tensors: each step either
multiplies two live values (widened, candidates for muladd), adds two live
int8 values (candidates for SILVIAAdd), adds two widened values (tree
builders), or reuses a shared operand -- covering the paper's candidate
patterns plus plenty of non-candidates.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import example, given, settings, strategies as st

from repro import core as silvia
from repro.core import opcount

N = 8  # vector length for all generated tensors


def build_program(opcodes):
    """opcodes: list of (op, i, j) with indices into the live-value list."""

    def fn(a, b, c):
        live8 = [a, b, c]          # int8 values
        live32 = []                # widened values
        f = lambda x: x.astype(jnp.int32)
        for op, i, j in opcodes:
            if op == 0:            # shared-operand mul
                live32.append(f(live8[i % len(live8)]) * f(c))
            elif op == 1:          # mul of two int8
                live32.append(f(live8[i % len(live8)])
                              * f(live8[j % len(live8)]))
            elif op == 2:          # int8 add (SILVIAAdd candidate)
                live8.append(live8[i % len(live8)]
                             + live8[j % len(live8)])
            elif op == 3 and len(live32) >= 2:   # tree add
                live32.append(live32[i % len(live32)]
                              + live32[j % len(live32)])
            elif op == 4:          # int8 sub
                live8.append(live8[i % len(live8)]
                             - live8[j % len(live8)])
        outs = tuple(live32[-4:]) + tuple(live8[-4:])
        return outs

    return fn


opcode_st = st.tuples(st.integers(0, 4), st.integers(0, 7),
                      st.integers(0, 7))

# two add tuples that each read a value the other defines:
# {d = a-a, g = a-e} and {e = a+a, f = d+a} -- packing both is a cycle
CROSS_TUPLES = [(4, 0, 0), (2, 0, 0), (2, 3, 0), (4, 0, 4)]


@settings(max_examples=30, deadline=None)
@given(st.lists(opcode_st, min_size=2, max_size=12), st.integers(0, 2**31))
@example(CROSS_TUPLES, 0)
def test_random_programs_preserve_semantics(opcodes, seed):
    rng = np.random.default_rng(seed)
    fn = build_program(opcodes)
    args = [jnp.asarray(rng.integers(-128, 128, (N,)), jnp.int8)
            for _ in range(3)]
    want = fn(*args)
    opt = silvia.optimize(fn, silvia.DEFAULT_PASSES)
    got = opt(*args)
    assert len(got) == len(want)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@settings(max_examples=20, deadline=None)
@given(st.lists(opcode_st, min_size=2, max_size=12), st.integers(0, 2**31))
@example(CROSS_TUPLES, 0)
def test_density_never_decreases(opcodes, seed):
    rng = np.random.default_rng(seed)
    fn = build_program(opcodes)
    args = [jnp.asarray(rng.integers(-128, 128, (N,)), jnp.int8)
            for _ in range(3)]
    before = opcount.count_ops(jax.make_jaxpr(fn)(*args))
    after = opcount.count_ops(
        silvia.optimized_jaxpr(fn, *args, passes=silvia.DEFAULT_PASSES))
    if before.mul_units:
        assert after.mul_density >= before.mul_density - 1e-9
    if before.add_units and after.add_units:
        assert after.add_density >= before.add_density - 1e-9
    # logical op counts are conserved or reduced only by DCE of dead code
    assert after.mul_ops <= before.mul_ops
    # every packed unit must carry > 1 op on average for its category
    if after.packed_units:
        assert after.packed_units <= before.mul_units + before.add_units


@settings(max_examples=15, deadline=None)
@given(st.integers(2, 6), st.integers(0, 2**31))
def test_chain_split_matches_reference(n_leaves, seed):
    """Random-length MAD trees: Eq. 2 splitting must stay exact."""
    rng = np.random.default_rng(seed)

    def trees(a, b, c):
        f = lambda x: x.astype(jnp.int32)
        pa = f(a[0]) * f(c[0])
        pb = f(b[0]) * f(c[0])
        for i in range(1, n_leaves):
            pa = pa + f(a[i]) * f(c[i])
            pb = pb + f(b[i]) * f(c[i])
        return pa, pb

    mk = lambda: tuple(jnp.asarray(rng.integers(-128, 128, (N,)), jnp.int8)
                       for _ in range(n_leaves))
    args = [mk(), mk(), mk()]
    opt = silvia.optimize(trees, [silvia.PassConfig(op="muladd")])
    for g, w in zip(opt(*args), trees(*args)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
