"""Filter algebra + DNF compiler: unit and property tests."""
import math
import sys
from dataclasses import dataclass
from pathlib import Path

import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="property tests need hypothesis "
                    "(pip install -e '.[dev]')")
from hypothesis import given, settings, strategies as st

from repro.core import filters as F

SCHEMA = F.paper_schema(n_bool=1, n_int=2, n_float=2)


def _mask(flt, attrs):
    prog = F.compile_filter(flt, SCHEMA)
    return F.eval_program(prog, attrs.ints, attrs.floats)


@pytest.fixture(scope="module")
def attrs():
    return F.random_attributes(SCHEMA, 500, seed=0)


def test_equality_bool(attrs):
    m = _mask(F.Equality("b0", True), attrs)
    assert m.sum() == (attrs.ints[:, 0] == 1).sum()


def test_equality_int(attrs):
    m = _mask(F.Equality("i0", 3), attrs)
    np.testing.assert_array_equal(m, attrs.ints[:, 1] == 3)


def test_inclusion(attrs):
    m = _mask(F.Inclusion("i1", [1, 4, 7]), attrs)
    np.testing.assert_array_equal(m, np.isin(attrs.ints[:, 2], [1, 4, 7]))


def test_range_float(attrs):
    m = _mask(F.Range("f0", 20.0, 60.0), attrs)
    col = attrs.floats[:, 0]
    np.testing.assert_array_equal(m, (col >= 20.0) & (col <= 60.0))


def test_range_int(attrs):
    m = _mask(F.Range("i0", 2, 5), attrs)
    col = attrs.ints[:, 1]
    np.testing.assert_array_equal(m, (col >= 2) & (col <= 5))


def test_logic_and_or_not(attrs):
    f = F.And(F.Equality("b0", True), F.Or(F.Range("f0", None, 50.0),
                                           F.Not(F.Inclusion("i0", [0, 1, 2]))))
    m = _mask(f, attrs)
    expect = np.array([F.eval_filter_python(f, attrs.row(i)) for i in range(attrs.n)])
    np.testing.assert_array_equal(m, expect)


def test_true_false(attrs):
    assert _mask(F.TrueFilter(), attrs).all()
    assert not _mask(F.FalseFilter(), attrs).any()


def test_not_range_strict_bounds(attrs):
    f = F.Not(F.Range("f1", 25.0, 75.0))
    m = _mask(f, attrs)
    col = attrs.floats[:, 1]
    np.testing.assert_array_equal(m, (col < 25.0) | (col > 75.0))


def test_width_overflow_raises():
    clauses = [F.Not(F.Range("f0", i * 10.0, i * 10.0 + 5.0)) for i in range(8)]
    with pytest.raises(ValueError):
        F.compile_filter(F.Or(*[F.And(*clauses)]), SCHEMA, width=4)


def test_stack_programs_pads():
    p1 = F.compile_filter(F.Equality("b0", True), SCHEMA, width=2)
    p2 = F.compile_filter(F.Not(F.Range("f0", 10.0, 20.0)), SCHEMA, width=4)
    batch = F.stack_programs([p1, p2])
    assert batch["valid"].shape == (2, 4)


def test_gathered_eval_matches_batched(attrs):
    progs = [F.compile_filter(F.Equality("i0", v), SCHEMA) for v in (1, 2, 3)]
    batch = F.stack_programs(progs)
    rows = np.array([[0, 1, 2], [3, 4, 5], [6, 7, 8]])
    g = F.eval_program_gathered(batch, attrs.ints[rows], attrs.floats[rows])
    for b in range(3):
        full = F.eval_program(progs[b], attrs.ints, attrs.floats)
        np.testing.assert_array_equal(g[b], full[rows[b]])


# -- property: compiled program == AST interpreter ---------------------------
@st.composite
def filter_trees(draw, depth=0):
    leaf = st.one_of(
        st.builds(F.Equality, st.just("b0"), st.booleans()),
        st.builds(F.Equality, st.just("i0"), st.integers(0, 9)),
        st.builds(lambda v: F.Inclusion("i1", v),
                  st.lists(st.integers(0, 9), min_size=1, max_size=4)),
        st.builds(lambda lo, w: F.Range("f0", lo, lo + w),
                  st.floats(0, 90, allow_nan=False, width=32),
                  st.floats(0.5, 50, allow_nan=False, width=32)),
        st.builds(lambda lo, w: F.Range("f1", lo, lo + w),
                  st.floats(0, 90, allow_nan=False, width=32),
                  st.floats(0.5, 50, allow_nan=False, width=32)),
    )
    if depth >= 2:
        return draw(leaf)
    sub = filter_trees(depth=depth + 1)
    return draw(st.one_of(
        leaf,
        st.builds(lambda a, b: F.And(a, b), sub, sub),
        st.builds(lambda a, b: F.Or(a, b), sub, sub),
        st.builds(F.Not, leaf),
    ))


@settings(max_examples=60, deadline=None)
@given(filter_trees())
def test_property_program_matches_ast(flt):
    attrs = F.random_attributes(SCHEMA, 200, seed=42)
    try:
        prog = F.compile_filter(flt, SCHEMA, width=16)
    except ValueError:
        return  # DNF width overflow is allowed to raise
    m = F.eval_program(prog, attrs.ints, attrs.floats)
    expect = np.array([F.eval_filter_python(flt, attrs.row(i)) for i in range(attrs.n)])
    np.testing.assert_array_equal(m, expect)


# -- property: canonical signatures (cache keys) -----------------------------
def _equivalent_rewrite(f):
    """A semantically identical AST: AND/OR children reversed recursively,
    leaves double-negated."""
    if isinstance(f, F.And):
        return F.And(*[_equivalent_rewrite(c) for c in reversed(f.children)])
    if isinstance(f, F.Or):
        return F.Or(*[_equivalent_rewrite(c) for c in reversed(f.children)])
    if isinstance(f, F.Not):
        return F.Not(_equivalent_rewrite(f.child))
    return F.Not(F.Not(f))


@settings(max_examples=60, deadline=None)
@given(filter_trees())
def test_property_signature_invariant_under_equivalence(flt):
    """Reordered conjuncts/disjuncts, double negation, duplicated or
    absorbed disjuncts, and AND/OR identities all share one signature."""
    try:
        sig = F.filter_signature(flt, SCHEMA, width=16)
        variants = [
            _equivalent_rewrite(flt),
            F.Not(F.Not(flt)),
            F.Or(flt, flt),
            F.Or(flt, F.FalseFilter()),
            F.And(flt, F.TrueFilter()),
            F.And(flt, flt),
        ]
        for v in variants:
            assert F.filter_signature(v, SCHEMA, width=16) == sig
    except ValueError:
        return  # DNF width overflow is allowed to raise


@settings(max_examples=60, deadline=None)
@given(filter_trees(), filter_trees())
def test_property_equal_signature_implies_equal_semantics(f1, f2):
    """Soundness: equal signatures must evaluate identically on every row
    (a cache key collision would silently serve wrong results)."""
    try:
        s1 = F.filter_signature(f1, SCHEMA, width=16)
        s2 = F.filter_signature(f2, SCHEMA, width=16)
        p1 = F.compile_filter(f1, SCHEMA, width=16)
        p2 = F.compile_filter(f2, SCHEMA, width=16)
    except ValueError:
        return
    attrs = F.random_attributes(SCHEMA, 300, seed=43)
    m1 = F.eval_program(p1, attrs.ints, attrs.floats)
    m2 = F.eval_program(p2, attrs.ints, attrs.floats)
    if s1 == s2:
        np.testing.assert_array_equal(m1, m2)
    elif not np.array_equal(m1, m2):
        assert s1 != s2  # contrapositive (always true here; documents intent)


# -- oracle: the per-filter lowering, one numpy conjunction at a time --------
# ``compile_batch`` must write exactly the arrays this elementwise float32
# lowering wrote, stacked by ``stack_programs``.
@dataclass
class _Conj:
    imask: np.ndarray  # (m_i,) uint32 allowed-value bitmasks
    flo: np.ndarray  # (m_f,) float32
    fhi: np.ndarray  # (m_f,) float32

    def feasible(self) -> bool:
        return bool(np.all(self.imask != 0) and np.all(self.flo <= self.fhi))


def _full_conj(schema):
    m_i = len(schema.int_columns)
    m_f = len(schema.float_columns)
    imask = np.zeros((m_i,), np.uint32)
    for j, c in enumerate(schema.int_columns):
        imask[j] = np.uint32((1 << c.vocab) - 1)
    flo = np.full((m_f,), -np.inf, np.float32)
    fhi = np.full((m_f,), np.inf, np.float32)
    return _Conj(imask, flo, fhi)


def _int_bits(values, vocab, column):
    mask = np.uint32(0)
    for v in values:
        v = int(v)
        if not (0 <= v < vocab):
            raise ValueError(f"value {v} out of vocab [0,{vocab}) for column {column!r}")
        mask |= np.uint32(1) << np.uint32(v)
    return mask


def _strict_below(x):
    return float(np.nextafter(np.float32(x), np.float32(-np.inf)))


def _strict_above(x):
    return float(np.nextafter(np.float32(x), np.float32(np.inf)))


def _leaf_conjs(f, schema, negated):
    if isinstance(f, F.TrueFilter):
        return [] if negated else [_full_conj(schema)]
    if isinstance(f, F.FalseFilter):
        return [_full_conj(schema)] if negated else []

    if isinstance(f, F.Equality):
        col = schema.column(f.column)
        if col.kind in F.INT_KINDS:
            j = schema.int_index(f.column)
            bits = _int_bits([int(f.value)], col.vocab, f.column)
            c = _full_conj(schema)
            c.imask[j] = ~bits & c.imask[j] if negated else bits
            return [c]
        j = schema.float_index(f.column)
        v = float(f.value)
        if not negated:
            c = _full_conj(schema)
            c.flo[j], c.fhi[j] = v, v
            return [c]
        lo_c, hi_c = _full_conj(schema), _full_conj(schema)
        lo_c.fhi[j] = _strict_below(v)
        hi_c.flo[j] = _strict_above(v)
        return [lo_c, hi_c]

    if isinstance(f, F.Inclusion):
        col = schema.column(f.column)
        if col.kind not in F.INT_KINDS:
            dnf = []
            for v in f.values:
                dnf.extend(_leaf_conjs(F.Equality(f.column, v), schema, False))
            if negated:
                raise ValueError("NOT(Inclusion) on float columns is not supported; "
                                 "use Range complements")
            return dnf
        j = schema.int_index(f.column)
        bits = _int_bits(f.values, col.vocab, f.column)
        c = _full_conj(schema)
        full = c.imask[j]
        c.imask[j] = (~bits & full) if negated else bits
        return [c]

    if isinstance(f, F.Range):
        col = schema.column(f.column)
        lo = -math.inf if f.lo is None else float(f.lo)
        hi = math.inf if f.hi is None else float(f.hi)
        if col.kind in F.INT_KINDS:
            j = schema.int_index(f.column)
            vals = [v for v in range(col.vocab) if lo <= v <= hi]
            bits = _int_bits(vals, col.vocab, f.column)
            c = _full_conj(schema)
            full = c.imask[j]
            c.imask[j] = (~bits & full) if negated else bits
            return [c]
        j = schema.float_index(f.column)
        if not negated:
            c = _full_conj(schema)
            c.flo[j], c.fhi[j] = lo, hi
            return [c]
        out = []
        if lo > -math.inf:
            c = _full_conj(schema)
            c.fhi[j] = _strict_below(lo)
            out.append(c)
        if hi < math.inf:
            c = _full_conj(schema)
            c.flo[j] = _strict_above(hi)
            out.append(c)
        return out

    raise TypeError(f"not a leaf filter: {f!r}")


def _conj_and(a, b):
    return _Conj(a.imask & b.imask, np.maximum(a.flo, b.flo),
                 np.minimum(a.fhi, b.fhi))


def _to_dnf(f, schema, negated, max_width):
    if isinstance(f, F.Not):
        return _to_dnf(f.child, schema, not negated, max_width)
    if isinstance(f, F.And) or isinstance(f, F.Or):
        is_and = isinstance(f, F.And) != negated  # de Morgan
        child_dnfs = [_to_dnf(c, schema, negated, max_width) for c in f.children]
        if not is_and:
            out = [c for d in child_dnfs for c in d]
        else:
            out = [_full_conj(schema)]
            for d in child_dnfs:
                out = [_conj_and(a, b) for a in out for b in d]
                out = [c for c in out if c.feasible()]
                if len(out) > 4 * max_width:
                    raise ValueError(
                        f"filter DNF exceeds width {max_width}; simplify the predicate")
        out = [c for c in out if c.feasible()]
        if len(out) > 4 * max_width:
            raise ValueError(f"filter DNF exceeds width {max_width}")
        return out
    return [c for c in _leaf_conjs(f, schema, negated) if c.feasible()]


def oracle_compile(f, schema, width=8):
    conjs = _to_dnf(f, schema, False, max_width=width)
    if len(conjs) > width:
        raise ValueError(f"filter needs DNF width {len(conjs)} > {width}")
    m_i = len(schema.int_columns)
    m_f = len(schema.float_columns)
    valid = np.zeros((width,), np.float32)
    imask = np.zeros((width, m_i), np.uint32)
    flo = np.full((width, m_f), np.inf, np.float32)
    fhi = np.full((width, m_f), -np.inf, np.float32)
    for w, c in enumerate(conjs):
        valid[w] = 1.0
        imask[w] = c.imask
        flo[w] = c.flo
        fhi[w] = c.fhi
    return F.FilterProgram(valid, imask, flo, fhi)


def _assert_matches_oracle(flts, schema, width):
    """``compile_batch`` writes the oracle's stacked arrays bit for bit
    (dtype, shape and row order), and raises what the oracle raises."""
    try:
        want = F.stack_programs([oracle_compile(f, schema, width) for f in flts])
    except (ValueError, KeyError, TypeError) as e:
        with pytest.raises(type(e)) as got:
            F.compile_batch(flts, schema, width)
        assert str(got.value) == str(e)
        return
    got = F.compile_batch(flts, schema, width)
    assert list(got) == list(want)
    for k in want:
        assert got[k].dtype == want[k].dtype, k
        assert got[k].shape == want[k].shape, k
        assert got[k].tobytes() == want[k].tobytes(), k
    for b, f in enumerate(flts[:8]):    # the one-program entry point too
        one = F.compile_filter(f, schema, width)
        for k in want:
            assert getattr(one, k).tobytes() == want[k][b].tobytes(), k


def _bench_batch(cell):
    """The first 256 requests' filters of a benchmark cell's pool, drawn by
    the benchmark's own generator from the cell's traffic file."""
    bench = Path(__file__).resolve().parents[1] / "bench"
    if str(bench) not in sys.path:
        sys.path.append(str(bench))
    import workload
    c = workload.load_cell(cell)
    pool = workload.make_pool(c.config, c.traffic, 2**31 + 4099)
    flts = [workload.to_program_filter(pool.filters[i])
            for i in pool.filter_of[:256]]
    return flts, workload.program_schema(c.config), 8


_FLOAT_ZERO_CASES = [
    F.Not(F.Range("f0", 0.0, 5.0)), F.Not(F.Range("f0", -0.0, 5.0)),
    F.Not(F.Range("f0", -3.0, 0.0)), F.Not(F.Range("f0", -3.0, -0.0)),
    F.Not(F.Equality("f1", 0.0)), F.Not(F.Equality("f1", -0.0)),
    F.And(F.Range("f0", -0.0, 5.0), F.Range("f0", 0.0, 3.0)),
    F.And(F.Range("f0", 0.0, 5.0), F.Range("f0", -0.0, 3.0)),
    F.And(F.Range("f0", -4.0, 0.0), F.Range("f0", -5.0, -0.0)),
    F.And(F.Range("f0", -4.0, -0.0), F.Range("f0", -5.0, 0.0)),
    F.And(F.Not(F.Range("f0", 0.0, 5.0)), F.Range("f0", -0.0, 9.0)),
]
_COLLAPSED = 1.0 + 1e-9       # rounds to float32 1.0
_EDGE_CASES = {
    "true_false": ([F.TrueFilter(), F.FalseFilter(), F.Not(F.TrueFilter()),
                    F.Not(F.FalseFilter()), F.And(), F.Or(),
                    F.And(F.TrueFilter(), F.FalseFilter()),
                    F.Or(F.TrueFilter(), F.Equality("i0", 2))], SCHEMA, 8),
    "signed_zero": (_FLOAT_ZERO_CASES, SCHEMA, 8),
    "collapsed_range": ([F.Range("f0", 1.0, _COLLAPSED),
                         F.Range("f0", _COLLAPSED, 1.0),
                         F.Not(F.Range("f0", _COLLAPSED, 1.0)),
                         F.And(F.Range("f0", 0.5, 1.0),
                               F.Range("f0", _COLLAPSED, 2.0)),
                         F.Inclusion("f1", [0.1, 0.1 + 1e-12, 7.0]),
                         F.Range("i0", 2.5, 2.9), F.Range("i1", 3.0, 3.0)],
                        SCHEMA, 8),
    "width_guard": ([F.Equality("b0", True),
                     F.And(*[F.Or(*[F.Equality(c, v) for v in (1, 2, 3)])
                             for c in ("f0", "f1", "i0")])], SCHEMA, 4),
    "width_overflow": ([F.Equality("b0", True),
                        F.Inclusion("f1", [float(v) for v in range(5)])],
                       SCHEMA, 4),
    "or_guard": ([F.Or(*[F.Equality("f0", float(v)) for v in range(17)])],
                 SCHEMA, 4),
    "bad_value": ([F.Equality("i0", 3), F.Inclusion("i1", [2, 10])],
                  SCHEMA, 8),
    "not_float_inclusion": ([F.Not(F.Inclusion("f0", [1.0, 2.0]))],
                            SCHEMA, 8),
    "unknown_column": ([F.Range("nope", 0.0, 1.0)], SCHEMA, 8),
    "empty_vocab": ([F.And(), F.TrueFilter(), F.Not(F.FalseFilter()),
                     F.Or(F.And(), F.Range("f0", 1.0, 2.0)),
                     F.And(F.Range("f0", 1.0, 2.0))],
                    F.Schema((F.ColumnSpec("z", "int", 0),
                              F.ColumnSpec("f0", "float"))), 8),
    "no_float_columns": ([F.Equality("b0", True), F.Not(F.Inclusion("i0", [1, 2])),
                          F.TrueFilter(), F.FalseFilter()],
                         F.paper_schema(n_float=0), 8),
    "no_int_columns": ([F.Range("f0", 1.0, 2.0), F.Not(F.Range("f0", 1.0, 2.0)),
                        F.TrueFilter(), F.FalseFilter()],
                       F.paper_schema(n_bool=0, n_int=0), 8),
}


@pytest.mark.parametrize("case", ["hypothesis", "sift128-pq.rare.closed",
                                  "sift128-f32.paper.closed",
                                  *_EDGE_CASES])
def test_compile_batch_matches_oracle(case):
    if case == "hypothesis":
        @settings(max_examples=40, deadline=None)
        @given(st.lists(filter_trees(), min_size=1, max_size=64),
               st.sampled_from([4, 8, 16]))
        def mixed(flts, width):
            _assert_matches_oracle(flts, SCHEMA, width)
        mixed()
        return
    flts, schema, width = (_EDGE_CASES[case] if case in _EDGE_CASES
                           else _bench_batch(case))
    _assert_matches_oracle(flts, schema, width)
