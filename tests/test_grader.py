"""Agreement vectors for the family-structured grading subsystem.

Every equivalence family in evaluation/grader.py gets positive AND
negative vectors, plus assertions on WHICH family decided (the debug-trace
contract: a miscounted reward must be auditable down to the deciding
rule). The reward channel (reward/math_parser) delegates here, so these
are correctness tests for RLVR training itself, not just eval tables.
"""

import pytest

from areal_tpu.evaluation.grader import (
    FAMILIES,
    GradeResult,
    answers_equal,
    grade_answer,
    normalize_answer,
    numeric_value,
    strip_units,
)


@pytest.fixture(scope="module", autouse=True)
def _sympy_is_warm():
    """A symbolic check has 3 s of wall clock. The first one of a process
    also imports sympy's parsers and makes their first parse, which beside
    five busy workers took more than that and read as "not equal"
    (``[-0.25--1/4-True]`` is the first case of this file that needs
    sympy): made here, untimed."""
    from areal_tpu.evaluation import grader

    assert grader._parse_sym("-1/4") is not None


def test_family_registry_complete():
    names = [n for n, _ in FAMILIES]
    assert names == [
        "exact", "choice", "numeric", "interval", "matrix", "equation",
        "symbolic",
    ]
    for _, fn in FAMILIES:
        assert callable(fn)


# --- numeric family (tolerance + percent ambiguity) -----------------------
NUMERIC = [
    ("42", "42.0", True),
    ("42", "43", False),
    ("3.14159", "3.1416", True),   # within rel_tol=1e-4
    ("3.14159", "3.15", False),
    ("1,234", "1234", True),
    ("2e3", "2000", True),
    ("-0.25", "-1/4", True),
    ("0.00001", "0", True),        # |pred| < rel_tol vs zero truth
    ("0.5", "0.52", False),
    # percent ambiguity: x matches x/100 and 100*x
    ("50%", "0.5", True),
    ("0.5", "50%", True),
    ("150%", "1.5", True),
    ("3%", "0.03", True),
    ("50", "0.5", True),
    ("0.5", "50", True),
    ("50%", "0.4", False),
    ("7%", "0.08", False),
]


@pytest.mark.parametrize("pred,truth,equal", NUMERIC)
def test_numeric_family(pred, truth, equal):
    r = grade_answer(pred, truth)
    assert r.equal is equal, r.trace
    if r.equal:
        assert r.family in ("exact", "numeric")
    else:
        assert r.family == "numeric"  # decisive negative, not symbolic


# --- percent / fraction / mixed-number forms ------------------------------
FRACTION = [
    ("3/4", "0.75", True),
    ("1/3", "0.33333", True),
    ("22/7", "3.14159", False),
    ("-1/2", "-0.5", True),
    (r"\frac{3}{4}", "0.75", True),
    (r"\frac12", "1/2", True),
    (r"\frac1{72}", "1/72", True),
    (r"\dfrac{3}{4}", "3/4", True),
    (r"\frac{3}{4}", "0.8", False),
    ("2 1/2", "2.5", True),        # mixed number
    ("-2 1/2", "-2.5", True),      # negative mixed number
    ("2 1/3", "2.5", False),
    ("0.5\\%", "0.005", True),
]


@pytest.mark.parametrize("pred,truth,equal", FRACTION)
def test_fraction_family(pred, truth, equal):
    assert answers_equal(pred, truth) is equal


# --- interval / tuple / set family ----------------------------------------
INTERVAL = [
    ("(1, 2)", "(1.0, 2.0)", True),
    ("(1, 2)", "(2, 1)", False),           # tuples are ORDERED
    ("(1, 2)", "(1, 2, 3)", False),        # arity mismatch
    ("[0, 1]", "(0, 1)", True),            # bracket style ignored
    ("(0, 1]", "[0, 1]", True),
    ("[0, 2]", "[0, 1]", False),
    ("[1/2, 1]", "[0.5, 1]", True),
    ("[50%, 1]", "[0.5, 1]", True),
    (r"[0, \frac{1}{2}]", "[0, 0.5]", True),
    ("(1, 2, 3)", "(1,2,3)", True),        # multi-answer tuple
    ("(1, 2, 3)", "(1, 2, 4)", False),
    (r"(\frac{3}{5},\frac{8}{3})", "(0.6,2.6667)", True),
]


@pytest.mark.parametrize("pred,truth,equal", INTERVAL)
def test_interval_family(pred, truth, equal):
    r = grade_answer(pred, truth)
    assert r.equal is equal, r.trace
    if equal:
        # ".0"-stripping normalization may already equate the strings
        assert r.family in ("exact", "interval")
    else:
        assert r.family == "interval"  # decisive negative


SETS = [
    # brace-literal sets compare UNORDERED
    ("{1, 2}", "{2, 1}", True),
    (r"\{1, 2\}", r"\{2, 1\}", True),
    (r"\{1, 2\}", r"\{1, 3\}", False),
    ("{1, 2}", "{1, 2, 3}", False),
    ("{1/2, 2}", "{2, 0.5}", True),
]


@pytest.mark.parametrize("pred,truth,equal", SETS)
def test_set_family(pred, truth, equal):
    r = grade_answer(pred, truth)
    assert r.equal is equal, r.trace


def test_paren_tuple_is_not_a_set():
    # same elements, different order: parens stay ordered even though the
    # equivalent brace form matches
    assert not answers_equal("(1, 2)", "(2, 1)")
    assert answers_equal("{1, 2}", "{2, 1}")


# --- matrix / vector family ------------------------------------------------
MATRIX = [
    (
        r"\begin{pmatrix}1 & 2\\3 & 4\end{pmatrix}",
        r"\begin{bmatrix}1.0 & 2\\3 & 4.0\end{bmatrix}",
        True,
    ),
    (
        r"\begin{pmatrix}1 & 2\\3 & 4\end{pmatrix}",
        r"\begin{pmatrix}1 & 2\\3 & 5\end{pmatrix}",
        False,
    ),
    (  # column vector
        r"\begin{pmatrix}1\\2\\3\end{pmatrix}",
        r"\begin{pmatrix}1.0\\2\\3.0\end{pmatrix}",
        True,
    ),
    (  # shape mismatch: 2x2 vs 1x4
        r"\begin{pmatrix}1 & 2\\3 & 4\end{pmatrix}",
        r"\begin{pmatrix}1 & 2 & 3 & 4\end{pmatrix}",
        False,
    ),
    (  # array env canonicalizes to pmatrix
        r"\begin{array}{cc}1 & 2\\3 & 4\end{array}",
        r"\begin{pmatrix}1 & 2\\3 & 4\end{pmatrix}",
        True,
    ),
    (  # fractional elements recurse through the numeric family
        r"\begin{pmatrix}\frac{1}{2}\\1\end{pmatrix}",
        r"\begin{pmatrix}0.5\\1.0\end{pmatrix}",
        True,
    ),
]


@pytest.mark.parametrize("pred,truth,equal", MATRIX)
def test_matrix_family(pred, truth, equal):
    r = grade_answer(pred, truth)
    assert r.equal is equal, r.trace
    if equal:
        assert r.family in ("exact", "matrix")
    else:
        assert r.family == "matrix"


# --- choice family ---------------------------------------------------------
CHOICE = [
    ("(B)", "B", True),
    ("B.", "B", True),
    ("The answer is B", "B", True),
    ("The answer is C, a tricky one", "A", False),  # "a" is an article
    ("B", "C", False),
]


@pytest.mark.parametrize("pred,truth,equal", CHOICE)
def test_choice_family(pred, truth, equal):
    assert answers_equal(pred, truth) is equal


def test_choice_family_decides_positive():
    r = grade_answer("(B)", "B")
    assert r.equal and r.family == "choice"


# --- equation family -------------------------------------------------------
EQUATION = [
    ("x + y = 3", "y + x = 3", True),
    ("2a - b = 4", "b - 2a = -4", True),   # either sign
    ("x + y = 3", "x + y = 4", False),
    ("x = 5", "5", True),                  # short-lhs prefix stripping
]


@pytest.mark.parametrize("pred,truth,equal", EQUATION)
def test_equation_family(pred, truth, equal):
    assert answers_equal(pred, truth) is equal


# --- symbolic family -------------------------------------------------------
SYMBOLIC = [
    ("x**2 - 1", "(x-1)*(x+1)", True),
    ("x + 1", "x - 1", False),
    (r"\sqrt{8}", r"2\sqrt{2}", True),
    (r"\sqrt{2}", "2", False),
    ("2*pi", r"2\pi", True),
    (r"\frac{x+2}{7}", r"\frac{x}{7}+\frac{2}{7}", True),
    (r"\frac{x}{2}", "x/2", True),
]


@pytest.mark.parametrize("pred,truth,equal", SYMBOLIC)
def test_symbolic_family(pred, truth, equal):
    r = grade_answer(pred, truth)
    assert r.equal is equal, r.trace


def test_symbolic_family_decides():
    r = grade_answer("x**2 - 1", "(x-1)*(x+1)")
    assert r.family == "symbolic"


def test_hostile_expression_fails_fast():
    import time

    t0 = time.monotonic()
    r = grade_answer("9**9**9**9**9", "12")
    assert not r.equal
    assert time.monotonic() - t0 < 10.0


# --- unit stripping --------------------------------------------------------
def test_strip_units_rule():
    assert strip_units("5 cm").strip() == "5"
    assert strip_units("10 miles").strip() == "10"
    # bare "m" is algebra, not meters
    assert strip_units("2m") == "2m"


UNITS = [
    ("5 dollars", "5", True),
    (r"5\text{ cm}", "5", True),
    ("10 miles", "10", True),
    ("90^\\circ", "90", True),
    ("2m", "2", False),
]


@pytest.mark.parametrize("pred,truth,equal", UNITS)
def test_unit_stripping_vectors(pred, truth, equal):
    assert answers_equal(pred, truth) is equal


def test_keep_units_mode():
    """KEEP_UNITS benchmarks (minerva/carp) grade without unit stripping:
    "5 cm" is NOT "5" when the unit is part of the answer."""
    assert answers_equal("5 cm", "5", strip_units=True)
    assert not answers_equal("5 cm", "5", strip_units=False)
    assert answers_equal("5 cm", "5 cm", strip_units=False)


# --- trace / GradeResult contract ------------------------------------------
def test_grade_result_reports_deciding_family():
    cases = [
        ("42", "42", "exact"),
        ("0.5", "50%", "numeric"),
        ("(1/2, 2)", "(0.5, 2)", "interval"),
        (
            r"\begin{pmatrix}\frac{1}{2}\\2\end{pmatrix}",
            r"\begin{pmatrix}0.5\\2\end{pmatrix}",
            "matrix",
        ),
        ("x**2 - 1", "(x-1)*(x+1)", "symbolic"),
    ]
    for pred, truth, family in cases:
        r = grade_answer(pred, truth)
        assert isinstance(r, GradeResult)
        assert r.equal, (pred, truth, r.trace)
        assert r.family == family, (pred, truth, r.family)
        assert bool(r) is True  # GradeResult is truthy on equality


def test_trace_names_consulted_families():
    r = grade_answer(r"\frac{1}{2}", "0.5")
    assert r.equal
    # the trace must show the normalization and at least one family note
    assert any("normalized" in line for line in r.trace)
    assert len(r.trace) >= 2


def test_null_sides():
    assert grade_answer(None, "5").family == "null"
    assert grade_answer("5", None).family == "null"
    assert grade_answer("", "5").family == "null"
    assert not answers_equal(None, None)


def test_numeric_value_helper():
    assert numeric_value("3.5") == 3.5
    assert abs(numeric_value("sqrt(4)") - 2.0) < 1e-9
    assert numeric_value("x + 1") is None


def test_normalize_answer_reexported_surface():
    # normalization is shared with reward/math_parser verbatim
    from areal_tpu.reward import math_parser

    assert math_parser.normalize_answer is normalize_answer
    assert math_parser.answers_equal is answers_equal
