"""Benchmark-grade answer equivalence: a family-structured grader.

Role of the reference's evaluation/grader.py (401 LoC, the sympy-based
``math_equal`` behind every published AReaL quality table) AND of its
reward-side twin in reward/math_parser.py: decide whether two answer
strings denote the same mathematical object. This module is the ONE source
of truth — training rewards (``reward/math_parser.py``) and offline eval
(``evaluation/math_eval.py``) both delegate here, so eval accuracy measures
exactly the training-time success criterion and a grading fix lands in both
at once.

The cascade is decomposed into explicit **equivalence families**, each an
individually-testable rule that either decides (True/False) or abstains
(None), tried in ``FAMILIES`` order:

====================  ======================================================
family                decides when / what
====================  ======================================================
``exact``             normalized strings equal (case-insensitive); abstains
                      otherwise
``choice``            truth is a bare A–E letter and the prediction's last
                      standalone letter matches (case-sensitive match
                      against the RAW prediction — uppercasing would turn
                      the article "a" into choice A); abstains on mismatch
``numeric``           both sides evaluate to numbers: rel-tol comparison
                      incl. the percent ambiguity the reference accepts
                      (x matches x/100 and 100·x). Covers
                      percent/fraction/mixed-number forms because
                      normalization rewrites them to evaluable expressions.
                      DECISIVE (True or False) when both sides are numeric
``interval``          both sides are bracketed tuples/intervals/sets:
                      elementwise recursion; bracket style ignored
                      ((0,1] == [0,1], the reference's bracket stripping);
                      brace-literal sets ({1,2}) compare UNORDERED.
                      DECISIVE when both sides split
``matrix``            both sides are pmatrix/bmatrix/array envs:
                      elementwise recursion. DECISIVE when both parse
``equation``          both sides are single equations: lhs−rhs equivalence,
                      either sign; abstains on failure
``symbolic``          timeout-bounded sympy fallback (parse, ``.equals``,
                      ``simplify(a-b)==0``, N()); hostile expressions
                      (giant pow towers) are refused up front. DECISIVE
====================  ======================================================

:func:`grade_answer` returns a :class:`GradeResult` carrying the verdict,
WHICH family decided, and a debug trace of every family consulted — the
instrument for auditing a miscounted reward before it corrupts a policy
gradient (the failure mode async-RLVR systems like ROLL Flash and Laminar
call out: a wrong reward is silent data corruption, not a visible crash).

Unit stripping ("5 cm" == "5") is part of normalization and exposed as
:func:`strip_units`; benchmarks whose answers legitimately carry units
(minerva_math, carp_en — the reference's STRIP_EXCEPTIONS) grade with
``strip_units=False``.
"""

import dataclasses
import re
import threading as _threading
from typing import List, Optional

from areal_tpu.evaluation.extract import extract_boxed

# ---------------------------------------------------------------------------
# Normalization
# ---------------------------------------------------------------------------

_CHOICE_RE = re.compile(r"\b([A-E])\b")

_WORD_NUMBERS = {
    "zero": "0", "one": "1", "two": "2", "three": "3", "four": "4",
    "five": "5", "six": "6", "seven": "7", "eight": "8", "nine": "9",
    "ten": "10", "eleven": "11", "twelve": "12",
}

# measurement words stripped from answers ("5 cm" == "5"); the reference
# carries a much longer unit list — these cover the GSM8K/MATH datasets
# NOTE: no bare single letters (an "m" could be algebra, not meters) and
# no words that double as operators ("times")
_UNITS = (
    "degrees?|cm|km|mm|meters?|inch(?:es)?|feet|foot|ft|miles?|mph|"
    "hours?|hrs?|minutes?|mins?|seconds?|secs?|days?|weeks?|months?|"
    "years?|dollars?|cents?|bucks?|points?|units?|square|cubic|percent|"
    "people|students?|apples?|oranges?|ways?"
)
_UNIT_RE = re.compile(r"(^|[\s\d])(" + _UNITS + r")($|\W)")


def strip_units(s: str) -> str:
    """Remove measurement words ("5 cm" → "5"). Individually testable so
    the KEEP_UNITS benchmarks can pin the NON-stripped behavior."""
    prev = None
    while prev != s:
        prev = s
        s = _UNIT_RE.sub(r"\1\3", s)
    return s


def _fix_fracs(s: str) -> str:
    """\\frac12, \\frac1{72}, \\frac{a}2 → (1)/(2) style; nested braces
    handled by repeated innermost substitution."""
    s = s.replace("\\tfrac", "\\frac").replace("\\dfrac", "\\frac")
    # brace-less arguments first: \frac12 / \frac1{72} / \frac{a}2
    s = re.sub(r"\\frac(\d)(\d)", r"\\frac{\1}{\2}", s)
    s = re.sub(r"\\frac(\d)\{", r"\\frac{\1}{", s)
    s = re.sub(r"\\frac\{([^{}]+)\}(\d)", r"\\frac{\1}{\2}", s)
    pat = re.compile(r"\\frac\{([^{}]+)\}\{([^{}]+)\}")
    while True:
        s2 = pat.sub(r"((\1)/(\2))", s)
        if s2 == s:
            return s
        s = s2


def _fix_sqrt(s: str) -> str:
    s = re.sub(r"\\sqrt\[(\d+)\]\{([^{}]+)\}", r"((\2)**(1/\1))", s)
    s = re.sub(r"\\sqrt\s*(\d+)", r"sqrt(\1)", s)
    s = re.sub(r"\\sqrt\{([^{}]+)\}", r"sqrt(\1)", s)
    return s.replace("\\sqrt", "sqrt")


def normalize_answer(ans: str, do_strip_units: bool = True) -> str:
    s = str(ans).strip().replace("\n", "")
    s = s.rstrip(".").strip()
    if "\\boxed" in s:  # a raw \boxed{...} answer normalizes to its content
        b = extract_boxed(s)
        if b is not None:
            s = b
    s = s.replace("{,}", "")  # latex thousands separator: 5{,}905
    s = s.replace("\\!", "").replace("\\,", " ").replace("\\ ", " ")
    s = s.replace("\\left", "").replace("\\right", "")
    s = s.replace("^{\\circ}", "").replace("^\\circ", "")
    s = s.replace("\\$", "").replace("$", "")
    s = s.replace("\\%", "").replace("%", "")
    s = s.replace("\\(", "").replace("\\)", "")
    # latex set braces \{...\} → bare braces (later mapped to parens like
    # every brace; the set FAMILY looks at the raw string for brace-ness)
    s = s.replace("\\{", "{").replace("\\}", "}")
    # matrix env canonicalization (array/bmatrix → pmatrix)
    s = re.sub(r"\\begin\{array\}\{[^}]*\}", r"\\begin{pmatrix}", s)
    s = s.replace("\\end{array}", "\\end{pmatrix}")
    s = s.replace("bmatrix", "pmatrix")
    s = re.sub(r"\\text\s*\{([^{}]*)\}", r"\1", s)
    s = re.sub(r"\\mbox\s*\{[^{}]*\}", "", s)
    s = s.replace("\\mathbf", "").replace("\\mathrm", "")
    # strip "x=" / "k =" style prefixes (single short lhs)
    if s.count("=") == 1 and len(s.split("=")[0].strip()) <= 2:
        s = s.split("=")[1]
    # word numbers ("two" → "2") for bare word answers
    low = s.strip().lower()
    if low in _WORD_NUMBERS:
        return _WORD_NUMBERS[low]
    if do_strip_units:
        s = strip_units(s)
    # thousands separators only — "1,234" → "1234" but "(1, 2)" keeps its
    # tuple comma
    prev = None
    while prev != s:
        prev = s
        s = re.sub(r"(\d),(?=\d{3}(\D|$))", r"\1", s)
    # innermost-out: \frac{\sqrt{3}}{2} needs the sqrt's braces resolved
    # before the frac pattern (brace-free args) can match, and vice versa
    prev = None
    while prev != s:
        prev = s
        s = _fix_sqrt(_fix_fracs(s))
    s = s.replace("\\pi", "pi").replace("\\infty", "oo").replace(
        "infinity", "oo"
    )
    s = s.replace("\\cdot", "*").replace("\\times", "*").replace(
        "\\div", "/"
    )
    s = s.replace("^{", "**{").replace("^", "**")
    s = s.replace("{", "(").replace("}", ")")
    # bare a/b (no parens) stays as-is; "2 1/2" mixed number → (2+1/2)
    m = re.fullmatch(r"\s*(-?\d+)\s+(\d+)\s*/\s*(\d+)\s*", s)
    if m:
        sign = "-" if m.group(1).startswith("-") else "+"
        s = f"({m.group(1)}{sign}({m.group(2)})/({m.group(3)}))"
    s = re.sub(r"\s+", " ", s).strip()
    s = s.rstrip(". ").lstrip()
    # "0." prefixes
    if s.startswith("."):
        s = "0" + s
    # trailing ".000"
    s = re.sub(r"(\d+)\.0+$", r"\1", s)
    s = re.sub(r"(\d+)\.0+([^\d])", r"\1\2", s)
    return s.strip()


# ---------------------------------------------------------------------------
# sympy workers (timeout-bounded)
# ---------------------------------------------------------------------------
# sympy can blow up on pathological model outputs (e.g. 9**9**9**9); all
# sympy work runs in a DAEMON thread with a wall-clock timeout (daemon so a
# stuck worker can never block interpreter exit). Abandoned hostile threads
# leak until they finish; a live counter bounds them — past the bound,
# symbolic checks fail fast to False rather than stalling the reward path.

_SYMPY_TIMEOUT_S = 3.0
_MAX_STUCK_THREADS = 16
_stuck_lock = _threading.Lock()
_stuck_count = 0


def _hostile(s: str) -> bool:
    """Cheap pre-filter for expressions whose EVALUATION cannot be
    interrupted by a thread timeout (a giant integer pow is one CPython
    bytecode — it never releases the GIL, so the only safe defense is to
    refuse it up front; the reference pays a subprocess per check for the
    same reason)."""
    if len(s) > 300:
        return True
    if s.count("**") >= 3:
        return True
    for m in re.finditer(r"\*\*\s*\(?\s*-?(\d+)", s):
        if len(m.group(1)) > 4:  # exponent >= 10^4
            return True
    if re.search(r"\d{40,}", s):  # absurdly long literals
        return True
    return False


def _with_timeout(fn, *args):
    global _stuck_count
    # the interpreter's one import of sympy (seconds on a busy machine) is
    # the process's cost, not the answer's: outside the timed thread
    import sympy.parsing.latex  # noqa: F401
    import sympy.parsing.sympy_parser  # noqa: F401

    with _stuck_lock:
        if _stuck_count >= _MAX_STUCK_THREADS:
            return None
    box = {}
    state = {"abandoned": False, "finished": False}

    def run():
        global _stuck_count
        try:
            box["r"] = fn(*args)
        except Exception:
            box["r"] = None
        finally:
            with _stuck_lock:
                state["finished"] = True
                if state["abandoned"]:  # un-count ourselves
                    _stuck_count -= 1

    th = _threading.Thread(target=run, daemon=True, name="sympy-eval")
    th.start()
    th.join(timeout=_SYMPY_TIMEOUT_S)
    with _stuck_lock:
        if not state["finished"]:
            state["abandoned"] = True
            _stuck_count += 1
            return None
    return box.get("r")


def _parse_sym(s: str):
    """Parse a (normalized) answer into a sympy object: plain expression
    first, then LaTeX via the lark backend (reference tries parse_latex /
    parse_expr / latex2sympy in order)."""
    import sympy
    from sympy.parsing.sympy_parser import (
        implicit_multiplication_application,
        parse_expr,
        standard_transformations,
    )

    transforms = standard_transformations + (
        implicit_multiplication_application,
    )
    for attempt in (
        lambda: parse_expr(s, evaluate=True, transformations=transforms),
        lambda: sympy.parsing.latex.parse_latex(s, backend="lark"),
        lambda: sympy.sympify(s),
    ):
        try:
            out = attempt()
            if out is not None:
                return out
        except Exception:
            continue
    return None


def _sympy_equal(a: str, b: str) -> bool:
    if _hostile(a) or _hostile(b):
        return False

    def work():
        import sympy

        ea, eb = _parse_sym(a), _parse_sym(b)
        if ea is None or eb is None:
            return False
        try:
            if ea == eb or str(ea) == str(eb):
                return True
        except Exception:
            pass
        try:
            if ea.equals(eb) or sympy.simplify(ea - eb) == 0:
                return True
        except Exception:
            pass
        try:
            # equation forms: |lhs-rhs| agree
            if abs(ea.lhs - ea.rhs).equals(abs(eb.lhs - eb.rhs)):
                return True
        except Exception:
            pass
        try:
            return _isclose(float(sympy.N(ea)), float(sympy.N(eb)))
        except Exception:
            return False

    return bool(_with_timeout(work))


def numeric_value(s: str) -> Optional[float]:
    """Float value of a possibly-symbolic expression (None when the string
    does not denote a number)."""
    try:
        return float(s)
    except (ValueError, TypeError):
        pass
    if s.endswith("\\"):
        s = s[:-1]
    if _hostile(s):
        return None

    def work():
        import sympy

        v = _parse_sym(s)
        if v is not None and getattr(v, "is_number", False):
            return float(sympy.N(v))
        return None

    return _with_timeout(work)


def _isclose(a: float, b: float, rel_tol: float = 1e-4) -> bool:
    from math import isclose

    return isclose(a, b, rel_tol=rel_tol)


# ---------------------------------------------------------------------------
# Structure parsers shared by the interval / matrix families
# ---------------------------------------------------------------------------

def _split_elements(s: str) -> Optional[List[str]]:
    """Top-level comma split of a bracketed tuple/interval/set."""
    if len(s) < 2 or s[0] not in "([" or s[-1] not in ")]":
        return None
    inner = s[1:-1]
    parts, depth, cur = [], 0, []
    for ch in inner:
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        if ch == "," and depth == 0:
            parts.append("".join(cur))
            cur = []
        else:
            cur.append(ch)
    parts.append("".join(cur))
    return [p.strip() for p in parts] if len(parts) > 1 else None


def _matrix_rows(s: str) -> Optional[List[List[str]]]:
    m = re.fullmatch(
        r"\\begin\(pmatrix\)(.*)\\end\(pmatrix\)", s
    ) or re.fullmatch(r"\\begin\{pmatrix\}(.*)\\end\{pmatrix\}", s)
    if not m:
        return None
    rows = [r.strip() for r in m.group(1).split("\\\\") if r.strip()]
    return [[c.strip() for c in r.split("&")] for r in rows]


_SET_LITERAL_RE = re.compile(r"\\?\{.*\\?\}")


def _is_set_literal(raw: str) -> bool:
    """True when the RAW answer is written in set-brace notation
    ({1, 2} or \\{1, 2\\}) — those compare unordered."""
    s = str(raw).strip().strip("$").strip()
    return bool(_SET_LITERAL_RE.fullmatch(s))


# ---------------------------------------------------------------------------
# Equivalence families
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class _Ctx:
    """Per-grade context threaded through the families."""

    raw_pred: str
    raw_truth: str
    rel_tol: float
    strip_units: bool
    trace: List[str]

    def recurse(self, a: str, b: str) -> bool:
        return answers_equal(
            a, b, rel_tol=self.rel_tol, strip_units=self.strip_units
        )

    def note(self, msg: str) -> None:
        self.trace.append(msg)


def family_exact(p: str, t: str, ctx: _Ctx) -> Optional[bool]:
    """Normalized string equality, case-insensitive."""
    if p.lower() == t.lower():
        ctx.note(f"exact: normalized strings equal ({p!r})")
        return True
    ctx.note(f"exact: {p!r} != {t!r}")
    return None


def family_choice(p: str, t: str, ctx: _Ctx) -> Optional[bool]:
    """Multiple choice: reference accepts "(B)" / "B." / "answer B" for
    "B". Case-sensitive against the RAW prediction — uppercasing the
    completion would turn the article "a" into choice A. Abstains on
    mismatch (a numeric answer may still match a numeric truth later)."""
    if t not in "ABCDE" or len(t) != 1:
        return None
    letters = _CHOICE_RE.findall(str(ctx.raw_pred))
    if letters and letters[-1] == t:
        ctx.note(f"choice: last letter {letters[-1]!r} matches")
        return True
    ctx.note(f"choice: letters {letters!r} do not end with {t!r}")
    return None


def family_numeric(p: str, t: str, ctx: _Ctx) -> Optional[bool]:
    """Numeric equality at rel_tol, with the percentage ambiguity the
    reference accepts (x matches x/100 and 100·x). Covers plain numbers,
    percents, fractions and mixed numbers (normalization rewrites those to
    evaluable expressions). Decisive when both sides are numeric."""
    fp, ft = numeric_value(p), numeric_value(t)
    if fp is None or ft is None:
        ctx.note(f"numeric: not both numeric (pred={fp}, truth={ft})")
        return None
    for label, target in (
        ("truth", ft), ("truth/100", ft / 100.0), ("truth*100", ft * 100.0)
    ):
        if target == 0:
            if abs(fp) < ctx.rel_tol:
                ctx.note(f"numeric: |{fp}| < rel_tol vs zero {label}")
                return True
        elif _isclose(fp, target, ctx.rel_tol):
            ctx.note(f"numeric: {fp} ~= {target} ({label})")
            return True
    ctx.note(f"numeric: {fp} != {ft} (incl. percent forms)")
    return False


def family_interval(p: str, t: str, ctx: _Ctx) -> Optional[bool]:
    """Tuples / intervals / sets: element-wise recursion. Bracket style is
    IGNORED ((0,1] == [0,1]) — matching the reference, which strips
    brackets before comparing (math_equal's "deal with [], (), {}" block).
    Raw brace-literal sets ({1,2} / \\{1,2\\}) compare unordered."""
    pe, te = _split_elements(p), _split_elements(t)
    if pe is None or te is None:
        return None
    if len(pe) != len(te):
        ctx.note(f"interval: arity {len(pe)} != {len(te)}")
        return False
    if _is_set_literal(ctx.raw_pred) and _is_set_literal(ctx.raw_truth):
        # unordered multiset match: each pred element consumes one
        # unmatched truth element
        remaining = list(te)
        for a in pe:
            for i, b in enumerate(remaining):
                if ctx.recurse(a, b):
                    remaining.pop(i)
                    break
            else:
                ctx.note(f"interval(set): no match for element {a!r}")
                return False
        ctx.note(f"interval(set): {len(pe)} elements matched unordered")
        return True
    ok = all(ctx.recurse(a, b) for a, b in zip(pe, te))
    ctx.note(
        f"interval: elementwise {'match' if ok else 'MISMATCH'} "
        f"({len(pe)} elements)"
    )
    return ok


def family_matrix(p: str, t: str, ctx: _Ctx) -> Optional[bool]:
    """Matrices / column vectors: element-wise recursion over pmatrix rows
    (array/bmatrix envs were canonicalized to pmatrix)."""
    pm, tm = _matrix_rows(p), _matrix_rows(t)
    if pm is None or tm is None:
        return None
    if [len(r) for r in pm] != [len(r) for r in tm]:
        ctx.note("matrix: shape mismatch")
        return False
    ok = all(
        ctx.recurse(a, b)
        for ra, rb in zip(pm, tm)
        for a, b in zip(ra, rb)
    )
    ctx.note(f"matrix: elementwise {'match' if ok else 'MISMATCH'}")
    return ok


def family_equation(p: str, t: str, ctx: _Ctx) -> Optional[bool]:
    """Single equations on both sides: lhs−rhs difference equivalent,
    either sign. Abstains on failure (symbolic gets the last word)."""
    if p.count("=") != 1 or t.count("=") != 1:
        return None
    pl, pr = p.split("=")
    tl, tr = t.split("=")
    if _sympy_equal(f"({pl})-({pr})", f"({tl})-({tr})") or _sympy_equal(
        f"-(({pl})-({pr}))", f"({tl})-({tr})"
    ):
        ctx.note("equation: lhs-rhs difference equivalent")
        return True
    ctx.note("equation: differences not equivalent")
    return None


def family_symbolic(p: str, t: str, ctx: _Ctx) -> Optional[bool]:
    """Timeout-bounded sympy symbolic equivalence — the cascade
    terminator: always decisive."""
    ok = _sympy_equal(p, t)
    ctx.note(f"symbolic: sympy says {'equal' if ok else 'not equal'}")
    return ok


FAMILIES: List[tuple] = [
    ("exact", family_exact),
    ("choice", family_choice),
    ("numeric", family_numeric),
    ("interval", family_interval),
    ("matrix", family_matrix),
    ("equation", family_equation),
    ("symbolic", family_symbolic),
]


# ---------------------------------------------------------------------------
# Entry points
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class GradeResult:
    """Verdict plus the audit trail: which family decided and what every
    consulted family saw."""

    equal: bool
    family: Optional[str]
    trace: List[str] = dataclasses.field(default_factory=list)

    def __bool__(self) -> bool:
        return self.equal


def grade_answer(
    pred: Optional[str],
    truth: Optional[str],
    rel_tol: float = 1e-4,
    strip_units: bool = True,
) -> GradeResult:
    """Run the family cascade; first family with an opinion decides."""
    trace: List[str] = []
    if pred is None or truth is None:
        return GradeResult(False, "null", ["null side"])
    if str(pred).strip().lower() == str(truth).strip().lower():
        return GradeResult(True, "exact", ["raw strings equal"])
    p = normalize_answer(pred, do_strip_units=strip_units)
    t = normalize_answer(truth, do_strip_units=strip_units)
    trace.append(f"normalized: {p!r} vs {t!r}")
    if not p or not t:
        trace.append("empty after normalization")
        return GradeResult(False, "null", trace)
    ctx = _Ctx(
        raw_pred=str(pred), raw_truth=str(truth),
        rel_tol=rel_tol, strip_units=strip_units, trace=trace,
    )
    for name, fn in FAMILIES:
        verdict = fn(p, t, ctx)
        if verdict is not None:
            return GradeResult(bool(verdict), name, trace)
    return GradeResult(False, None, trace)


def answers_equal(
    pred: Optional[str],
    truth: Optional[str],
    rel_tol: float = 1e-4,
    strip_units: bool = True,
) -> bool:
    """Boolean view of :func:`grade_answer` — the training-reward hot path
    (no trace formatting cost beyond list appends)."""
    return grade_answer(
        pred, truth, rel_tol=rel_tol, strip_units=strip_units
    ).equal


__all__ = [
    "FAMILIES",
    "GradeResult",
    "answers_equal",
    "family_choice",
    "family_equation",
    "family_exact",
    "family_interval",
    "family_matrix",
    "family_numeric",
    "family_symbolic",
    "grade_answer",
    "normalize_answer",
    "numeric_value",
    "strip_units",
]
