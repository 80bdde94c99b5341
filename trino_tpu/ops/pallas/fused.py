"""Fused scan→filter→project→aggregate Pallas pipeline.

q01/q06-shaped fragments — a TableScan feeding a stack of Filters/Projects
feeding one Aggregate whose keys are small dictionary columns — are memory
bound: the sort-based path reads the scan columns from HBM once per
relational operator (filter mask, projected expressions, key sort, segment
reduce).  This kernel reads every referenced scan column from HBM exactly
once and does everything else in VMEM:

  * the compiler (exec/compiler.py) substitutes the filter predicates and
    aggregate arguments down to scan level (plan/ir.substitute), so the
    kernel receives the scan's columns plus a closed IR tree of FieldRef,
    Const, Param and Call;
  * layout: every column the recipe references is an operand of its own,
    the (n,) array as it lies in HBM, read in blocks of 8,192 rows (a grid
    step) and viewed a sub-chunk at a time as an (8, 128) tile — no pad, no
    stack, no plane written before the kernel.  What an operand is follows
    the dtype the column is RESIDENT in and nothing else (`_Planner`,
    `recipe.operands`): an int32 column — a date, an integer, a dictionary's
    codes, and every DECIMAL that data/page.py narrowed on upload, which is
    every TPC-H money column — is the resident array itself, and the kernel
    lifts a decimal to its double-float pair in registers (`_lift_i32`,
    exact for every int32); an int64 or floating column has no 64-bit lane
    to ride in, so its hi/lo f32 pair is split outside the kernel
    (`_dd_planes`) and goes in as two operands; a boolean, a validity mask,
    the page's live mask and a narrower integer go in as a cast to int32.
    A page without a live mask of its own hands over its row count and the
    kernel tells live rows by their index; the last block hangs over the
    arrays' end, and rows past n are dead by index too.  `operand_counts`
    (the dispatch detail's `operands <k> resident <r>`,
    `trino_tpu_fused_operands_total`) says how many operands a program's
    kernel takes and how many of them it reads in place.  One exception, the
    TPU compiler's: it lays a 1-D array of up to 512 elements out in smaller
    tiles than the kernel's blocks are read in, so a page that short is
    copied into one sub-chunk's length first;
  * a Param (a bound parameter of a prepared statement, plan/ir.py) is
    checked like a constant of its type and scale and reaches the kernel
    as a scalar operand in SMEM — an int32, or the hi/lo f32 pair made
    outside the kernel from the traced value as _dd_const makes a
    constant's — so every binding of one prepared statement runs one
    compiled kernel and a statement without parameters has the operands
    it always had.  No value is in the recipe, which is the kernel's cache
    key;
  * numeric values are computed as double-float pairs (hi = f32(v),
    lo = f32(v - f64(hi))): exact for |v| < 2^47, which covers the scaled
    decimals of the TPC-H fact columns; arithmetic uses the classic
    error-free transforms (Knuth two-sum, Dekker two-product with a 4097
    split), so products like extendedprice*(1-discount) stay exact per row;
  * grouping keys are dictionary codes combined into one mixed-radix code
    of domain D <= 512, and each (group, stream) pair is a sum in
    compensated f32 (an acc and an err that holds what acc's roundings
    lost, recombined in f64 outside the kernel).  How the masked streams of
    a 1,024-row sub-chunk reach their groups follows the shape of the
    recipe (`scatter_form`):
      - D x streams <= 448 (q01: 6 x 15; q06 and every keyless scan: D = 1):
        one (8, 128) f32 tile per pair, `where(code == g, stream, 0)` added
        into it lane by lane across a grid step's eight sub-chunks, then
        one compensated step per tile — D compares and D x streams
        select-adds a sub-chunk, no one-hot, no MXU.  The 1,024 lanes of a
        tile are summed in f64 afterwards, so an f32 partial is 8 rows;
      - beyond that, a one-hot MXU matmul as wide as D needs,
        128 * ceil(D / 128) lanes: stacked streams (8, NR, 128) x one-hot
        (8, 128, lanes) at HIGHEST contracted over lanes and summed over
        sublanes into an (NR, lanes) accumulator, one compensated step a
        sub-chunk.
    On a v5e at 60,000,466 rows (PERF.md, PR 34, over twelve stacked f32
    planes) a 1,024-row sub-chunk of q01 took 102 ns in the first form (6.0
    ms a scan) where the 512-lane one-hot of PRs 22-33 took 1,248 ns and the
    one-hot at 128 lanes takes ~600 ns (35 ms a scan, whether 3 or 15
    streams ride on it: building the one-hot and loading it into the MXU is
    the cost).  The first form grows by ~0.7 ns a pair: 240 pairs 12.9 ms a
    scan, 448 pairs 22.4 ms — the widest measured, still a third under the
    narrowest one-hot — so the rule keeps it to 448 pairs.  Over the seven
    resident int32 columns themselves (PERF.md, PR 36) q01 takes 6.8 ms a
    scan — 1.68 GB at 248 GB/s, bound by its schedule (the spills of its 90
    tiles, the lift, the ragged block's guards), not by its bytes — and q06
    2.5 ms (0.96 GB at 390 GB/s: four 32 KB blocks in flight a step); what
    went is everything that ran before the kernel, 26 ms a pair of scans.

Every aggregate lowers to a handful of f32 *streams* (per-row values summed
per group): count -> the row mask; sum -> the hi and lo parts (summed as
separate streams, recombined in f64); avg -> sum's streams plus a count.
Streams are deduplicated, so q01's six sums+avgs over four expressions cost
fifteen streams, not eighteen.

Accuracy: per-row expression math is exact; only the f32 summation inside a
partial rounds (compensated across partials), and that averages out as
1/sqrt(partials).  On the chip at SF10 the decimal sums of q01 and q06 read
2.2e-11 relative in the first form (8-row partials; 5.3e-10 with the
1,024-row partials of the 512-lane one-hot) and q01's AVGs 5.0e-9 in
either (4.7e-9): their last digits are lost outside the partial sums.
The pair the kernel lifts from a resident int32 is the pair the planes held
(hi = f32(x) to nearest, lo the rest), so reading the columns in place left
every answer as it was, to the last digit (2.220e-11 and 5.022e-9, PR 36).
Exactness-critical cases (BIGINT sum's mod-2^64 semantics) are rejected at
plan time and take the sort path.

Everything here runs under pallas interpret mode on CPU so tier-1
exercises the same code path as the TPU build.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

from ...plan.ir import Call, Const, FieldRef, IrExpr, Param
from . import hashagg as _hashagg

# rows stream in 8192-row grid steps of eight (8, 128) sub-chunks
_CHUNK_S = 8
_CHUNK_L = 128
_SUB_ROWS = _CHUNK_S * _CHUNK_L
_STEP_CHUNKS = 8
_STEP_ROWS = _SUB_ROWS * _STEP_CHUNKS

# the widest mixed-radix key-code domain the planner accepts: four lane
# tiles of one-hot, no table walk
_MAX_DOMAIN = 512
# (group, stream) pairs up to which the scatter is a select and an add per
# pair on the VPU; beyond, a one-hot matmul (scatter_form).  The widest the
# chip was timed at and found faster (module docstring); the kernel is
# unrolled, ~10,000 vector ops a grid step there
_VPU_PAIRS = 448
_MAX_STREAMS = 64
# the TPU compiler lays a 1-D array of up to 512 elements out in tiles of 128,
# 256 or 512, a longer one in tiles of 1,024 — the only ones Mosaic takes an
# (8192,) block over ("XLA layout ({0:T(128)}) does not match Mosaic layout
# ({0:T(1024)})").  A page that short is copied into one sub-chunk's length
_XLA_SMALL_ROWS = 512
# double-float pairs are exact only while the integer payload fits hi+lo
_DD_EXACT_BITS = 47

_AGG_WHITELIST = ("sum", "count", "count_star", "avg")


class _Unsupported(Exception):
    pass


# --------------------------------------------------------------- planning
#
# Static pass over the scan-level IR: decide every subexpression's kernel
# kind ("i32" | "dd" | "bool"), its decimal scale, and whether it can be
# NULL — rejecting anything the kernel can't evaluate exactly.  The same
# walk orders the input planes and deduplicates aggregate streams, so the
# result (a frozen _Recipe) is both the support proof and the kernel spec.


@dataclass(frozen=True)
class _Recipe:
    n_cols: int
    # col_idx -> ("i32" | "bool", operand, valid operand|-1, scale)
    #          | ("dd", hi operand, lo operand, valid operand|-1, scale): lo is
    #            -1 where the column is ONE int32 operand, lifted in the kernel
    #          | ("dict", operand)
    cols: tuple
    # the kernel's column operands, in its order: (what, col_idx), what one of
    # "data" (the resident array as it is: 32 bits wide already), "cast" (its
    # data as int32), "hi" / "lo" (the f32 pair of a 64-bit dd column, split
    # outside the kernel), "valid" (its validity mask as int32)
    operands: tuple
    filters: tuple  # IrExpr, scan-level
    keys: tuple     # (col_idx, domain, stride)
    domain: int
    streams: tuple  # ("rows", None) | ("cnt", e) | ("hi", e) | ("lo", e)
    aggs: tuple     # ("count", si) | ("sum", hi, lo, cnt, scale_shift, wide)
                    # | ("avg", hi, lo, cnt, scale_shift) | ("fsum", hi, lo, cnt)
                    # | ("favg", hi, lo, cnt)
    params: tuple = ()  # the distinct Param nodes, in the order `run` takes
                        # their values


def _kind_of_type(t) -> tuple[str, Optional[int]]:
    """Map a column/const Type to a kernel kind + decimal scale (None for
    floating point, 0 for integers/dates/bools)."""
    name = getattr(t, "name", "")
    if t.is_decimal:
        return "dd", t.scale
    if name in ("double", "real"):
        return "dd", None
    if name in ("integer", "date", "smallint", "tinyint"):
        return "i32", 0
    if name == "boolean":
        return "bool", 0
    raise _Unsupported(f"type {name}")


def _kind_of_param(t) -> tuple[str, Optional[int]]:
    """A parameter rides like a constant of its type.  An EXECUTE's integer
    literal is a BIGINT, which no column here may be (a BIGINT sum wraps mod
    2^64) but a scalar can: a whole number at scale 0."""
    if getattr(t, "name", "") == "bigint":
        return "dd", 0
    return _kind_of_type(t)


def _kind_of(e: IrExpr) -> tuple[str, Optional[int]]:
    return _kind_of_param(e.type) if isinstance(e, Param) else _kind_of_type(e.type)


def _param_fits(t) -> bool:
    """Whether the type alone bounds a parameter's payload to what a
    double-float pair holds exactly: the value is not there to look at."""
    kind, scale = _kind_of_param(t)
    if kind != "dd" or scale is None:
        return True  # an int32 scalar; a double is approximate by nature
    return t.is_decimal and 10 ** t.precision <= (1 << _DD_EXACT_BITS)


def _compared(e: Call) -> tuple[IrExpr, IrExpr, int, int]:
    """A comparison's operands as the kernel compares them, and the power
    of ten each is raised by first.  A decimal column beside a BIGINT
    parameter is planned as doubles (plan/planner.py _cmp: the value that
    would say how far the integer rescales is not there), and the kernel's
    cast to double multiplies by a rounded 10^-s, which can put 24.00 on
    either side of 24.  Both sides being exact numbers under their casts,
    comparing them at their common scale is what the doubles say, for every
    payload a double-float pair holds, and is the form a constant takes."""
    inner = []
    for a in e.args:
        if not (isinstance(a, Call) and a.op == "cast"
                and _kind_of_type(a.type) == ("dd", None)):
            return e.args[0], e.args[1], 0, 0
        try:
            kind, scale = _kind_of(a.args[0])
        except _Unsupported:
            return e.args[0], e.args[1], 0, 0
        if kind == "bool" or scale is None:
            return e.args[0], e.args[1], 0, 0
        inner.append((a.args[0], scale))
    (a, sa), (b, sb) = inner
    return a, b, max(sa, sb) - sa, max(sa, sb) - sb


def _scalar_slots(params) -> tuple[list, int, int]:
    """Where each parameter lies among the kernel's scalar operands:
    ("i", k) the k-th int32, ("f", k) the f32 pair at k and k + 1
    -> (slots, int32s in all, f32s in all)."""
    slots, n_i, n_f = [], 0, 0
    for prm in params:
        if _kind_of_param(prm.type)[0] == "dd":
            slots.append(("f", n_f))
            n_f += 2
        else:
            slots.append(("i", n_i))
            n_i += 1
    return slots, n_i, n_f


class _Planner:
    def __init__(self, cols):
        self.scan_cols = cols
        self.col_plan: dict[int, tuple] = {}
        self.operands: list = []
        self.streams: list = []
        self.stream_ix: dict = {}
        self.params: list = []

    def operand(self, what: str, i: int) -> int:
        self.operands.append((what, i))
        return len(self.operands) - 1

    def data_operand(self, i: int) -> int:
        """The column's data as one int32 operand: the resident array itself
        where that is what it holds, a cast of it (a narrower dictionary, a
        boolean, a smallint) where not."""
        resident = self.scan_cols[i].data.dtype == jnp.int32
        return self.operand("data" if resident else "cast", i)

    def use_col(self, i: int) -> tuple:
        got = self.col_plan.get(i)
        if got is not None:
            return got
        cv = self.scan_cols[i]
        if cv.data2 is not None:
            raise _Unsupported("decimal128 scan column")
        if cv.dict is not None:
            raise _Unsupported("dictionary column in expression")
        kind, scale = _kind_of_type(cv.type)
        vop = -1 if cv.valid is None else self.operand("valid", i)
        if kind != "dd":  # an integer or a date; a bool rides as int32 too
            plan = (kind, self.data_operand(i), vop, scale)
        elif cv.data.dtype == jnp.int32:
            # a narrowed decimal (data/page.py): the kernel lifts it to its
            # double-float pair in registers (_Eval._dd)
            plan = ("dd", self.data_operand(i), -1, vop, scale)
        else:  # int64 or a float: no 64-bit lanes in the kernel, so the pair
            # is split outside it (_dd_planes)
            plan = ("dd", self.operand("hi", i), self.operand("lo", i), vop, scale)
        self.col_plan[i] = plan
        return plan

    def use_key(self, i: int) -> int:
        cv = self.scan_cols[i]
        if cv.dict is None or cv.valid is not None:
            raise _Unsupported("group key must be a no-null dictionary column")
        got = self.col_plan.get(i)
        if got is not None:
            if got[0] != "dict":
                raise _Unsupported("key column also used as a value")
            return got[1]
        plan = ("dict", self.data_operand(i))
        self.col_plan[i] = plan
        return plan[1]

    # ---- static type/nullability check: returns (kind, scale, nullable)

    def check(self, e: IrExpr, compared: bool = False) -> tuple[str, Optional[int], bool]:
        """`compared`: `e` is an operand of a comparison, directly or under
        casts.  Only there may a parameter be wider than a double-float pair
        holds exactly: the pair of any int64 still orders right against the
        exact pairs of the columns (rounding is monotone), while a sum or a
        product over it would round in silence."""
        if isinstance(e, FieldRef):
            plan = self.use_col(e.index)
            cv = self.scan_cols[e.index]
            kind, scale = _kind_of_type(cv.type)
            return kind, scale, cv.valid is not None
        if isinstance(e, Const):
            kind, scale = _kind_of_type(e.type)
            if e.value is None:
                return kind, scale, True
            if kind == "dd" and scale is not None and abs(int(e.value)) >= (1 << _DD_EXACT_BITS):
                raise _Unsupported("decimal constant too wide")
            return kind, scale, False
        if isinstance(e, Param):  # bound, so never NULL (a NULL is baked)
            kind, scale = _kind_of_param(e.type)
            if not (compared or _param_fits(e.type)):
                raise _Unsupported("parameter too wide for arithmetic")
            if e not in self.params:
                self.params.append(e)
            return kind, scale, False
        if isinstance(e, Call):
            return self._check_call(e, compared)
        raise _Unsupported(f"expression {type(e).__name__}")

    def _check_call(self, e: Call, compared: bool = False):
        op = e.op
        if op in ("add", "sub", "mul", "neg"):
            sub = [self.check(a) for a in e.args]
            if any(k == "bool" for k, _, _ in sub):
                raise _Unsupported(f"{op} over boolean")
            scales = [s for _, s, _ in sub]
            if any(s is None for s in scales) != all(s is None for s in scales):
                raise _Unsupported("mixed decimal/double arithmetic")
            nullable = any(nl for _, _, nl in sub)
            okind, oscale = _kind_of_type(e.type)
            if okind != "dd":
                raise _Unsupported(f"integer {op}")
            if oscale is not None:
                if op == "mul":
                    if oscale != scales[0] + scales[1]:
                        raise _Unsupported("mul rescale")
                elif op == "neg":
                    if oscale != scales[0]:
                        raise _Unsupported("neg rescale")
                elif oscale != scales[0] or scales[0] != scales[1]:
                    raise _Unsupported(f"{op} operand scales differ")
            return "dd", oscale, nullable
        if op in ("eq", "ne", "lt", "le", "gt", "ge"):
            a, b, up_a, up_b = _compared(e)
            (k1, s1, n1), (k2, s2, n2) = self.check(a, True), self.check(b, True)
            if "bool" in (k1, k2):
                raise _Unsupported("comparison over boolean")
            if (s1 is None) != (s2 is None) or (
                s1 is not None and s1 + up_a != s2 + up_b
            ):
                raise _Unsupported("comparison operand scales differ")
            return "bool", 0, n1 or n2
        if op in ("and", "or"):
            subs = [self.check(a) for a in e.args]
            if any(k != "bool" for k, _, _ in subs):
                raise _Unsupported(f"{op} over non-boolean")
            return "bool", 0, any(nl for _, _, nl in subs)
        if op == "not":
            k, _, nl = self.check(e.args[0])
            if k != "bool":
                raise _Unsupported("not over non-boolean")
            return "bool", 0, nl
        if op == "is_null":
            self.check(e.args[0])
            return "bool", 0, False
        if op == "cast":
            k, s, nl = self.check(e.args[0], compared)
            okind, oscale = _kind_of_type(e.type)
            if okind != "dd":
                raise _Unsupported(f"cast to {e.type}")
            if k == "bool":
                raise _Unsupported("cast from boolean")
            if oscale is None:  # -> double: any numeric source works
                return "dd", None, nl
            if k == "i32":
                return "dd", oscale, nl
            if s is None or oscale < s:
                raise _Unsupported("narrowing or float->decimal cast")
            return "dd", oscale, nl
        raise _Unsupported(f"op {op}")

    # ---- stream dedup

    def stream(self, tag: str, e: Optional[IrExpr]) -> int:
        key = (tag, e)
        got = self.stream_ix.get(key)
        if got is not None:
            return got
        ix = len(self.streams)
        if ix >= _MAX_STREAMS:
            raise _Unsupported("too many aggregate streams")
        self.streams.append((tag, e))
        self.stream_ix[key] = ix
        return ix


def plan_pipeline(scan_cols, filters, key_exprs, agg_fns, agg_args, agg_types):
    """Try to compile the fused pipeline.  Returns (recipe, "") on success or
    (None, reason) when any piece falls outside the kernel's reach —
    the caller then runs the regular operator-at-a-time path."""
    p = _Planner(scan_cols)
    try:
        for f in filters:
            k, _, _ = p.check(f)
            if k != "bool":
                raise _Unsupported("non-boolean filter")
        keys = []
        domain = 1
        for ke in key_exprs:
            if not isinstance(ke, FieldRef):
                raise _Unsupported("computed group key")
            plane = p.use_key(ke.index)
            d = len(p.scan_cols[ke.index].dict)
            keys.append((ke.index, d))
            domain *= max(d, 1)
        if domain > _MAX_DOMAIN:
            raise _Unsupported(f"key domain {domain} > {_MAX_DOMAIN}")
        rows_s = p.stream("rows", None)
        aggs = []
        for fn, arg, otype in zip(agg_fns, agg_args, agg_types):
            if fn not in _AGG_WHITELIST:
                raise _Unsupported(f"agg {fn}")
            if fn == "count_star":
                aggs.append(("count", rows_s))
                continue
            kind, scale, nullable = p.check(arg)
            if kind == "bool":
                raise _Unsupported(f"{fn} over boolean")
            cnt_s = rows_s if not nullable else p.stream("cnt", arg)
            if fn == "count":
                aggs.append(("count", cnt_s))
                continue
            hi_s = p.stream("hi", arg)
            lo_s = p.stream("lo", arg)
            okind, oscale = _kind_of_type(otype)
            if okind != "dd":
                raise _Unsupported(f"{fn} result {otype}")
            if scale is None:  # floating point in
                if oscale is not None:
                    raise _Unsupported(f"float {fn} with decimal result")
                aggs.append((("fsum" if fn == "sum" else "favg"), hi_s, lo_s, cnt_s))
                continue
            if oscale is None or oscale < scale:
                raise _Unsupported(f"{fn} result rescale")
            shift = oscale - scale
            if fn == "sum":
                wide = bool(getattr(otype, "precision", 18) > 18)
                aggs.append(("sum", hi_s, lo_s, cnt_s, shift, wide))
            else:
                aggs.append(("avg", hi_s, lo_s, cnt_s, shift))
    except _Unsupported as ex:
        return None, str(ex)
    # mixed-radix strides, first key most significant (matches nested order)
    strides = []
    acc = 1
    for _, d in reversed(keys):
        strides.append(acc)
        acc *= max(d, 1)
    strides.reverse()
    recipe = _Recipe(
        n_cols=len(scan_cols),
        cols=tuple(sorted((i, plan) for i, plan in p.col_plan.items())),
        operands=tuple(p.operands),
        filters=tuple(filters),
        keys=tuple((i, d, s) for (i, d), s in zip(keys, strides)),
        domain=domain,
        streams=tuple(p.streams),
        aggs=tuple(aggs),
        params=tuple(p.params),
    )
    return recipe, ""


# ------------------------------------------------------- in-kernel evaluator
#
# Double-float (f32 pair) error-free transforms.  All classic: Knuth
# two-sum, Dekker split/two-product.  Exact per-row for payloads < 2^47.


def _two_sum(a, b):
    s = a + b
    bb = s - a
    return s, (a - (s - bb)) + (b - bb)


def _split(a):
    c = a * jnp.float32(4097.0)  # 2^12 + 1
    hi = c - (c - a)
    return hi, a - hi


def _two_prod(a, b):
    p = a * b
    ah, al = _split(a)
    bh, bl = _split(b)
    return p, ((ah * bh - p) + ah * bl + al * bh) + al * bl


def _dd_add(x, y):
    s, e = _two_sum(x[0], y[0])
    e = e + x[1] + y[1]
    return _two_sum(s, e)


def _dd_neg(x):
    return (-x[0], -x[1])


def _dd_mul(x, y):
    p, e = _two_prod(x[0], y[0])
    e = e + x[0] * y[1] + x[1] * y[0]
    return _two_sum(p, e)


def _dd_lt(x, y):
    return (x[0] < y[0]) | ((x[0] == y[0]) & (x[1] < y[1]))


def _dd_eq(x, y):
    return (x[0] == y[0]) & (x[1] == y[1])


def _lift_i32(x):
    """An int32 tile as its double-float pair (hi = f32(x) rounded to nearest,
    lo = x - hi), exact for EVERY int32 and the pair _dd_planes makes of the
    same integer.  The top 24 bits and the low 8 are each exact in f32 and
    add up to x; |top| >= 256 > low unless top is 0, so Dekker's fast two-sum
    takes their rounded sum and what the rounding lost.  No int32 is made of
    hi, which is 2^31 for x > 2^31 - 64."""
    top = (x & jnp.int32(-256)).astype(jnp.float32)
    low = (x & jnp.int32(255)).astype(jnp.float32)
    hi = top + low
    return hi, low - (hi - top)


def _dd_const(v: float):
    import numpy as np

    hi = np.float32(v)
    lo = np.float32(float(v) - float(hi))
    return jnp.float32(hi), jnp.float32(lo)


class _Eval:
    """Evaluates the closed IR over one (8, 128) sub-chunk.  Values are
    (kind, payload..., valid) with valid None when statically non-null."""

    def __init__(self, recipe, planes, shape, scalars=()):
        self.col_plan = dict(recipe.cols)
        # an (8, 128) tile per operand of recipe.operands: int32, but f32
        # for the "hi" / "lo" pair of a 64-bit dd column
        self.planes = planes
        self.shape = shape
        # Param -> its scalar(s) read from SMEM: an int32, or (hi, lo) f32
        self.scalars = dict(zip(recipe.params, scalars))
        self.memo: dict = {}

    def _valid(self, vplane):
        return None if vplane < 0 else (self.planes[vplane] > 0)

    def ev(self, e: IrExpr):
        got = self.memo.get(e)
        if got is None:
            got = self._ev(e)
            self.memo[e] = got
        return got

    def _ev(self, e: IrExpr):
        if isinstance(e, FieldRef):
            plan = self.col_plan[e.index]
            if plan[0] == "dd":
                _, hi, lo, vp, _ = plan
                if lo < 0:  # resident as int32: lifted here, in registers
                    return ("dd", _lift_i32(self.planes[hi]), self._valid(vp))
                return ("dd", (self.planes[hi], self.planes[lo]), self._valid(vp))
            if plan[0] == "i32":
                _, p, vp, _ = plan
                return ("i32", self.planes[p], self._valid(vp))
            _, p, vp, _ = plan
            return ("bool", self.planes[p] > 0, self._valid(vp))
        if isinstance(e, Const):
            kind, scale = _kind_of_type(e.type)
            if e.value is None:
                zero = jnp.zeros(self.shape, jnp.float32)
                dead = jnp.zeros(self.shape, jnp.bool_)
                if kind == "dd":
                    return ("dd", (zero, zero), dead)
                if kind == "bool":
                    return ("bool", dead, dead)
                return ("i32", jnp.zeros(self.shape, jnp.int32), dead)
            if kind == "dd":
                hi, lo = _dd_const(float(e.value) if scale is None else int(e.value))
                full = jnp.full(self.shape, 1.0, jnp.float32)
                return ("dd", (hi * full, lo * full), None)
            if kind == "bool":
                return ("bool", jnp.full(self.shape, bool(e.value)), None)
            return ("i32", jnp.full(self.shape, int(e.value), jnp.int32), None)
        if isinstance(e, Param):  # a constant's forms, the value a scalar
            kind, _ = _kind_of_param(e.type)
            v = self.scalars[e]
            if kind == "dd":
                full = jnp.full(self.shape, 1.0, jnp.float32)
                return ("dd", (v[0] * full, v[1] * full), None)
            if kind == "bool":
                return ("bool", jnp.full(self.shape, v, jnp.int32) > 0, None)
            return ("i32", jnp.full(self.shape, v, jnp.int32), None)
        assert isinstance(e, Call)
        return self._call(e)

    def _dd(self, v):
        """Lift a value to dd."""
        if v[0] == "dd":
            return v[1], v[2]
        return _lift_i32(v[1]), v[2]

    def _call(self, e: Call):
        op = e.op
        if op in ("add", "sub", "mul", "neg"):
            parts = [self._dd(self.ev(a)) for a in e.args]
            valid = None
            for _, vl in parts:
                valid = vl if valid is None else (valid if vl is None else valid & vl)
            if op == "neg":
                return ("dd", _dd_neg(parts[0][0]), parts[0][1])
            x, y = parts[0][0], parts[1][0]
            if op == "add":
                return ("dd", _dd_add(x, y), valid)
            if op == "sub":
                return ("dd", _dd_add(x, _dd_neg(y)), valid)
            return ("dd", _dd_mul(x, y), valid)
        if op in ("eq", "ne", "lt", "le", "gt", "ge"):
            a, b, up_a, up_b = _compared(e)
            a, b = self.ev(a), self.ev(b)
            if a[0] == "i32" and b[0] == "i32" and not (up_a or up_b):
                x, y = a[1], b[1]
                data = {
                    "eq": x == y, "ne": x != y, "lt": x < y,
                    "le": x <= y, "gt": x > y, "ge": x >= y,
                }[op]
            else:
                (x, vx), (y, vy) = self._dd(a), self._dd(b)
                if up_a:
                    x = _dd_mul(x, _dd_const(10 ** up_a))
                if up_b:
                    y = _dd_mul(y, _dd_const(10 ** up_b))
                if op == "eq":
                    data = _dd_eq(x, y)
                elif op == "ne":
                    data = ~_dd_eq(x, y)
                elif op == "lt":
                    data = _dd_lt(x, y)
                elif op == "le":
                    data = ~_dd_lt(y, x)
                elif op == "gt":
                    data = _dd_lt(y, x)
                else:
                    data = ~_dd_lt(x, y)
            valid = _and_opt(a[-1], b[-1])
            return ("bool", data, valid)
        if op in ("and", "or"):
            vals = [self.ev(a) for a in e.args]
            data, valid = vals[0][1], vals[0][2]
            for v in vals[1:]:
                data, valid = _kleene(op, data, valid, v[1], v[2])
            return ("bool", data, valid)
        if op == "not":
            v = self.ev(e.args[0])
            return ("bool", ~v[1], v[2])
        if op == "is_null":
            v = self.ev(e.args[0])
            if v[-1] is None:
                return ("bool", jnp.zeros(self.shape, jnp.bool_), None)
            return ("bool", ~v[-1], None)
        if op == "cast":
            v = self.ev(e.args[0])
            s = _kind_of(e.args[0])[1]
            oscale = _kind_of_type(e.type)[1]
            (x, _), valid = self._dd(v), v[-1]
            if oscale is None:
                # -> double: divide out the source's decimal scale
                if s:
                    x = _dd_mul(x, _dd_const(10.0 ** -s))
            else:
                shift = oscale - (s if s is not None else oscale)
                if shift:
                    x = _dd_mul(x, _dd_const(10 ** shift))
            return ("dd", x, valid)
        raise AssertionError(op)  # plan_pipeline vetted the tree

    def pred(self, e: IrExpr):
        """NULL -> row fails (FilterAndProject semantics)."""
        v = self.ev(e)
        m = v[1]
        if v[2] is not None:
            m = m & v[2]
        return m

    def masked_stream(self, tag, e, mask):
        one = jnp.float32(1.0)
        zero = jnp.float32(0.0)
        if tag == "rows":
            return jnp.where(mask, one, zero)
        v = self.ev(e)
        ok = mask if v[-1] is None else (mask & v[-1])
        if tag == "cnt":
            return jnp.where(ok, one, zero)
        (hi, lo), _ = self._dd(v)
        return jnp.where(ok, hi if tag == "hi" else lo, zero)


def _and_opt(a, b):
    if a is None:
        return b
    if b is None:
        return a
    return a & b


def _kleene(op, d1, v1, d2, v2):
    """SQL three-valued AND/OR over (data, valid) pairs."""
    t1 = d1 if v1 is None else (d1 & v1)
    t2 = d2 if v2 is None else (d2 & v2)
    f1 = ~d1 if v1 is None else (~d1 & v1)
    f2 = ~d2 if v2 is None else (~d2 & v2)
    if op == "and":
        data = t1 & t2
        known = (f1 | f2) | data
    else:
        data = t1 | t2
        known = (f1 & f2) | data
    return data, (None if (v1 is None and v2 is None) else known)


# -------------------------------------------------------------- the kernel


def _accumulate(acc, err, part):
    """One step of the running sum over a table's partials (Neumaier's
    compensated sum, the rounding taken branch-free by Knuth's two-sum):
    -> (acc + part rounded to f32, err + what that rounding lost).  The loss
    of one f32 addition is itself an f32, so acc + err stays the exact sum
    for as long as err's own additions are exact — for integer streams
    (counts, whole hundredths) while |err| < 2^24, which a 60M-row table's
    at most ~58,600 steps of half an ulp of acc or less each keep far away."""
    t, lost = _two_sum(acc, part)
    return t, err + lost


def scatter_form(recipe: _Recipe) -> tuple[str, int]:
    """How the kernel puts a sub-chunk's masked streams into their groups,
    and the lane width of the accumulator that takes them: ("vpu", 128) —
    one (8, 128) tile per (group, stream), a select and an add each — while
    there are at most _VPU_PAIRS such tiles, else ("mxu", lanes) — a one-hot
    matmul over as many lane tiles as the key domain needs."""
    if recipe.domain * len(recipe.streams) <= _VPU_PAIRS:
        return "vpu", _CHUNK_L
    return "mxu", _CHUNK_L * -(-recipe.domain // _CHUNK_L)


def _acc_shape(recipe: _Recipe) -> tuple:
    form, dtile = scatter_form(recipe)
    nr = len(recipe.streams)
    if form == "vpu":
        return (recipe.domain * nr, _CHUNK_S, _CHUNK_L)
    return (nr, dtile)


def operand_counts(recipe: _Recipe, has_live: bool) -> tuple[int, int]:
    """-> (row operands the kernel takes — columns, validity masks and the
    page's live mask where it has one —, how many of them are resident
    arrays handed over as they are: no cast, no split, no copy)."""
    resident = sum(what == "data" for what, _ in recipe.operands)
    return len(recipe.operands) + bool(has_live), resident


@functools.lru_cache(maxsize=64)
def _fused_kernel(recipe: _Recipe, n: int, has_live: bool, interpret: bool):
    """The scan of `n` rows: an (n,) operand per entry of recipe.operands,
    then the live mask if the page has one, then the parameters' scalars."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    nr = len(recipe.streams)
    domain = recipe.domain
    key_planes = {i: dict(recipe.cols)[i][1] for i, _, _ in recipe.keys}
    form, dtile = scatter_form(recipe)
    acc_shape = _acc_shape(recipe)
    n_rows = len(recipe.operands) + has_live
    tile = (_CHUNK_S, _CHUNK_L)

    # an operand is there only if some parameter needs it, so a statement
    # without parameters has its row operands alone
    slots, n_pi, n_pf = _scalar_slots(recipe.params)
    scalar_shapes = [(1, k) for k in (n_pi, n_pf) if k]

    def read_scalars(refs):
        refs = list(refs)
        pi_ref = refs.pop(0) if n_pi else None
        pf_ref = refs.pop(0) if n_pf else None
        return [
            (pf_ref[0, k], pf_ref[0, k + 1]) if where == "f" else pi_ref[0, k]
            for where, k in slots
        ]

    def sub_chunk(row_refs, c, scalars):
        """-> (key code | None, masked streams) of a step's c-th sub-chunk."""
        at = pl.ds(c * _SUB_ROWS, _SUB_ROWS)
        planes = [r[at].reshape(tile) for r in row_refs]
        mask = planes.pop() > 0 if has_live else None
        if n % _STEP_ROWS:
            # the last step's blocks hang over the end of the arrays, and
            # what they hold past row n is not data: live by index alone
            row = (
                jax.lax.broadcasted_iota(jnp.int32, tile, 0) * jnp.int32(_CHUNK_L)
                + jax.lax.broadcasted_iota(jnp.int32, tile, 1)
                + jnp.int32(c * _SUB_ROWS)
            )
            inside = row < jnp.int32(n) - pl.program_id(0) * jnp.int32(_STEP_ROWS)
            mask = inside if mask is None else mask & inside
        if mask is None:
            mask = jnp.full(tile, True)
        ev = _Eval(recipe, planes, tile, scalars)
        for f in recipe.filters:
            mask = mask & ev.pred(f)
        code = None
        for ci, _, stride in recipe.keys:
            term = planes[key_planes[ci]] * jnp.int32(stride)
            code = term if code is None else code + term
        return code, [ev.masked_stream(tag, e, mask) for tag, e in recipe.streams]

    def vpu_step(row_refs, scalars, acc, err):
        # tile g * nr + s sums stream s over the rows of group g, lane by
        # lane: a step adds 8 rows to each of a tile's 1,024 lanes, and the
        # lanes are summed in f64 outside the kernel (_totals).  One group
        # (no keys, or a dictionary of one value) needs no compare.
        part = [None] * (domain * nr)
        for c in range(_STEP_CHUNKS):
            code, streams = sub_chunk(row_refs, c, scalars)
            for g in range(domain):
                hit = None if domain == 1 else code == jnp.int32(g)
                for s, x in enumerate(streams):
                    v = x if hit is None else jnp.where(hit, x, jnp.float32(0.0))
                    k = g * nr + s
                    part[k] = v if part[k] is None else part[k] + v
        for k, p in enumerate(part):
            acc[k], err[k] = _accumulate(acc[k], err[k], p)

    def mxu_step(row_refs, scalars, acc, err):
        lane = jax.lax.broadcasted_iota(
            jnp.int32, (_CHUNK_S, _CHUNK_L, dtile), 2
        )
        for c in range(_STEP_CHUNKS):
            code, streams = sub_chunk(row_refs, c, scalars)
            upd = jnp.stack(streams, axis=1)  # (8, NR, 128)
            oh = (code[:, :, None] == lane).astype(jnp.float32)
            part = jax.lax.dot_general(
                upd, oh,
                (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGHEST,
            ).sum(axis=0)  # (NR, dtile)
            acc[...], err[...] = _accumulate(acc[...], err[...], part)

    def kernel(*refs):
        # every grid step maps to the one output block, so it stays in VMEM
        # for the whole table and is the accumulator: out[0] the running
        # sums, out[1] what their roundings lost
        row_refs, scalar_refs, out_ref = refs[:n_rows], refs[n_rows:-1], refs[-1]

        @pl.when(pl.program_id(0) == 0)
        def _init():
            out_ref[...] = jnp.zeros((2,) + acc_shape, jnp.float32)

        step = vpu_step if form == "vpu" else mxu_step
        step(row_refs, read_scalars(scalar_refs), out_ref.at[0], out_ref.at[1])

    vmem = pltpu.VMEM
    origin = (0,) * (1 + len(acc_shape))
    return pl.pallas_call(
        kernel,
        grid=(-(-n // _STEP_ROWS),),
        # the arrays as they lie in HBM, a step's 8,192 rows of each a block:
        # nothing is padded, so the last block is ragged (sub_chunk)
        in_specs=[
            pl.BlockSpec((_STEP_ROWS,), lambda i: (i,), memory_space=vmem)
        ] * n_rows + [
            pl.BlockSpec(shape, lambda i: (0, 0), memory_space=pltpu.SMEM)
            for shape in scalar_shapes
        ],
        out_specs=pl.BlockSpec(
            (2,) + acc_shape, lambda i: origin, memory_space=vmem
        ),
        out_shape=jax.ShapeDtypeStruct((2,) + acc_shape, jnp.float32),
        interpret=interpret,
        name="fused_scan",
    )


# ------------------------------------------------------------ host driver


def _dd_planes(data):
    v = data.astype(jnp.float64)
    hi = v.astype(jnp.float32)
    lo = (v - hi.astype(jnp.float64)).astype(jnp.float32)
    return hi, lo


def run(recipe: _Recipe, scan_cols, live, *, params=(), interpret: bool = False):
    """Execute the fused pipeline.  `live`: the page's live mask, or — an
    int — the row count of a page that has none (every row live: the kernel
    then takes no mask and tells rows by their index).  `params`: the value
    of each of `recipe.params` as a scalar of its SQL type's dtype, traced
    or concrete.

    Returns (totals f64 (NR, D), n_groups int array) — per-stream per-group
    sums; the caller assembles aggregate columns via `assemble`."""
    interpret = bool(interpret or _hashagg.INTERPRET)
    has_live = not isinstance(live, int)
    n = live.shape[0] if has_live else live
    if n == 0:  # no row, no block to read
        return _totals(recipe, jnp.zeros((2,) + _acc_shape(recipe), jnp.float32))

    # what is not resident in the form the kernel reads — a cast, a 64-bit
    # column's split — is device work of its own, told apart in a trace by
    # this scope; a resident int32 column goes to the kernel as it is
    with jax.named_scope("fused_scan_prep"):
        split = functools.cache(lambda ci: _dd_planes(scan_cols[ci].data))
        rows = []
        for what, ci in recipe.operands:
            cv = scan_cols[ci]
            if what == "data":
                rows.append(cv.data)
            elif what == "cast":
                rows.append(cv.data.astype(jnp.int32))
            elif what == "valid":
                rows.append(cv.valid.astype(jnp.int32))
            else:  # "hi" | "lo"
                rows.append(split(ci)[what == "lo"])
        if has_live:
            rows.append(live.astype(jnp.int32))
        if n <= _XLA_SMALL_ROWS:
            rows = [
                jnp.concatenate([r, jnp.zeros((_SUB_ROWS - n,), r.dtype)])
                for r in rows
            ]
        pi, pf = [], []
        slots, _, _ = _scalar_slots(recipe.params)
        for (where, _k), v in zip(slots, params, strict=True):
            if where == "f":
                pf.extend(_dd_planes(jnp.asarray(v)))  # as _dd_const's pair
            else:
                pi.append(jnp.asarray(v).astype(jnp.int32))
        scalars = [jnp.stack(p).reshape(1, -1) for p in (pi, pf) if p]

    call = _fused_kernel(recipe, n, has_live, interpret)
    with jax.enable_x64(False):
        out = call(*rows, *scalars)
    return _totals(recipe, out)


def _totals(recipe: _Recipe, out):
    """The kernel's (acc, err) pair recombined in f64 -> (NR, D)."""
    tot = out[0].astype(jnp.float64) + out[1].astype(jnp.float64)
    if scatter_form(recipe)[0] == "vpu":
        nr = len(recipe.streams)
        return tot.reshape(recipe.domain, nr, -1).sum(axis=2).T
    return tot[:, : recipe.domain]


def assemble(recipe: _Recipe, totals):
    """Turn raw stream totals into aggregate output columns.

    Returns (key_codes list of (D,) int32, agg_cols list of tuples shaped
    like relops group_aggregate outputs — (data, valid) or the decimal128
    4-tuple (lo, valid, None, hi) — out_live (D,) bool, n_groups)."""
    D = recipe.domain
    rows_ix = 0  # stream 0 is always the row-mask stream
    for ix, (tag, _) in enumerate(recipe.streams):
        if tag == "rows":
            rows_ix = ix
            break
    rows = jnp.round(totals[rows_ix]).astype(jnp.int64)
    if recipe.keys:
        out_live = rows > 0
        n_groups = jnp.sum(out_live.astype(jnp.int64))
    else:
        out_live = jnp.ones((1,), jnp.bool_)
        n_groups = jnp.ones((), jnp.int64)

    key_codes = []
    lanes = jnp.arange(D, dtype=jnp.int32)
    for _, d, stride in recipe.keys:
        key_codes.append((lanes // jnp.int32(stride)) % jnp.int32(max(d, 1)))

    agg_cols = []
    for spec in recipe.aggs:
        if spec[0] == "count":
            cnt = jnp.round(totals[spec[1]]).astype(jnp.int64)
            agg_cols.append((cnt, None))
            continue
        if spec[0] in ("fsum", "favg"):
            _, hi_s, lo_s, cnt_s = spec
            tot = totals[hi_s] + totals[lo_s]
            cnt = jnp.round(totals[cnt_s])
            valid = cnt > 0
            if spec[0] == "favg":
                data = tot / jnp.maximum(cnt, 1.0)
            else:
                data = tot
            agg_cols.append((data, valid))
            continue
        if spec[0] == "sum":
            _, hi_s, lo_s, cnt_s, shift, wide = spec
            tot = (totals[hi_s] + totals[lo_s]) * float(10 ** shift)
            cnt = jnp.round(totals[cnt_s])
            valid = cnt > 0
            lo = jnp.round(tot).astype(jnp.int64)
            if wide:
                agg_cols.append((lo, valid, None, lo >> jnp.int64(63)))
            else:
                agg_cols.append((lo, valid))
            continue
        _, hi_s, lo_s, cnt_s, shift = spec
        cnt = jnp.round(totals[cnt_s])
        valid = cnt > 0
        tot = (totals[hi_s] + totals[lo_s]) * float(10 ** shift)
        data = jnp.round(tot / jnp.maximum(cnt, 1.0)).astype(jnp.int64)
        agg_cols.append((data, valid))
    return key_codes, agg_cols, out_live, n_groups
