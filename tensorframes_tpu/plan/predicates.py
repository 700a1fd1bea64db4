"""Filter-predicate atom extraction for parquet row-group pushdown.

A filter computation is an opaque traced JAX program; this module
recognizes the narrow, useful shape — conjunctions of single-column
comparisons against literals (``lambda x: x > 3``, ``lambda x, y:
(x > 3) & (y <= 0)``) — by walking the predicate's jaxpr. Anything it
does not PROVE is such a comparison yields no atoms, and the scan reads
everything (pushdown is an optimization, never a semantics change).

Refutation (:func:`refutes`) is evaluated against row-group footer
min/max statistics in the column's DEVICE dtype: casting is monotone
but can round a host value ONTO the literal, so strict and non-strict
comparisons use different boundary rules — a skipped row group must be
one where the predicate is false for EVERY row as the device would
evaluate it. Rows whose value is NaN compare false under every
supported operator, so float stats (which exclude NaN) stay sound.
"""

from __future__ import annotations

from typing import List, NamedTuple, Optional

import numpy as np

from ..utils.logging import get_logger
from ..utils.tracing import counters

__all__ = ["Atom", "extract_atoms", "refutes"]

_log = get_logger("plan.predicates")

_CMP = {"gt": "gt", "lt": "lt", "ge": "ge", "le": "le", "eq": "eq"}


class Atom(NamedTuple):
    """One conjunct: ``column <op> value`` (op in gt/lt/ge/le/eq)."""

    column: str
    op: str
    value: float


def _value_preserving(src_dt, dst_dt) -> bool:
    """True when casting ``src_dt -> dst_dt`` provably changes no value
    the refutation could see: bool widening, same-kind int widening,
    f32->f64, small-int->f32, and any-int->f64 (``refutes`` bails
    beyond 2**53 for integer columns, inside which f64 is exact)."""
    try:
        s, d = np.dtype(src_dt), np.dtype(dst_dt)
    except (TypeError, ValueError):
        return False
    if s == d:
        return True
    if s.kind == "b":
        return d.kind in "biuf"
    if s.kind in "iu" and d.kind in "iu":
        return d.kind == s.kind and d.itemsize >= s.itemsize
    if s.kind in "iu" and d.kind == "f":
        if d.itemsize >= 8:
            return True  # exact under the 2**53 bail in refutes()
        return s.itemsize <= 2  # i8/i16/u8/u16 fit f32's mantissa
    if s.kind == "f" and d.kind == "f":
        return d.itemsize >= s.itemsize
    return False


def _literal_scalar(v) -> Optional[float]:
    try:
        a = np.asarray(v)
    except Exception:
        return None
    if a.ndim == 0:
        return float(a)
    return None


def extract_atoms(comp) -> List[Atom]:
    """Conjunctive ``column <op> literal`` atoms of a filter predicate,
    ``[]`` when the shape is not provably that (cached on the comp)."""
    cached = getattr(comp, "_tft_pred_atoms", None)
    if cached is not None:
        return list(cached)
    atoms: List[Atom] = []
    try:
        atoms = _extract(comp)
    except Exception as e:  # noqa: BLE001 - unextractable means unpushed
        # counted: a jax API drift that breaks extraction must fail a
        # test (tests/test_plan.py), not quietly switch pushdown off
        counters.inc("plan.predicate_extract_failures")
        _log.debug("predicate extraction failed (%s: %s); no pushdown",
                   type(e).__name__, e)
        atoms = []
    try:
        comp._tft_pred_atoms = tuple(atoms)
    except Exception as e:
        _log.debug("could not cache atoms on %r: %s", comp, e)
    return atoms


def _extract(comp) -> List[Atom]:
    import jax
    from jax.extend.core import Literal

    from .. import dtypes as _dt

    avals = {s.name: jax.ShapeDtypeStruct(
        tuple(2 if d == -1 else d for d in s.shape.dims),
        _dt.device_dtype(s.dtype)) for s in comp.inputs}
    closed = jax.make_jaxpr(comp.fn)(avals)
    jaxpr = closed.jaxpr
    consts = dict(zip(jaxpr.constvars, closed.consts))
    # var -> source column name (identity-preserving unary ops only)
    src = {}
    flat_in = jaxpr.invars
    # comp.fn takes a dict: jax flattens it sorted by key
    for v, name in zip(flat_in, sorted(avals)):
        src[v] = ("col", name)

    def resolve(v):
        if isinstance(v, Literal):
            lit = _literal_scalar(v.val)
            return ("lit", lit) if lit is not None else None
        if v in consts:
            lit = _literal_scalar(consts[v])
            return ("lit", lit) if lit is not None else None
        return src.get(v)

    # var -> list of atoms it PROVABLY equals (a boolean vector)
    bools = {}
    _FLIP = {"gt": "lt", "lt": "gt", "ge": "le", "le": "ge", "eq": "eq"}
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        if prim in ("convert_element_type", "copy"):
            s = resolve(eqn.invars[0])
            if s is not None and (
                    prim == "copy"
                    or _value_preserving(
                        getattr(eqn.invars[0].aval, "dtype", None),
                        getattr(eqn.outvars[0].aval, "dtype", None))):
                # only VALUE-PRESERVING casts keep column identity: a
                # truncating/narrowing cast (float->int, f64->f32)
                # changes what the device compares, so an atom over the
                # raw column would refute groups whose rows match
                src[eqn.outvars[0]] = s
            if eqn.invars[0] in bools:
                bools[eqn.outvars[0]] = bools[eqn.invars[0]]
            continue
        if prim in _CMP:
            a = resolve(eqn.invars[0])
            b = resolve(eqn.invars[1])
            if a and b and a[0] == "col" and b[0] == "lit":
                bools[eqn.outvars[0]] = [Atom(a[1], prim, b[1])]
            elif a and b and a[0] == "lit" and b[0] == "col":
                bools[eqn.outvars[0]] = [Atom(b[1], _FLIP[prim], a[1])]
            continue
        if prim == "and":
            a = bools.get(eqn.invars[0])
            b = bools.get(eqn.invars[1])
            if a is not None and b is not None:
                bools[eqn.outvars[0]] = a + b
            continue
        # any other primitive producing the eventual output breaks the
        # proof chain for its result; harmless intermediates are fine
    out = jaxpr.outvars
    if len(out) != 1:
        return []
    return list(bools.get(out[0], []))


def refutes(atom: Atom, vmin, vmax, device_dtype) -> bool:
    """True when ``column <op> value`` is FALSE for every row of a
    group whose column spans ``[vmin, vmax]`` — as the DEVICE would
    evaluate it. Conservative: unknown stats never refute.

    Integer/bool columns compare in float64: a non-integral literal
    promotes the device comparison to float anyway, and float64 is
    exact for both sides below 2**53 (beyond that, never refute —
    truncating the literal INTO the int dtype would wrongly refute
    groups whose rows match, e.g. ``x < 3.5`` over a group holding 3).
    Float columns compare after the (monotone) cast to the device
    dtype, with strict/non-strict boundary rules that survive a host
    value rounding ONTO the literal."""
    if vmin is None or vmax is None:
        return False
    try:
        dd = np.dtype(device_dtype)
        if dd.kind in "iub":
            exact = float(2 ** 53)
            lo = float(vmin)
            hi = float(vmax)
            v = float(atom.value)
            if abs(lo) > exact or abs(hi) > exact or abs(v) > exact:
                return False
        else:
            lo = np.asarray(vmin, np.float64).astype(dd)
            hi = np.asarray(vmax, np.float64).astype(dd)
            v = np.asarray(atom.value, np.float64).astype(dd)
    except (TypeError, ValueError, OverflowError):
        return False
    # monotone cast: x <= vmax  =>  cast(x) <= hi, etc. Strict device
    # comparisons survive equality at the bound; non-strict need a
    # strict host bound.
    if atom.op == "gt":   # all false iff every cast(x) <= v
        return bool(hi <= v)
    if atom.op == "ge":
        return bool(hi < v)
    if atom.op == "lt":
        return bool(lo >= v)
    if atom.op == "le":
        return bool(lo > v)
    if atom.op == "eq":
        return bool(v < lo or v > hi)
    return False
