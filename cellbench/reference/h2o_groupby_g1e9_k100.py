"""Plain reference for db-benchmark ``G1_1e9_1e2_0_0``: the per-group sums
of ``h2o_groupby_g1e8_k100.py`` (numpy on the host, int64 for the integer
columns, float64 for the doubles, groups in ascending key order;
``dtype="bfloat16"`` the control), computed over 1e9 rows after the
window.

For one non-negative integer key the rows are split into one contiguous
part a thread; each part's ``np.bincount`` of the keys (which releases the
GIL) gives per-key counts and sums, and the parts' tables are added. The
float64 sums may differ from one pass in the last bits; the integer sums
are exact. Anything else is handed to the 1e8 file.
"""

from __future__ import annotations

import functools
import importlib.util
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

_spec = importlib.util.spec_from_file_location(
    "cellbench_reference_h2o_groupby_g1e8_k100",
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "h2o_groupby_g1e8_k100.py"))
_g1e8 = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_g1e8)

group_ids = _g1e8.group_ids


def groupby(cols, by, agg, dtype: str = "float64"):
    """``{key: distinct keys, column: per-group sum}`` (see the module
    docstring)."""
    k = np.asarray(cols[by[0]])
    if (dtype != "float64" or len(by) != 1
            or not np.issubdtype(k.dtype, np.integer) or k.min() < 0):
        return _g1e8.groupby(cols, by, agg, dtype)
    for col, comb in agg.items():
        if comb != "sum":
            raise ValueError(f"reference computes sums only, not {comb!r}")
    bins = int(k.max()) + 1
    threads = min(32, os.cpu_count() or 1)
    edges = np.linspace(0, len(k), threads + 1).astype(np.int64)

    def part(i):
        rows = slice(edges[i], edges[i + 1])
        keys = k[rows]
        out = {None: np.bincount(keys, minlength=bins)}
        for col in agg:
            v = np.asarray(cols[col][rows], np.float64)
            out[col] = np.bincount(keys, weights=v, minlength=bins)
        return out

    with ThreadPoolExecutor(threads) as pool:
        parts = list(pool.map(part, range(threads)))

    def total(col):
        return functools.reduce(lambda a, b: np.add(a, b, out=a),
                                [p[col] for p in parts])

    present = np.flatnonzero(total(None))
    out = {by[0]: present}
    for col in agg:
        s = total(col)[present]
        if np.issubdtype(np.asarray(cols[col]).dtype, np.integer):
            s = s.astype(np.int64)
        out[col] = s
    return out
