"""Peak host-memory bench for the merge paths (round-3 weak #5).

The reference streamed partitions through its UDAF shuffle and never held
the whole dataset in one buffer; this framework's host ``aggregate`` and
``order_by`` used to ``Block.concat`` the frame (~3x column bytes of HOST
copies at peak). After the round-4 blockwise rewrite, the ASSERTED
contract is on the HOST-side allocations the rewrite governs
(``tracemalloc`` peak — numpy reports through it; XLA's device buffers
and program temporaries do NOT, correctly: on a TPU host those live in
HBM, and on this CPU-backend measurement they would conflate the
device's scratch with the host data path):

    aggregate: host allocations beyond the resident input frame
               < 1x the frame's column bytes  (total < 2x, input incl.)
    order_by:  < 2x (its RESULT is a full reordered copy of the frame,
               so ~1x of that extra is the output itself)

``ru_maxrss`` (which does include XLA CPU temps) is reported alongside,
uncapped, for transparency. CPU-only: the parent never touches jax and
each child forces the CPU backend. Each case runs in its own subprocess
(``ru_maxrss`` is a cumulative high-water mark). One JSON line per case;
nonzero exit if an assertion fails. Usage::

    python benchmarks/host_memory_bench.py [rows] [groups]
"""

import json
import resource
import subprocess
import sys
import tracemalloc

_is_child = len(sys.argv) >= 3 and sys.argv[1] == "--child"
ROWS = int(sys.argv[1]) if len(sys.argv) > 1 and not _is_child \
    else 10_000_000
GROUPS = int(sys.argv[2]) if len(sys.argv) > 2 and not _is_child \
    else 100_000

_CASES = ("aggregate_monoid", "aggregate_generic", "order_by")


def _child(case: str) -> None:
    import os

    import numpy as np

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    # host-memory measurement: always the CPU backend
    import jax

    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp  # noqa: F401  (warm the import before rss0)
    import tensorframes_tpu as tft

    rng = np.random.default_rng(0)
    key = rng.integers(0, GROUPS, ROWS).astype(np.int64)
    x = rng.normal(size=ROWS)
    column_bytes = key.nbytes + x.nbytes
    df = tft.frame({"key": key, "x": x}, num_partitions=8)
    df.cache()
    df.count()  # materialize the blocks
    del key, x
    rss0_kb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    tracemalloc.start()

    if case == "aggregate_monoid":
        out = tft.aggregate({"x": "sum"}, df.group_by("key"))
        out.count()
    elif case == "aggregate_generic":
        out = tft.aggregate(
            lambda x_input: {"x": x_input.sum(axis=0)},
            df.group_by("key"))
        out.count()
    elif case == "order_by":
        df.order_by("x").count()
    else:
        raise SystemExit(f"unknown case {case}")

    host_peak = tracemalloc.get_traced_memory()[1]
    tracemalloc.stop()
    rss_extra = (resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
                 - rss0_kb) * 1024
    cap = 2.0 if case == "order_by" else 1.0
    rec = {
        "metric": f"host_memory_{case}",
        "rows": ROWS,
        "groups": GROUPS,
        "column_bytes": column_bytes,
        "host_alloc_peak_bytes": host_peak,
        "host_alloc_over_column_bytes": round(host_peak / column_bytes, 3),
        "rss_extra_bytes_incl_xla_temps": rss_extra,
        "asserted_cap": cap,
        "ok": bool(host_peak < cap * column_bytes),
    }
    print(json.dumps(rec), flush=True)
    if not rec["ok"]:
        raise SystemExit(1)


def main() -> int:
    rc = 0
    for case in _CASES:
        proc = subprocess.run(
            [sys.executable, __file__, "--child", case,
             str(ROWS), str(GROUPS)],
            capture_output=True, text=True, timeout=1200)
        out = [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]
        print(out[-1] if out else json.dumps(
            {"metric": f"host_memory_{case}", "error":
             (proc.stderr or "no output")[-300:]}))
        rc |= proc.returncode
    return rc


if __name__ == "__main__":
    if len(sys.argv) >= 3 and sys.argv[1] == "--child":
        case = sys.argv[2]
        ROWS = int(sys.argv[3]) if len(sys.argv) > 3 else ROWS
        GROUPS = int(sys.argv[4]) if len(sys.argv) > 4 else GROUPS
        _child(case)
    else:
        sys.exit(main())
