"""Run every benchmark suite; one JSON line per metric on stdout.

``python -m benchmarks.run_all [--light]`` — ``--light`` scales the row
counts down ~100x for a fast correctness pass (the sizes the reference's
suites used are kept as the defaults). ``bench.py`` at the repo root stays
the driver's single headline metric; this is the full sweep behind
BASELINE.json's configs.
"""

from __future__ import annotations

import json
import sys


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    light = "--light" in argv

    from tensorframes_tpu.utils.platform import place_compile_cache

    place_compile_cache()

    from . import baseline_configs, e2e_bench, marshal_bench

    records = []
    if light:
        records += marshal_bench.run_ragged(n_rows=10_000, iters=2)
        records += marshal_bench.run(n_scalar=100_000, n_vector=100_000,
                                     iters=2)
        records += e2e_bench.run(n_rows=200_000, iters=2)
        records += baseline_configs.run(heavy=False)
    else:
        records += marshal_bench.run_ragged()
        records += marshal_bench.run()
        records += e2e_bench.run()
        records += baseline_configs.run()
    for rec in records:
        print(json.dumps(rec))
    return 0


if __name__ == "__main__":
    sys.exit(main())
