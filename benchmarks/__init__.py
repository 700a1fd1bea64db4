"""Performance harnesses.

The reference ships benchmark *infrastructure* but publishes no numbers
(BASELINE.json): three self-timed ScalaTest suites, all ``ignore``d —
marshalling micro-benchmarks (``perf/ConvertPerformanceSuite.scala``,
``perf/ConvertBackPerformanceSuite.scala``) and an end-to-end map+agg run
(``perf/PerformanceSuite.scala``). This package is the TPU build's
equivalent, plus the five BASELINE.json target configs. Each module exposes
``run() -> list[dict]`` returning one record per metric; ``run_all.py``
prints them as JSON lines.
"""
