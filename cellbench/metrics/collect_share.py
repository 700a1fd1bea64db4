"""collect_share.agg4: the share of the window in which the host waited
for an aggregation's tables and copied the result.

100 x the union of the program's ``daggregate.collect`` span records that
ended in the window (clipped to it), over the window's seconds, read as
``h2d_share`` reads its spans: nothing is returned when the program keeps
no records, none ended in the window, or some of the window's were
evicted.
"""

import os

from cellbench.harness import load_module

SPAN = "daggregate.collect"

_spans = load_module(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                  "h2d_share.py"), "cellbench_metric_h2d_share")


def read(run):
    recs = _spans.window_records(run)
    t0 = int(run.window.t0 * 1e9)
    spans = sorted((max(r.start_ns, t0), r.end_ns) for r in recs or ()
                   if r.name == SPAN)
    if not spans:
        return None
    busy, last = 0, t0
    for s, e in spans:
        s = max(s, last)
        if e > s:
            busy += e - s
            last = e
    return 100.0 * busy * 1e-9 / run.window_s
