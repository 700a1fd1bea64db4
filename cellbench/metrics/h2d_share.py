"""h2d_share.infer: the share of the window in which a block upload ran.

100 x the union of the program's ``executor.h2d`` span records (from a
block's ``device_put`` until its bytes are on the device), clipped to
the window, over the window's seconds. Read from the program's span
records (``tensorframes_tpu.utils.tracing.span_records``): nothing is
returned when the program keeps none, when none ended in the window, or
when its record buffer evicted records that ended in the window.
"""

SPAN = "executor.h2d"


def window_records(run):
    """The program's span records that ended inside the window, or None
    (the program keeps no records, or lost some of the window's)."""
    from tensorframes_tpu.utils import tracing

    read = getattr(tracing, "span_records", None)
    if read is None:
        return None
    t0, t1 = int(run.window.t0 * 1e9), int(run.window.t_end * 1e9)
    held = read()
    if tracing.counters.get("trace.spans_dropped") and (
            not held or held[0].end_ns >= t0):
        return None
    return [r for r in held if t0 <= r.end_ns <= t1]


def read(run):
    recs = window_records(run)
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
