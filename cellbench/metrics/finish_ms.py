"""finish_ms.serve: the scheduler's finish tail of a served query, in ms.

The mean duration of the ``serve.finish`` span records (outcome
``completed``) that ended in the window: the future's resolution and the
always-on hooks after it, during which the worker takes no next query.
Read from the program's span records
(``tensorframes_tpu.utils.tracing.span_records``): nothing is returned
when the program keeps none, when no query completed in the window, or
when its record buffer evicted records that ended in the window.
"""


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
    fin = [r.end_ns - r.start_ns for r in window_records(run) or ()
           if r.name == "serve.finish"
           and (r.attrs or {}).get("outcome") == "completed"]
    if not fin:
        return None
    return sum(fin) / len(fin) * 1e-6
