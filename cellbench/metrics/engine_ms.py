"""engine_ms.serve: the engine's time in each served query, in ms.

Over the queries whose ``serve.finish`` record (outcome ``completed``)
ended in the window: the mean, per query, of the summed durations of its
outermost engine span records (``executor.*``, ``pipeline.*``,
``map_blocks.*``; one with an engine span among its ancestors is not
counted again), grouped by the records' ``query``. The device time the
engine waits for inside those spans counts. Read from the program's span
records (``tensorframes_tpu.utils.tracing.span_records``): nothing is
returned when the program keeps none, when no query completed in the
window, or when its record buffer evicted records that ended in the
window.
"""

ENGINE = ("executor.", "pipeline.", "map_blocks.")


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


def _nested(rec, by_id) -> bool:
    """Whether an engine span encloses ``rec``."""
    parent = by_id.get(rec.parent)
    while parent is not None:
        if parent.name.startswith(ENGINE):
            return True
        parent = by_id.get(parent.parent)
    return False


def read(run):
    recs = window_records(run) or ()
    done = {r.query for r in recs if r.name == "serve.finish"
            and r.query is not None
            and (r.attrs or {}).get("outcome") == "completed"}
    if not done:
        return None
    by_id = {r.id: r for r in recs}
    engine_ns = dict.fromkeys(done, 0)
    for r in recs:
        if r.query in engine_ns and r.name.startswith(ENGINE) \
                and not _nested(r, by_id):
            engine_ns[r.query] += r.end_ns - r.start_ns
    return sum(engine_ns.values()) / len(engine_ns) * 1e-6
