"""collective_share.agg4: the share of the chips' time spent in
collectives.

The device time of the traced window's ops whose names start with a
collective's (``all-reduce``, ``all-gather``, ``reduce-scatter``,
``all-to-all``, ``collective-permute``; their ``-start``/``-done`` halves
included), summed over the chips, over the chips times the window.
Nothing is returned when the trace holds no collective.
"""

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")


def read(run):
    if run.trace is None:
        return None
    ops = [s for name, s in run.trace["ops"].items()
           if name.startswith(COLLECTIVES)]
    if not ops:
        return None
    return 100.0 * sum(ops) / (run.trace["chips"] * run.trace["window_s"])
