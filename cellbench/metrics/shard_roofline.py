"""shard_roofline.agg4: one chip's aggregate program against its HBM
roofline, for a table row-sharded over several chips.

Each chip reads its own rows and writes a whole group table, so one
query moves ``costs.groupby_bytes(rows / chips, ...)`` on each chip; the
least time for the traced window's queries is that over the chip's HBM
bandwidth, divided by one chip's device time in the aggregate program
(the ``XLA Modules`` whose name holds ``shard_fn``, summed over the chips
and divided by their number). ``agg_roofline`` counts every chip's rows
against one chip's time and bandwidth, which a mesh of ``c`` chips
inflates ``c``-fold. Nothing is returned when the trace holds no such
program.
"""

from cellbench.costs import groupby_bytes
from cellbench.peaks import peak

PROGRAM = "shard_fn"


def read(run):
    if run.trace is None or not run.ok:
        return None
    chips = run.trace["chips"]
    prog_s = sum(s for name, s in run.trace["modules"].items()
                 if PROGRAM in name) / chips
    if prog_s <= 0:
        return None
    widths = [4] * len(run.cell.mix["request"]["agg"])
    total = sum(groupby_bytes(r["units"] / chips, 4, widths, r["groups"])
                for r in run.ok)
    return 100.0 * total / peak(run.device_kind)["hbm_bytes_per_s"] / prog_s
