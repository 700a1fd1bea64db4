"""Keyed aggregation over a frame resident on the device, the data made
in parallel chunks.

As ``frame_groupby`` (``distribute`` once onto a mesh of the cell's chips,
then one ``daggregate`` per request with its result collected to host
numpy, checked against ``cellbench/reference/<config>.py``), except that
the columns are generated in chunks of ``CHUNK`` rows, each chunk of each
column from its own generator seeded ``[seed, column, chunk]`` and
written in place by a pool of threads (numpy's generators release the
GIL): at 1e9 rows one thread would take about a minute of the set-up.
"""

from __future__ import annotations

import importlib.util
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

CHUNK = 1 << 24


def _load(name: str):
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        name + ".py")
    spec = importlib.util.spec_from_file_location("cellbench_system_" + name,
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_base = _load("frame_groupby")


def generate(cfg: dict, seed: int) -> dict:
    """The configuration's columns, chunk by chunk on a pool of threads."""
    n = int(cfg["rows"])
    cols = {}
    for c in cfg["columns"]:
        if c["kind"] not in ("int", "double"):
            raise ValueError(f"unknown column kind {c['kind']!r}")
        cols[c["name"]] = np.empty(n, np.int32 if c["kind"] == "int"
                                   else np.float64)

    def fill(job):
        col, i = job
        c = cfg["columns"][col]
        a = i * CHUNK
        out = cols[c["name"]][a:min(a + CHUNK, n)]
        rng = np.random.default_rng([seed, col, i])
        if c["kind"] == "int":
            out[...] = rng.integers(c["low"], c["high"] + 1, len(out),
                                    dtype=np.int32)
            return
        rng.random(out=out)
        out *= c["high"] - c["low"]
        out += c["low"]
        np.round(out, c["decimals"], out=out)

    jobs = [(col, i) for col in range(len(cfg["columns"]))
            for i in range(-(-n // CHUNK))]
    with ThreadPoolExecutor(min(32, os.cpu_count() or 1)) as pool:
        list(pool.map(fill, jobs))
    return cols


class System(_base.System):
    def setup(self) -> None:
        import tensorframes_tpu as tft
        from tensorframes_tpu.parallel.distributed import distribute
        from tensorframes_tpu.parallel.mesh import local_mesh

        self.cols = generate(self.cfg, self.seed)
        frame = tft.frame(self.cols, num_partitions=1)
        self.dist = distribute(frame, local_mesh(self.chips))
        del frame
        self.view = self.dist.select(self.by + sorted(self.agg))
        # the first query builds the device group ids and compiles; the
        # second finds both cached, as every query of the window does
        for _ in range(2):
            self._query()
