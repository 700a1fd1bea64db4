"""Minimal keyed aggregation + README examples — the smallest demos.

Parity with ``tensorframes_snippets/groupby_scratch.py`` (string-keyed
``aggregate`` of a sum) and the reference ``README.md:56-124`` examples:
the ``x + 3`` map over a 5-row frame, and ``analyze`` + reduce over a
vector column.
"""

from __future__ import annotations

import numpy as np

import tensorframes_tpu as tft


def groupby_sum():
    """groupby_scratch.py: sum x per string key '0'/'1'."""
    rows = [(str(x // 3), float(x)) for x in range(1, 6)]
    df = tft.frame(rows, columns=["key", "x"])
    gb = df.group_by("key")
    out = tft.aggregate(lambda x_input: {"x": x_input.sum(0)}, gb)
    return sorted(out.collect())


def readme_map_blocks():
    """README.md:56-87 — add 3 to every element of the x column."""
    df = tft.frame([(float(x),) for x in range(5)], columns=["x"])
    df2 = tft.map_blocks(lambda x: {"z": x + 3.0}, df)
    return df2.collect()


def readme_reduce_vector():
    """README.md:92-124 — analyze, then reduce_sum / reduce_min over a
    vector column."""
    import jax.numpy as jnp

    df = tft.frame([([1.0, 1.0],), ([2.0, 2.0],)], columns=["x"])
    df = tft.analyze(df)
    s = tft.reduce_blocks(lambda x_input: {"x": x_input.sum(0)}, df)
    m = tft.reduce_rows(lambda x_1, x_2: {"x": jnp.minimum(x_1, x_2)}, df)
    return s, m


def readme_dsl_map():
    """README.md:154-172 — the Scala-DSL mapBlocks on a double column,
    here via the operator DSL front end."""
    from tensorframes_tpu import dsl

    df = tft.frame({"x": np.arange(5.0) * 0.1})
    with dsl.with_graph():
        x = tft.block(df, "x")
        z = (x + 3.0).named("z")
        out = tft.map_blocks(z, df)
    return out.collect()


def main():
    print("groupby_sum:", groupby_sum())
    print("readme_map_blocks:", readme_map_blocks())
    s, m = readme_reduce_vector()
    print("reduce_sum:", s, "reduce_min:", m)
    print("dsl_map:", readme_dsl_map())


if __name__ == "__main__":
    from tensorframes_tpu.utils.platform import place_compile_cache

    place_compile_cache()
    main()
