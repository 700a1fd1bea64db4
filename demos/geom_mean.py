"""Harmonic and geometric means per key — chained pipeline demo.

Parity with ``tensorframes_snippets/geom_mean.py:26-49``, the workload that
"found some bugs" in the reference (non-numeric string columns riding along,
unused columns, outputs with children). The pipeline shape is the same:

  map_blocks (per-row transform) -> select -> group_by + aggregate (keyed
  sums) -> map_blocks (final ratio)

and it exercises exactly those bug surfaces: ``key`` is a *string* column
that passes through the tensor engine untouched, and the first map leaves
the original ``x`` column unused downstream (dropped by ``select``).

The harmonic mean of group g is  n_g / sum(1/x_i);  the geometric mean is
exp(mean(log x_i)) — both algebraic, so the keyed aggregation is the same
sum-shaped reduce the reference's UDAF performs.
"""

from __future__ import annotations

import numpy as np

import tensorframes_tpu as tft


def harmonic_mean_per_key(df: tft.TensorFrame,
                          col_key: str = "key") -> tft.TensorFrame:
    """Value column is ``x`` (the traced functions bind it by name)."""
    import jax.numpy as jnp

    def invs_and_count(x):
        inv = 1.0 / x
        return {"invs": inv, "count": jnp.ones_like(inv)}

    df2 = tft.map_blocks(invs_and_count, df)
    gb = df2.select([col_key, "invs", "count"]).group_by(col_key)

    def sums(invs_input, count_input):
        return {"invs": invs_input.sum(0), "count": count_input.sum(0)}

    df3 = tft.aggregate(sums, gb)

    def ratio(invs, count):
        return {"harmonic_mean": count / invs}

    return tft.map_blocks(ratio, df3).select([col_key, "harmonic_mean"])


def geometric_mean_per_key(df: tft.TensorFrame,
                           col_key: str = "key") -> tft.TensorFrame:
    """Value column is ``x`` (the traced functions bind it by name)."""
    import jax.numpy as jnp

    def logs_and_count(x):
        lg = jnp.log(x)
        return {"logs": lg, "count": jnp.ones_like(lg)}

    df2 = tft.map_blocks(logs_and_count, df)
    gb = df2.select([col_key, "logs", "count"]).group_by(col_key)

    def sums(logs_input, count_input):
        return {"logs": logs_input.sum(0), "count": count_input.sum(0)}

    df3 = tft.aggregate(sums, gb)

    def finish(logs, count):
        return {"geometric_mean": jnp.exp(logs / count)}

    return tft.map_blocks(finish, df3).select([col_key, "geometric_mean"])


def make_data(n: int = 60, num_partitions: int = 3, seed: int = 7):
    rng = np.random.default_rng(seed)
    x = rng.uniform(0.5, 4.0, n)
    key = np.array([f"g{i % 3}" for i in range(n)], dtype=object)
    return tft.frame({"key": key, "x": x}, num_partitions=num_partitions)


def main():
    df = make_data()
    print("harmonic:", sorted(harmonic_mean_per_key(df).collect()))
    print("geometric:", sorted(geometric_mean_per_key(df).collect()))


if __name__ == "__main__":
    from tensorframes_tpu.utils.platform import place_compile_cache

    place_compile_cache()
    main()
