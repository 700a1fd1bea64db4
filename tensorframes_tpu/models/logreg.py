"""Logistic regression as a DataFrame workload (BASELINE config 5).

The training step is expressed through the framework's own ops, the way the
reference's k-means demo drives Spark (``kmeans_demo.py:47-148``): the model
is broadcast into the computation as constants, ``map_blocks`` scores blocks
of rows, and the gradient is a ``reduce_blocks`` — which on a mesh becomes a
``psum`` allreduce over the data axis (the reference's Spark tree-reduce,
re-expressed as an ICI collective; SURVEY.md §2.3).
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..frame import TensorFrame
from ..parallel.mesh import DeviceMesh

__all__ = ["LogisticRegression"]


class LogisticRegression:
    """Binary logistic regression over a feature-vector column.

    Parameters are a ``{"w": [d], "b": []}`` pytree. All methods are pure;
    the instance only carries hyperparameters.
    """

    def __init__(self, num_features: int, l2: float = 0.0):
        self.num_features = int(num_features)
        self.l2 = float(l2)

    def init(self, rng: Optional[jax.Array] = None) -> Dict[str, jax.Array]:
        if rng is None:
            rng = jax.random.PRNGKey(0)
        w = jax.random.normal(rng, (self.num_features,), jnp.float32) * 0.01
        return {"w": w, "b": jnp.zeros((), jnp.float32)}

    # -- pure model math ----------------------------------------------------
    def logits(self, params, x: jax.Array) -> jax.Array:
        return x @ params["w"] + params["b"]

    def loss(self, params, x: jax.Array, y: jax.Array) -> jax.Array:
        """Mean sigmoid cross-entropy over the batch (+ L2)."""
        z = self.logits(params, x)
        # log(1+e^z) - y*z, numerically stable
        nll = jnp.maximum(z, 0.0) - z * y + jnp.log1p(jnp.exp(-jnp.abs(z)))
        reg = 0.5 * self.l2 * jnp.sum(params["w"] ** 2)
        return jnp.mean(nll) + reg

    def grads(self, params, x: jax.Array, y: jax.Array):
        return jax.grad(self.loss)(params, x, y)

    def sgd_step(self, params, x, y, lr: float = 0.1):
        g = self.grads(params, x, y)
        return jax.tree_util.tree_map(lambda p, gi: p - lr * gi, params, g)

    # -- DataFrame-op formulation (the BASELINE workload) -------------------
    def gradient_via_frame(self, params, df: TensorFrame,
                           features: str = "features", label: str = "label",
                           ) -> Tuple[Dict[str, np.ndarray], float]:
        """One gradient evaluation driven entirely through the six-op API.

        ``map_blocks`` computes per-row gradient contributions (the model's
        parameters ride into the jitted computation as closed-over
        constants — the reference's broadcast-the-graph step), then
        ``reduce_blocks`` sums them across partitions. Returns
        ``({'w': gw, 'b': gb}, loss)``.
        """
        w = np.asarray(params["w"])
        b = np.asarray(params["b"])
        n_total = df.count()

        def per_row(**cols):
            x, y = cols[features], cols[label]
            z = x @ w + b
            p = jax.nn.sigmoid(z)
            err = (p - y)[:, None]
            gw = err * x                       # [n, d] per-row grad
            gb = err[:, 0]
            nll = (jnp.maximum(z, 0.0) - z * y
                   + jnp.log1p(jnp.exp(-jnp.abs(z))))
            return {"gw": gw, "gb": gb, "nll": nll}

        fn = _named_args_fn(per_row, [features, label])
        scored = df.map_blocks(fn, trim=True)
        sums = scored.reduce_blocks(
            lambda gw_input, gb_input, nll_input: {
                "gw": gw_input.sum(axis=0),
                "gb": gb_input.sum(axis=0),
                "nll": nll_input.sum(axis=0)})
        gb_s, gw_s, nll_s = sums  # fetches come back sorted by name
        grad = {"w": gw_s / n_total + self.l2 * w,
                "b": gb_s / n_total}
        loss = float(nll_s / n_total + 0.5 * self.l2 * np.sum(w ** 2))
        return grad, loss

    def fit_via_frame(self, df: TensorFrame, steps: int = 10,
                      lr: float = 0.5, features: str = "features",
                      label: str = "label", params=None):
        """Driver-side iteration loop, k-means-demo style: state lives on
        the host between rounds, re-embedded as constants each round."""
        params = params if params is not None else self.init()
        params = {k: np.asarray(v) for k, v in params.items()}
        losses = []
        for _ in range(steps):
            grad, loss = self.gradient_via_frame(
                params, df, features=features, label=label)
            params = {"w": params["w"] - lr * grad["w"],
                      "b": params["b"] - lr * grad["b"]}
            losses.append(loss)
        return params, losses

    # -- mesh-parallel single-program step (the v5e-8 path) -----------------
    def make_sharded_train_step(self, mesh: DeviceMesh, lr: float = 0.1):
        """Data-parallel train step as ONE compiled program over the mesh.

        Batch enters row-sharded over the data axis; the gradient allreduce
        is the ``jnp.mean`` XLA lowers to a ``psum`` across shards — the
        reference's Spark tree-reduce as an ICI collective.
        """
        from jax.sharding import NamedSharding, PartitionSpec as P

        data_sharding = NamedSharding(mesh.mesh, P(mesh.data_axis))
        repl = NamedSharding(mesh.mesh, P())

        def step(params, x, y):
            g = self.grads(params, x, y)
            new = jax.tree_util.tree_map(lambda p, gi: p - lr * gi,
                                         params, g)
            return new, self.loss(params, x, y)

        return jax.jit(
            step,
            in_shardings=(jax.tree_util.tree_map(lambda _: repl,
                                                 {"w": 0, "b": 0}),
                          data_sharding, data_sharding),
            out_shardings=(jax.tree_util.tree_map(lambda _: repl,
                                                  {"w": 0, "b": 0}), repl))


def _frame_fn(owner, key, kw_fn, names):
    """``owner``'s block function for ``key``: :func:`_named_args_fn` of
    ``kw_fn`` the first time, the same object after. ``map_blocks``
    caches the traced computation, and with it the executable, on the
    function object, so a later call under ``key`` with bound values
    of the same shapes compiles nothing."""
    memo = owner.__dict__.setdefault("_frame_fns", {})
    fn = memo.get(key)
    if fn is None:
        fn = memo[key] = _named_args_fn(kw_fn, names)
    return fn


def _named_args_fn(kw_fn, names):
    """Build a positional function whose parameter names are ``names`` —
    the engine derives computation inputs from parameter names
    (``engine/ops.py:_callable_input_names``)."""
    args = ", ".join(names)
    kwargs = ", ".join(f"{n!r}: {n}" for n in names)
    ns = {"_kw_fn": kw_fn}
    exec(f"def _f({args}):\n    return _kw_fn(**{{{kwargs}}})\n", ns)
    return ns["_f"]
