"""Multi-host operation: process bootstrap + process-local distribution.

The reference genuinely spanned processes — a driver JVM plus N executor
JVMs, with partitions resident in executors and closures shipped over
Spark RPC (``DebugRowOps.scala:372-386``, ``ExperimentalOperations.scala:91``).
The TPU-native equivalent is JAX's multi-controller SPMD: every host runs
the same program, :func:`initialize` joins them into one cluster
(``jax.distributed``), and a :class:`~.mesh.DeviceMesh` built over the
GLOBAL device set makes the cross-host topology just another mesh — data
collectives ride ICI within a slice and DCN across hosts, with no
framework-level RPC at all.

:func:`distribute_local` is the executor-side entry: each process
contributes its OWN rows (the analogue of partitions already living in
that executor) and gets back a :class:`~.distributed.DistributedFrame`
whose columns are global arrays. Per-process padding is tracked with a
per-shard validity vector, so reductions and aggregations mask pad rows
wherever they fall — not just in a global suffix.

The 2-process CPU test (``tests/test_cluster.py``) runs dmap/dreduce/
daggregate end-to-end through this module; on TPU pods the same code runs
unchanged with ``initialize()`` reading the cluster env.
"""

from __future__ import annotations

from typing import Dict, Mapping, Optional, Sequence

import jax
import numpy as np

from .. import dtypes as _dt
from ..frame import TensorFrame
from ..resilience import (ClusterInitError, DeadlineExceeded, deadline,
                          default_policy, env_bool, env_float, faults,
                          is_transient, remaining_time)
from ..schema import Schema
from ..utils.logging import get_logger
from ..utils.tracing import counters
from .distributed import DistributedFrame
from .mesh import DeviceMesh

__all__ = ["initialize", "cluster_mesh", "distribute_local",
           "process_index", "process_count", "process_identity"]

_log = get_logger("parallel.cluster")

# default bound on the whole bootstrap (connect + retries); jax's own
# default (300s) is tuned for pod schedulers, far too patient for the
# "coordinator address is simply wrong" failure mode at the heart of
# multi-process bring-up problems (TF-HPC, arXiv:1903.04364 §5)
_DEFAULT_BOOTSTRAP_TIMEOUT = 60.0


def initialize(coordinator_address: Optional[str] = None,
               num_processes: Optional[int] = None,
               process_id: Optional[int] = None,
               timeout: Optional[float] = None,
               **kwargs) -> bool:
    """Join this process to the cluster (idempotent). Returns True when
    the process is part of a multi-process cluster afterwards, False when
    it degraded to (or already was) single-process.

    Policy wrapper over ``jax.distributed.initialize``: explicit
    arguments win, otherwise ``TFT_COORDINATOR`` / ``TFT_NUM_PROCESSES`` /
    ``TFT_PROCESS_ID`` are read, otherwise jax's own autodetection (TPU
    pod metadata, SLURM, ...) runs. Call before the first jax operation.

    Robustness semantics (see ``docs/resilience.md``):

    - a partially-specified cluster env (e.g. a coordinator address with
      no process count) raises ``ValueError`` immediately instead of
      handing jax a spec that hangs;
    - the whole bootstrap is bounded by ``timeout`` (or
      ``TFT_BOOTSTRAP_TIMEOUT``, default 60s) and retried with backoff:
      an explicitly-configured cluster keeps retrying until that deadline
      (the coordinator may simply not be up yet), autodetection retries
      under the attempt-counted process policy (``TFT_RETRY_*`` knobs);
    - when the bootstrap still fails, the process degrades to a
      single-process mesh with a LOUD warning — unless
      ``TFT_REQUIRE_CLUSTER=1``, which turns degradation into a
      :class:`~..resilience.ClusterInitError` raised within the deadline.
    """
    import os

    if jax.distributed.is_initialized():  # already up
        return jax.process_count() > 1

    coordinator_address = coordinator_address or os.environ.get(
        "TFT_COORDINATOR")
    if num_processes is None and os.environ.get("TFT_NUM_PROCESSES"):
        num_processes = int(os.environ["TFT_NUM_PROCESSES"])
    if process_id is None and os.environ.get("TFT_PROCESS_ID"):
        process_id = int(os.environ["TFT_PROCESS_ID"])

    spec = {"TFT_COORDINATOR / coordinator_address": coordinator_address,
            "TFT_NUM_PROCESSES / num_processes": num_processes,
            "TFT_PROCESS_ID / process_id": process_id}
    given = [k for k, v in spec.items() if v is not None]
    missing = [k for k, v in spec.items() if v is None]
    if given and missing:
        # a partial spec reaches jax.distributed as a malformed cluster
        # and surfaces as an opaque hang/grpc error; fail fast instead
        raise ValueError(
            f"partially-specified cluster environment: {given} set but "
            f"{missing} missing — set all three (or none, for "
            f"single-process / autodetection)")
    if coordinator_address is not None:
        # malformed addresses fail fast like the partial spec above —
        # retrying (or degrading on) a typo helps nobody
        _parse_hostport(coordinator_address)

    if timeout is None:
        timeout = env_float("TFT_BOOTSTRAP_TIMEOUT",
                            _DEFAULT_BOOTSTRAP_TIMEOUT)
    require_cluster = env_bool("TFT_REQUIRE_CLUSTER", False)
    if given:
        # an explicitly-configured cluster is retried until the bootstrap
        # deadline, not for an attempt count: connection-refused is
        # near-instant while the coordinator has not bound its port yet
        # (the normal worker-before-coordinator launch race), so a
        # 3-attempt budget would give up in milliseconds and split-brain
        # the job. The retry loop's deadline accounting ends the loop.
        policy = default_policy(max_attempts=1_000_000)
    else:
        # autodetection: a handful of tries is plenty — "no cluster
        # detected" answers quickly and is usually the final answer
        policy = default_policy()

    def attempt() -> None:
        faults.check("cluster_init")
        if jax.distributed.is_initialized():
            return  # a slow earlier attempt won the race after all
        left = remaining_time()
        if coordinator_address is not None and process_id not in (None, 0):
            # probe the coordinator over plain TCP FIRST: on several
            # jaxlib versions a failed in-process connect ends in
            # LOG(FATAL) (the distributed client terminates the whole
            # process), which no Python-level retry could survive. A
            # refused/timed-out socket here raises ConnectionError /
            # TimeoutError — both transient, both retried.
            _probe_coordinator(coordinator_address,
                               min(left, 10.0) if left else 10.0)
        kw = dict(kwargs)
        if left is not None and "initialization_timeout" not in kw:
            # per-attempt bound: jax's own default (300s) would swallow
            # the whole budget in one try
            kw["initialization_timeout"] = max(1, int(left))
        jax.distributed.initialize(coordinator_address=coordinator_address,
                                   num_processes=num_processes,
                                   process_id=process_id, **kw)

    try:
        with deadline(timeout):
            policy.call(attempt, op="cluster_init")
    except Exception as e:
        if require_cluster:
            counters.inc("cluster_init.failures")
            raise ClusterInitError(
                f"cluster bootstrap failed within {timeout}s and "
                f"TFT_REQUIRE_CLUSTER is set: {e}") from e
        if (not given and not isinstance(e, DeadlineExceeded)
                and not is_transient(e)):
            # nothing was configured and autodetection said "no cluster
            # here" — the normal single-process case, not a failure (no
            # counter). A TRANSIENT error that survived the retry budget
            # is different: a cluster was within reach and bootstrap
            # genuinely failed, which must be a loud degradation.
            _log.debug("no cluster detected (%s); running single-process",
                       e)
            return False
        counters.inc("cluster_init.failures")
        counters.inc("cluster_init.degraded")
        _log.warning(
            "DEGRADED TO SINGLE-PROCESS: cluster bootstrap failed (%s). "
            "Collectives will only span this process's devices; set "
            "TFT_REQUIRE_CLUSTER=1 to make this fatal instead.", e)
        return False
    return jax.process_count() > 1


def _parse_hostport(address: str):
    """``host:port`` / ``[v6]:port`` → ``(host, port)``; ``ValueError``
    on anything a socket connect could not use."""
    host, sep, port_s = address.rpartition(":")
    if host.startswith("[") and host.endswith("]"):
        host = host[1:-1]  # bracketed IPv6 literal
    try:
        port = int(port_s)
    except ValueError:
        port = -1
    if not sep or not 0 < port < 65536:
        raise ValueError(
            f"coordinator address {address!r} is not host:port")
    return host or "127.0.0.1", port


def _probe_coordinator(address: str, timeout: float) -> None:
    """One TCP connect to the coordinator, bounded by ``timeout``.

    Raises ``ConnectionError`` (refused/reset) or ``TimeoutError``
    (unroutable) — the transient classifications the retry loop expects.
    """
    import socket

    host, port = _parse_hostport(address)
    try:
        sock = socket.create_connection((host, port),
                                        timeout=max(timeout, 0.001))
    except socket.timeout as e:  # pre-3.10 spelling of TimeoutError
        raise TimeoutError(
            f"coordinator {address} unreachable within {timeout:.1f}s"
        ) from e
    except OSError as e:
        raise ConnectionError(
            f"coordinator {address} not accepting connections: {e}"
        ) from e
    sock.close()


def process_index() -> int:
    return jax.process_index()


def process_count() -> int:
    return jax.process_count()


def process_identity() -> str:
    """A stable worker-id string for THIS process (``p<i>of<n>``).

    The serving fabric's per-process identity in real multi-process
    deployments: ``serve/fabric.py`` seeds worker ids from it and the
    flight recorder stamps it on records and dump headers
    (``TFT_FLIGHT_DUMP``), so per-process JSONL dumps merge
    unambiguously in ``tft.doctor()``. Safe before :func:`initialize`
    (a single uninitialized process is ``p0of1``)."""
    try:
        return f"p{jax.process_index()}of{jax.process_count()}"
    except Exception as e:
        _log.debug("process_identity before backend init: %s", e)
        return "p0of1"


def cluster_mesh(axis_names: Sequence[str] = ("data",),
                 shape: Optional[Sequence[int]] = None) -> DeviceMesh:
    """A mesh over the GLOBAL device set (every process's chips).

    The data axis must lead (``distribute_local`` relies on data-major
    device order to lay process rows contiguously).
    """
    devices = jax.devices()
    n = len(devices)
    if shape is None:
        shape = (n,) + (1,) * (len(axis_names) - 1)
    if int(np.prod(shape)) != n:
        raise ValueError(f"Mesh shape {shape} does not cover {n} devices")
    from jax.sharding import Mesh

    arr = np.array(devices).reshape(tuple(shape))
    return DeviceMesh(Mesh(arr, tuple(axis_names)),
                      data_axis=axis_names[0])


def _allgather_host_ints(values: Sequence[int]) -> np.ndarray:
    """Allgather small host ints across processes → [P, len(values)]."""
    from jax.experimental import multihost_utils

    return np.asarray(multihost_utils.process_allgather(
        np.asarray(values, np.int64)))


def distribute_local(local: Mapping[str, np.ndarray] | TensorFrame,
                     mesh: DeviceMesh,
                     schema: Optional[Schema] = None) -> DistributedFrame:
    """Build a global :class:`DistributedFrame` from process-local rows.

    Every process calls this collectively with its OWN row block (local
    row counts may differ). Rows land process-contiguously in the global
    order; each process's block is zero-padded up to its shards, and the
    per-shard valid-row counts ride along so every reduction masks pads
    wherever they fall (``DistributedFrame.shard_valid``).
    """
    if isinstance(local, TensorFrame):
        from ..frame import Block

        merged = Block.concat(local.blocks(), local.schema)
        schema = local.schema
        cols_in: Dict[str, np.ndarray] = {
            f.name: merged.dense(f.name) for f in schema}
        n_local = merged.num_rows
    else:
        if schema is None:
            df = TensorFrame.from_columns(dict(local))
            schema = df.schema
        cols_in = {k: np.asarray(v) for k, v in local.items()}
        n_local = next(iter(cols_in.values())).shape[0] if cols_in else 0

    dev_mesh = mesh.mesh
    axis = mesh.data_axis
    if dev_mesh.axis_names[0] != axis:
        raise ValueError(
            f"distribute_local needs the data axis {axis!r} leading in the "
            f"mesh (axes: {dev_mesh.axis_names}) for process-contiguous "
            f"row layout")
    S = mesh.num_data_shards
    # process owning each data shard (data-major device order)
    shard_proc = [d.process_index
                  for d in dev_mesh.devices.reshape(S, -1)[:, 0]]
    my = jax.process_index()
    my_shards = [s for s in range(S) if shard_proc[s] == my]
    if not my_shards:
        raise ValueError(f"process {my} owns no data shards of {mesh!r}")

    counts = _allgather_host_ints([n_local])[:, 0]  # [P]
    # uniform rows-per-shard across the global mesh (XLA's equal-shard
    # world); sized for the largest process block
    per_proc_shards = {p: sum(1 for s in shard_proc if s == p)
                       for p in set(shard_proc)}
    rows_per = max(
        (int(counts[p]) + per_proc_shards[p] - 1) // per_proc_shards[p]
        for p in per_proc_shards)
    rows_per = max(rows_per, 1)

    # per-shard valid counts, globally (every process computes identically)
    shard_valid = np.zeros(S, np.int64)
    seen: Dict[int, int] = {p: 0 for p in per_proc_shards}
    for s in range(S):
        p = shard_proc[s]
        got = seen[p]
        shard_valid[s] = min(max(int(counts[p]) - got, 0), rows_per)
        seen[p] = got + rows_per
    num_rows = int(counts.sum())

    local_padded = len(my_shards) * rows_per
    columns: Dict[str, jax.Array] = {}
    for f in schema:
        a = cols_in[f.name]
        if not f.dtype.tensor:
            from .distributed import _host_side_column

            columns[f.name] = _host_side_column(a, f, local_padded)
            continue
        dd = _dt.device_dtype(f.dtype)
        if a.dtype != dd:
            from .. import native as _native
            a = _native.convert(np.ascontiguousarray(a), dd)
        if a.shape[0] != local_padded:
            pad = [(0, local_padded - a.shape[0])] + [(0, 0)] * (a.ndim - 1)
            a = np.pad(a, pad)
        sharding = mesh.row_sharding(a.ndim)
        global_shape = (S * rows_per,) + a.shape[1:]
        columns[f.name] = jax.make_array_from_process_local_data(
            sharding, a, global_shape)
    return DistributedFrame(mesh, schema, columns, num_rows,
                            shard_valid=shard_valid)
