"""Resilient execution: retries, deadlines, fault injection, degradation.

The reference inherited all of its fault tolerance from Spark — task
retry, straggler re-execution, executor replacement — and the TPU-native
port dropped that layer entirely: a transient PJRT error, a slow
coordinator, or a failing padded compile killed the whole job. This
subsystem restores an explicit reliability story at the three layers that
can fail:

- **policy** (:mod:`.policy`): :class:`RetryPolicy` — bounded attempts,
  exponential backoff with deterministic jitter, an overall deadline —
  plus the :func:`deadline` context helper. Every retry/giveup is
  exported through :data:`~..utils.tracing.counters` and the framework
  logger, and each attempt runs inside a tracing span.
- **classification** (:mod:`.classify`): which exceptions are transient
  (retry), which are out-of-memory (split the block), and which are
  permanent (fail fast). Misclassifying a deterministic error as
  transient turns one failure into ``max_attempts`` failures, so the
  default set is conservative and extensible via ``TFT_TRANSIENT_ERRORS``.
- **faults** (:mod:`.faults`): a deterministic fault-injection harness
  (``with faults.inject("compile", fail_n=2): ...``) that the tier-1
  resilience suite uses to prove every retry/fallback path end-to-end on
  CPU — no real TPU failures required.
- **chaos** (:mod:`.chaos`): seeded multi-site schedules over the fault
  sites (``TFT_CHAOS="seed:42,rate:0.05,sites:device|worker|disk"``) —
  probabilistic in distribution, fully replayable by seed — for proving
  the contracts survive *composed* faults, not just single drills.
- **invariants** (:mod:`.invariants`): cross-cutting auditors at
  quiesce points (slot leases, memory ledger, row conservation,
  scheduler/fabric accounting); violations raise a classified
  :class:`InvariantViolation` in strict/chaos mode and flight-record +
  count always-on.

Consumers: ``parallel/cluster.py`` (bootstrap timeout, retry, graceful
single-process degradation), ``engine/executor.py`` (dispatch retry,
exact-shape fallback from bucketed compiles, OOM split-block re-dispatch),
and ``serve/`` — the multi-tenant scheduler's load rejections
(:class:`QueueFull`, :class:`OverQuota`, :class:`AdmissionDeadline`) are
classified here so clients and retry loops see ``rejected`` / ``over_quota`` /
``deadline_admission`` kinds instead of anonymous RuntimeErrors. The
degradation matrix — what falls back versus what fails fast — is
documented in ``docs/resilience.md``.
"""

from .classify import (AdmissionDeadline, DeviceLost, InvariantViolation,
                       OverQuota, QueryCancelled, QueryInterrupted,
                       QueryPreempted, QueryQuarantined, QueueFull,
                       ServeRejected, WorkerLost, error_kind,
                       is_device_lost, is_oom, is_permanent, is_transient,
                       is_worker_lost)
from .faults import InjectedFault, inject
from .policy import (DEFAULT_POLICY, ClusterInitError, DeadlineExceeded,
                     RetryPolicy, check_deadline, deadline, default_policy,
                     env_bool, env_float, env_int, remaining_time)
from . import faults

__all__ = [
    "RetryPolicy", "DeadlineExceeded", "ClusterInitError",
    "DEFAULT_POLICY", "default_policy", "deadline", "remaining_time",
    "check_deadline",
    "is_transient", "is_oom", "is_permanent", "is_device_lost",
    "is_worker_lost", "error_kind",
    "ServeRejected", "QueueFull", "OverQuota", "AdmissionDeadline",
    "QueryQuarantined", "InvariantViolation",
    "DeviceLost", "WorkerLost",
    "QueryInterrupted", "QueryPreempted", "QueryCancelled",
    "env_bool", "env_float", "env_int",
    "faults", "inject", "InjectedFault",
]
