"""Pipelined block execution: overlap marshalling, H2D, compute, and D2H.

The engine's hot path used to be fully serial: every map/filter/reduce
materialized its result with ``[run_block(b) for b in df.blocks()]``, and
each dispatch hard-barriered (``jax.block_until_ready``) before converting
outputs back to host — so host marshalling, H2D transfer, device compute,
and D2H readback never overlapped across blocks. This module is the
streaming replacement: a bounded window of **in-flight blocks** where block
*k+1*'s convert/pad/device_put runs while block *k* computes on device and
block *k−1* drains back to host (the inter-step overlap of "Extending
TensorFlow's Semantics with Pipelined Execution", PAPERS.md).

The executor side is split in two halves (``BlockExecutor.submit`` /
``PendingBlock.drain``): *submit* converts inputs, plans padding, and
dispatches asynchronously — no barrier; *drain* waits for readiness and
converts outputs back. :func:`run_pipelined` keeps at most
``TFT_PIPELINE_DEPTH`` (default 3) blocks in flight and drains strictly
FIFO, so **output ordering is preserved** and the lazy-thunk contract of
the ops is unchanged.

Resilience composition (the load-bearing part): the async fast path has no
retry loop of its own. Any error — at submit (compile/dispatch) or
surfacing at drain (async execution failures materialize at the output
barrier) — is attributed to its originating block, and that block is
re-run **synchronously** through ``executor.run``, i.e. through the
existing retry / OOM-split / pad-fallback machinery
(``docs/resilience.md``). Counted in ``pipeline.sync_fallbacks``.

``TFT_PIPELINE_DEPTH=1`` (or a single-block frame) restores the serial
path exactly: the ops' unchanged per-block function runs in a plain loop,
bit-identical to the pre-pipeline engine.

Preemption composition (``docs/serving.md``): when the serving layer
activates a :class:`~.preempt.PreemptionScope` around a forcing, the
stream polls it between submits — a cancel raises a classified
``QueryCancelled`` at the boundary; a preempt drains the in-flight
window, parks the drained prefix as a ``QueryCheckpoint``
(``memory/checkpoint.py``), and raises ``QueryPreempted`` for the
scheduler to re-queue. On resume the parked outputs restore and only
the remaining blocks re-dispatch (``pipeline.resumed_blocks``). With no
scope active the cost is one contextvar read per stream.

Multi-query composition: when the serving layer installs a
:class:`SlotPool` (``docs/serving.md``), every pipelined stream leases
one pool slot per in-flight block, bounding TOTAL cross-query block
concurrency instead of per-stream depth only; waits are counted in
``pipeline.slot_waits`` and recorded as ``slot_wait`` trace events. With
no pool installed (the default, anything outside a serving scheduler)
the leasing path is a single ``None`` check.

Observability: ``pipeline.submitted`` / ``pipeline.drained`` /
``pipeline.sync_fallbacks`` are always-on counters
(``utils.tracing.counters``); window occupancy is sampled into the
``pipeline.occupancy`` gauge and submit/drain run inside
``pipeline.submit`` / ``pipeline.drain`` spans when tracing is enabled.
With an active :class:`~..observability.QueryTrace` each block also
records typed ``block_submit``/``block_compute``/``block_drain`` events
on its in-flight slot's track plus per-submit occupancy samples — the
chrome-trace export (``docs/observability.md``) renders one track per
slot so depth tuning becomes visual.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Callable, List, Optional, Sequence, TypeVar

from ..observability import baseline as _baseline
from ..observability import device as _obs_device
from ..observability import events as _obs
from ..resilience import check_deadline, env_int
from ..resilience import invariants as _invariants
from ..utils.logging import get_logger
from ..utils.tracing import counters, gauge, span
from . import preempt as _preempt

__all__ = ["DEFAULT_DEPTH", "pipeline_depth", "stream_depth", "submit",
           "run_pipelined", "ReadyResult", "PipelinedExecutor",
           "SlotPool", "install_slot_pool", "current_slot_pool",
           "last_occupancy"]

_log = get_logger("engine.pipeline")

DEFAULT_DEPTH = 3

B = TypeVar("B")
R = TypeVar("R")


class SlotPool:
    """A process-wide budget of in-flight pipeline blocks, leased by
    concurrent query streams.

    Without a pool, N queries racing into the engine each open their own
    ``TFT_PIPELINE_DEPTH`` window — total in-flight memory scales with
    whoever shows up. The serving layer installs one pool sized to the
    machine (``serve.QueryScheduler``: workers x depth by default,
    ``TFT_SERVE_SLOTS`` overrides) and every pipelined stream leases a
    slot per in-flight block from it, so cross-query block concurrency is
    bounded globally, not per caller.

    Deadlock-free by construction: a stream that cannot lease drains its
    OWN oldest in-flight block first (freeing a slot it holds), and
    blocks only when it holds none — at which point every held slot
    belongs to a stream that is computing and will drain. Waiting streams
    honor the ambient resilience deadline.
    """

    __slots__ = ("slots", "_sem", "_leased", "_lock")

    def __init__(self, slots: int):
        if slots < 1:
            raise ValueError(f"SlotPool needs >= 1 slot, got {slots}")
        self.slots = int(slots)
        self._sem = threading.Semaphore(self.slots)
        # explicit lease count alongside the semaphore: the invariant
        # auditors (resilience/invariants.py) need to READ the balance
        # — a Semaphore's internal value is not inspectable — so every
        # acquire/release keeps this mirror
        self._leased = 0
        self._lock = threading.Lock()

    def try_acquire(self, timeout: float = 0.0) -> bool:
        if timeout <= 0:
            got = self._sem.acquire(blocking=False)
        else:
            got = self._sem.acquire(timeout=timeout)
        if got:
            with self._lock:
                self._leased += 1
        return got

    def release(self) -> None:
        with self._lock:
            self._leased -= 1
        self._sem.release()

    def leased(self) -> int:
        """Currently-outstanding leases (negative = a release without
        an acquire; the auditors flag both directions)."""
        with self._lock:
            return self._leased


_slot_pool: Optional[SlotPool] = None

# mean in-flight window occupancy of the most recently COMPLETED
# stream in this process (best-effort: concurrent streams overwrite
# each other; None before any stream and after a serial/depth-1 run).
# The adaptive planner's stream-feedback records read it right after
# their own forcing's stream completes (docs/adaptive.md), where the
# most-recent stream IS that forcing's on the uncontended path.
_last_occupancy: Optional[float] = None


def last_occupancy() -> Optional[float]:
    return _last_occupancy


def install_slot_pool(pool: Optional[SlotPool]) -> Optional[SlotPool]:
    """Install (or clear with ``None``) the process slot pool; returns
    the previous pool so callers can restore it. Streams snapshot the
    pool at entry, so a swap mid-stream never mismatches a lease."""
    global _slot_pool
    prev = _slot_pool
    _slot_pool = pool
    return prev


def current_slot_pool() -> Optional[SlotPool]:
    return _slot_pool


def pipeline_depth(explicit: Optional[int] = None) -> int:
    """The in-flight block window: ``explicit`` if given, else
    ``TFT_PIPELINE_DEPTH`` (default 3), floored at 1 (depth 1 = serial).

    Re-read per stream forcing — the knob is cheap and tests/benchmarks
    flip it between runs.
    """
    d = explicit if explicit is not None \
        else env_int("TFT_PIPELINE_DEPTH", DEFAULT_DEPTH)
    return max(1, d)


class ReadyResult:
    """A pre-computed pending: drains to a value already in hand.

    The generic fallback for executors without a ``submit`` half (e.g.
    :class:`~..engine.executor.PaddingExecutor`):
    the block runs eagerly — through the executor's full resilient path —
    at submit time, so the stream stays correct (no overlap, same
    semantics).
    """

    __slots__ = ("_value",)

    def __init__(self, value):
        self._value = value

    def drain(self):
        return self._value


def stream_depth(executor) -> Optional[int]:
    """The depth an executor pins for op-internal streams: a
    :class:`PipelinedExecutor` carries its own, anything else defers to
    ``TFT_PIPELINE_DEPTH`` (None)."""
    if isinstance(executor, PipelinedExecutor):
        return executor.depth
    return None


def submit(executor, comp, arrays, pad_ok: bool = True):
    """Submit one block on ``executor``: its async ``submit`` half when it
    has one, else the eager :class:`ReadyResult` fallback. Returns an
    object with a ``drain()`` method."""
    sub = getattr(executor, "submit", None)
    if sub is not None:
        return sub(comp, arrays, pad_ok=pad_ok)
    return ReadyResult(executor.run(comp, arrays, pad_ok=pad_ok))


def run_pipelined(blocks: Sequence[B],
                  serial_fn: Callable[[B], R],
                  submit_fn: Callable[[B], object],
                  drain_fn: Callable[[object, B], R],
                  depth: Optional[int] = None,
                  tag: Optional[str] = None) -> List[R]:
    """Run a block stream through a bounded in-flight window, in order.

    ``serial_fn(b)`` is the unchanged serial per-block function — used
    verbatim when the effective depth is 1 or the stream has at most one
    block, so ``TFT_PIPELINE_DEPTH=1`` IS the pre-pipeline engine.
    ``submit_fn(b)`` starts a block (returns a pending with ``drain()``,
    or any finished value the paired ``drain_fn`` recognizes);
    ``drain_fn(pending, b)`` completes it. Drains are strictly FIFO:
    results come back in block order. ``tag`` names the logical stream
    for preemption checkpoints (``engine/preempt.py``): a checkpoint
    parked here only ever restores into a stream with the SAME tag and
    block count — a resume whose execution path changed (fused plan
    fell back per-op, say) discards and re-runs instead. Untagged
    streams (``None``) are still preemptible but never checkpoint:
    with no stable identity, a full re-run is the only safe resume.
    """
    global _last_occupancy
    blocks = list(blocks)
    d = pipeline_depth(depth)
    trace = _obs.current_trace()
    scope = _preempt.current_scope()
    start = 0
    restored: Optional[List[R]] = None
    if scope is not None:
        if tag is not None:
            # disambiguate same-tag sibling streams within one run
            # attempt: the Nth same-tag stream parked only ever
            # restores into the Nth same-tag stream of the resume
            tag = f"{tag}#{scope.stream_ordinal(tag)}"
        # resume: a parked checkpoint restores the drained prefix and
        # the stream re-dispatches only the remaining blocks
        restored = _preempt.resume_stream(scope, len(blocks), tag)
        if restored:
            start = len(restored)
            # the restored prefix's filter counts were noted in the
            # PRIOR attempt's row ledger: this attempt's can no longer
            # balance, so it is voided rather than faked
            _invariants.taint_rows(
                f"resumed {start} restored block(s) of stream {tag!r}")
    if d <= 1 or len(blocks) - start <= 1:
        _last_occupancy = None  # a serial run has no window to measure
        if trace is None and scope is None:
            return [serial_fn(b) for b in blocks]
        out0: List[R] = list(restored or ())
        for i in range(start, len(blocks)):
            b = blocks[i]
            if scope is not None and _preempt.boundary(scope, i > start):
                _preempt.park(scope, out0, len(blocks), tag)  # raises
            if trace is None:
                out0.append(serial_fn(b))
                continue
            rows, nbytes = _obs.block_meta(b)
            t0 = trace.clock()
            r = serial_fn(b)
            rows_out, _ = _obs.block_meta(r)
            trace.add("block_run", name=f"block {i}", ts=t0,
                      dur=trace.clock() - t0, track=1, block=i,
                      rows=rows, bytes=nbytes, rows_out=rows_out)
            out0.append(r)
        return out0

    out: List[R] = list(restored or ())
    # window entries: (pending, block, index, submit_end_ts, leased)
    window: "deque" = deque()
    pool = _slot_pool  # snapshot: a mid-stream swap must not mismatch
    occ_sum = 0
    occ_n = 0

    def drain_one() -> None:
        pending, b, i, t_sub, leased = window.popleft()
        slot = i % d + 1
        try:
            t0 = 0.0
            if trace is not None:
                t0 = trace.clock()
                # the block's in-flight residency: submit end -> drain
                # start
                trace.add("block_compute", name=f"compute b{i}", ts=t_sub,
                          dur=max(t0 - t_sub, 0.0), track=slot, block=i)
            with span("pipeline.drain"):
                result = drain_fn(pending, b)
            out.append(result)
            counters.inc("pipeline.drained")
            if trace is not None:
                rows_out, _ = _obs.block_meta(result)
                trace.add("block_drain", name=f"drain b{i}", ts=t0,
                          dur=trace.clock() - t0, track=slot, block=i,
                          rows_out=rows_out)
                # HBM watermark around the drain (latched no-op on
                # backends without memory_stats, e.g. CPU)
                _obs_device.sample(trace, "block_drain")
        finally:
            if leased:
                pool.release()

    def lease_slot() -> bool:
        """One slot from the pool, draining our own window to make room
        when the pool is exhausted (never deadlocks: a stream holding no
        slots only waits on streams that are computing)."""
        if pool is None:
            return False
        if pool.try_acquire():
            return True
        counters.inc("pipeline.slot_waits")
        t0 = trace.clock() if trace is not None else 0.0
        # measured always-on (contended path only): the sentinel's cost
        # vector attributes this wait in seconds, not just a count
        w0 = time.perf_counter()
        while not pool.try_acquire(timeout=0.05):
            check_deadline("pipeline.slot")
            if window:
                drain_one()
        _baseline.note_wait(time.perf_counter() - w0)
        if trace is not None:
            trace.add("slot_wait", ts=t0, dur=trace.clock() - t0)
        return True

    try:
        for i in range(start, len(blocks)):
            b = blocks[i]
            if scope is not None and _preempt.boundary(scope, i > start):
                # preempt: finish what is in flight (never kill a
                # dispatched block), park the drained prefix, raise
                while window:
                    drain_one()
                _preempt.park(scope, out, len(blocks), tag)  # raises
            leased = lease_slot()
            # everything between the lease and the window.append is
            # guarded: a failure anywhere here (submit, or even a trace
            # hook) would otherwise strand the lease outside the window
            try:
                t0 = 0.0
                rows = nbytes = None
                if trace is not None:
                    rows, nbytes = _obs.block_meta(b)
                    t0 = trace.clock()
                with span("pipeline.submit"):
                    pending = submit_fn(b)
                t1 = trace.clock() if trace is not None else 0.0
            except BaseException:
                if leased:  # never made it into the window
                    pool.release()
                raise
            window.append((pending, b, i, t1, leased))
            counters.inc("pipeline.submitted")
            occ_sum += len(window)
            occ_n += 1
            gauge("pipeline.occupancy", len(window))
            if trace is not None:
                trace.add("block_submit", name=f"submit b{i}", ts=t0,
                          dur=t1 - t0, track=i % d + 1, block=i, rows=rows,
                          bytes=nbytes)
                trace.add("occupancy", value=len(window))
            if len(window) >= d:
                drain_one()
        while window:
            drain_one()
    finally:
        # an error unwinding mid-stream must not leak the undrained
        # entries' leases (their async work finishes on its own)
        while window:
            entry = window.popleft()
            if entry[4]:
                pool.release()
        if occ_n:
            _last_occupancy = occ_sum / occ_n
    return out


class PipelinedExecutor:
    """A block-stream runner bound to an inner executor and a depth.

    Thin orchestration handle over :func:`run_pipelined` /
    :func:`submit` for callers outside ``engine.ops`` that want the same
    windowed execution over their own block streams::

        pex = PipelinedExecutor(default_executor(), depth=4)
        results = pex.map(block_arrays, comp)          # ordered host dicts

    ``run`` delegates to the inner executor unchanged, so a
    ``PipelinedExecutor`` is accepted anywhere an ``executor=`` argument
    is (the six ops pipeline their own streams internally; handing them a
    ``PipelinedExecutor`` additionally pins the depth without consulting
    ``TFT_PIPELINE_DEPTH``).
    """

    def __init__(self, inner, depth: Optional[int] = None):
        self.inner = inner
        self._depth = depth

    @property
    def depth(self) -> int:
        return pipeline_depth(self._depth)

    @property
    def pad_rows(self) -> bool:
        return getattr(self.inner, "pad_rows", False)

    @property
    def compile_count(self) -> int:
        return self.inner.compile_count

    def run(self, comp, arrays, pad_ok: bool = True):
        return self.inner.run(comp, arrays, pad_ok=pad_ok)

    def submit(self, comp, arrays, pad_ok: bool = True):
        return submit(self.inner, comp, arrays, pad_ok=pad_ok)

    def map(self, block_arrays: Sequence, comp,
            pad_ok: bool = True) -> List:
        """Run ``comp`` over a sequence of input mappings, pipelined,
        results in input order."""
        return run_pipelined(
            block_arrays,
            lambda a: self.inner.run(comp, a, pad_ok=pad_ok),
            lambda a: self.submit(comp, a, pad_ok=pad_ok),
            lambda p, a: p.drain(),
            depth=self.depth)

    def clear(self):
        self.inner.clear()
