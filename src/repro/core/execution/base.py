"""Execution-backend interface + the measurement state machine.

``run_measurement`` is the claim/wait/steal state machine that used to live
inline in ``DiscoverySpace.sample_batch``: it is the *only* code path through
which an experiment is ever executed, regardless of backend, so the
measure-once guarantee (paper §III-D) holds identically for a thread in the
investigator, a forked child process, and a remote worker on another host.

An :class:`ExecutionBackend` is a small asynchronous work pool:

* :meth:`~ExecutionBackend.submit` accepts a :class:`WorkItem` and returns
  immediately (work may be queued internally until a slot frees);
* :meth:`~ExecutionBackend.poll` returns the :class:`WorkResult` list
  completed since the last poll, in completion order — the pipelined
  ask/tell driver consumes this;
* :meth:`~ExecutionBackend.drain` blocks until everything outstanding has
  completed — the barrier-synchronized batch driver consumes this.
"""

from __future__ import annotations

import abc
import math
import os
import threading
import time
from dataclasses import dataclass, field
from typing import List, Optional, Sequence

from .. import tracing
from ..actions import FailureRecord, MeasurementError
from ..clock import Clock, SYSTEM_CLOCK
from ..entities import Configuration, PropertyValue

__all__ = ["WorkItem", "WorkResult", "ExecutionBackend", "ExecutionContext",
           "WorkerCrashError", "AutoscalePolicy", "LeasePacer",
           "run_measurement"]


class WorkerCrashError(MeasurementError):
    """A worker process died (or raised an unexpected error) mid-measurement.

    Subclasses :class:`MeasurementError` on purpose: under process isolation
    a crashing experiment poisons only its own slot — the driver records the
    slot as ``failed`` and the investigator survives, which is the point of
    running experiments out-of-process.
    """


@dataclass(frozen=True)
class WorkItem:
    """One unit of execution: measure all of A's experiments for a configuration.

    ``priority`` is the optimizer's acquisition score for the candidate
    (higher = more informative, 0.0 when unscored).  Queue-rendezvous
    workers pop best-first on it; in-process backends execute in submission
    order regardless, which keeps the serial engine byte-identical.
    """

    configuration: Configuration
    digest: str
    tag: int  # submission index; the driver maps results back through it
    priority: float = 0.0


@dataclass
class WorkResult:
    """Outcome of one work item: a sampling-record action tag + optional error.

    ``action`` follows the sampling-record vocabulary (``measured`` /
    ``reused`` / ``predicted`` / ``failed``) plus ``crashed`` for unexpected
    non-measurement errors, which in-process backends propagate to the caller
    exactly like the pre-backend engine did.
    """

    item: WorkItem
    action: str
    error: Optional[BaseException] = None


@dataclass(frozen=True)
class AutoscalePolicy:
    """When to grow and shrink a worker fleet (ExpoCloud-style).

    The policy is a pure function of observed queue state, so scaling
    decisions are deterministic and unit-testable: :meth:`target` maps a
    backlog (and optionally the EWMA per-item latency) to a desired fleet
    size between ``min_workers`` and ``max_workers``.

    * grow while the backlog per worker exceeds ``backlog_per_worker``;
    * with a ``drain_horizon_s`` and an observed per-item latency, size the
      fleet so the current backlog drains within the horizon
      (``backlog * latency / horizon`` workers) — latency-aware scaling;
    * shrink a worker that has been idle for ``idle_retire_s`` (paced off
      the injected clock, so tests drive retirement deterministically).
    """

    min_workers: int = 1
    max_workers: int = 4
    backlog_per_worker: float = 1.0
    idle_retire_s: float = 30.0
    ewma_alpha: float = 0.3
    drain_horizon_s: Optional[float] = None

    def __post_init__(self):
        if not 1 <= self.min_workers <= self.max_workers:
            raise ValueError(
                f"need 1 <= min_workers <= max_workers, got "
                f"{self.min_workers}..{self.max_workers}")

    def target(self, backlog: int, ewma_latency_s: Optional[float] = None) -> int:
        """Desired fleet size for a queue backlog (pure, deterministic)."""
        if self.drain_horizon_s and ewma_latency_s is not None:
            want = math.ceil(backlog * ewma_latency_s / self.drain_horizon_s)
        else:
            want = math.ceil(backlog / max(self.backlog_per_worker, 1e-9))
        return max(self.min_workers, min(self.max_workers, int(want)))

    def smooth(self, ewma: Optional[float], observed: float) -> float:
        """Fold one latency observation into the EWMA."""
        if ewma is None:
            return observed
        return (1.0 - self.ewma_alpha) * ewma + self.ewma_alpha * observed


@dataclass
class ExecutionContext:
    """What a backend needs to execute work: the common context and A.

    ``store`` is the investigator's handle; ``store_path`` is what
    out-of-process backends hand to children so they open their *own*
    connections (forked/spawned processes must never share a SQLite handle).

    ``claim_timeout_s`` is how long a waiter trusts *another* investigator's
    in-flight measurement (size it to the slowest experiment: minutes for
    cloud deployments); ``lease_s`` is the much shorter heartbeat lease a
    *live* owner keeps renewed — death detection is decoupled from
    experiment duration.  Lease expiry compares *wall-clock* timestamps
    written by different hosts, so on a multi-machine deployment ``lease_s``
    must exceed the heartbeat interval (lease_s/3) plus the worst expected
    clock skew between hosts (NTP drift); the default 15 s suits a single
    host or well-synced fleet — raise it (or QueueBackend's
    ``requeue_after_s`` grace) for loosely-synced clocks, trading slower
    death detection for no spurious reaping of live workers.  ``clock`` is
    the injectable time source every timing decision reads (leases, sweeps,
    autoscaling); ``autoscale``, when set, is the fleet-sizing policy
    backends that own workers apply.
    """

    store: "SampleStore"  # noqa: F821 - circular import avoided
    experiments: Sequence
    claim_timeout_s: float = 60.0
    space_id: str = ""
    lease_s: float = 15.0
    clock: Clock = field(default_factory=lambda: SYSTEM_CLOCK)
    autoscale: Optional[AutoscalePolicy] = None

    @property
    def store_path(self) -> str:
        return self.store.path


class LeasePacer:
    """Heartbeat thread: renews an owner's leases every ``interval_s``.

    Runs against real wall time (a daemon thread blocking on an Event), so a
    hung *process* stops beating and gets reaped — which is the point.
    ``max_age_s``, when set, is the hung-*thread* watchdog: rows older than
    it stop being renewed (see :meth:`SampleStore.renew_lease`), so a live
    process with a deadlocked measurement cannot keep its work claimed
    forever — workers pass their claim timeout.  Deterministic tests bypass
    the thread and call :meth:`beat` directly with a fake clock.  Idempotent
    start/stop; safe to use as a context manager around a measurement loop.
    """

    def __init__(self, store, owner: str, lease_s: float,
                 interval_s: Optional[float] = None,
                 max_age_s: Optional[float] = None):
        self._store = store
        self._owner = owner
        self._lease_s = lease_s
        self._interval_s = interval_s if interval_s is not None else lease_s / 3.0
        self._max_age_s = max_age_s
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def beat(self) -> int:
        """Renew now; returns the number of leases extended."""
        return self._store.renew_lease(self._owner, self._lease_s,
                                       max_age_s=self._max_age_s)

    def _run(self) -> None:
        while not self._stop.wait(self._interval_s):
            try:
                self.beat()
            except Exception:
                # a transient store error (e.g. "database is locked" past the
                # busy timeout under heavy contention) must not kill the
                # heartbeat for good — a silenced pacer makes a live worker
                # look dead, its items get re-executed, and its finishes are
                # rejected.  Skip the beat; the lease spans 3 intervals, so
                # one (or even two) missed beats never reap a live owner.
                continue

    def start(self) -> "LeasePacer":
        if self._thread is None:
            self._stop.clear()
            self._thread = threading.Thread(
                target=self._run, name=f"lease-pacer-{self._owner}", daemon=True)
            self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5.0)
            self._thread = None

    def __enter__(self) -> "LeasePacer":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()


class ExecutionBackend(abc.ABC):
    """Asynchronous work pool with submit/poll/drain semantics."""

    #: True when a crashing experiment is contained to its slot (the driver
    #: then never sees ``crashed`` results from this backend).
    isolates_crashes = False

    @abc.abstractmethod
    def submit(self, item: WorkItem) -> int:
        """Accept a work item; returns its tag.  Never blocks on execution."""

    @abc.abstractmethod
    def poll(self) -> List[WorkResult]:
        """Results completed since the last poll, in completion order."""

    @property
    @abc.abstractmethod
    def outstanding(self) -> int:
        """Submitted items whose results have not been returned yet."""

    def drain(self, timeout_s: Optional[float] = None) -> List[WorkResult]:
        """Block until every outstanding item completes; return all results.

        Raises :class:`TimeoutError` when ``timeout_s`` elapses first (e.g. a
        queue backend with no live workers) — results gathered so far are
        attached to the exception as ``partial``.
        """
        deadline = None if timeout_s is None else time.monotonic() + timeout_s
        out: List[WorkResult] = []
        pause = 0.001
        while self.outstanding:
            got = self.poll()
            if got:
                out.extend(got)
                pause = 0.001
                continue
            if deadline is not None and time.monotonic() > deadline:
                err = TimeoutError(
                    f"drain timed out with {self.outstanding} work items outstanding"
                )
                err.partial = out  # type: ignore[attr-defined]
                raise err
            time.sleep(pause)
            pause = min(pause * 2, 0.05)
        out.extend(self.poll())
        return out

    def close(self) -> None:
        """Release pool resources (idempotent)."""

    def __enter__(self) -> "ExecutionBackend":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def run_measurement(store, experiments, configuration: Configuration,
                    digest: str, claim_timeout_s: float = 60.0,
                    owner: Optional[str] = None,
                    lease_s: Optional[float] = None):
    """Measure every experiment in A for one configuration — the state machine.

    Returns ``(action, error)`` where ``action`` is the sampling-record tag.
    Reuse/measure decisions go through the common context; per-cell
    measurement claims arbitrate measure-once across every concurrent
    investigator (threads, processes, remote hosts) sharing ``store``:

    * win the claim → measure, land values, keep the claim (values make
      re-claiming moot);
    * lose it → wait for the winner's values; if the claim is released
      (owner failed) race to re-claim; if it goes stale (owner presumed
      dead) exactly one waiter steals it.

    ``lease_s`` sizes the claim's lease: heartbeating owners (queue/process
    workers running a :class:`LeasePacer`) pass their short heartbeat lease,
    non-heartbeating callers default to ``claim_timeout_s`` — the pre-lease
    reaping horizon.  Any failure between claiming and durably landing
    values releases the claim so waiters take over instead of stalling
    until their timeout.
    """
    owner = owner or str(os.getpid())
    claim_lease_s = lease_s if lease_s is not None else claim_timeout_s
    measured_any = reused_any = predicted_any = False
    try:
        for exp in experiments:
            with tracing.span("store.claim"):
                if store.has_values(digest, exp.identifier):
                    reused_any = True
                    continue
                if exp.deferred:
                    # apply-on-demand (A*_pred semantics, paper §IV-4)
                    continue
                who = f"{owner}:{threading.get_ident()}"
                claimed = store.claim_experiment(digest, exp.identifier, who,
                                                 lease_s=claim_lease_s)
                while not claimed:
                    # Another investigator (thread or process) is already
                    # measuring this cell: wait and reuse their result — the
                    # measure-once guarantee.  Measure ONLY after winning a
                    # claim.
                    if store.wait_for_values(digest, exp.identifier,
                                             timeout_s=claim_timeout_s):
                        break
                    if store.claim_exists(digest, exp.identifier):
                        # timed out on a still-standing claim: the owner is
                        # presumed dead — exactly one waiter steals it
                        claimed = store.steal_claim(
                            digest, exp.identifier, who,
                            older_than_s=claim_timeout_s)
                    else:
                        # owner failed and released: race for the re-claim
                        claimed = store.claim_experiment(
                            digest, exp.identifier, who,
                            lease_s=claim_lease_s)
            if not claimed:
                reused_any = True
                continue
            try:
                # the claim is held until values durably land: any failure in
                # measuring, converting, or storing them must free the cell
                # so waiters take over instead of stalling until their timeout
                with tracing.span("measure"):
                    values = exp.measure(configuration)
                with tracing.span("store.values"):
                    store.put_values(
                        digest,
                        [
                            PropertyValue(
                                name=k,
                                value=float(v),
                                experiment_id=exp.identifier,
                                predicted=exp.predicted,
                            )
                            for k, v in values.items()
                        ],
                    )
            except MeasurementError as err:
                # persist structured failure provenance BEFORE releasing the
                # claim: the lifecycle attaches (phase, reason, attempts,
                # cost) to the exception, monolithic experiments get a
                # synthesized "measure" record.  Provenance is best-effort —
                # a store hiccup here must not turn a failed trial into a
                # crashed slot (nor mask the claim release below).
                rec = getattr(err, "failure", None) \
                    or FailureRecord("measure", str(err))
                try:
                    store.record_failure(digest, exp.identifier, rec.phase,
                                         rec.reason, rec.attempts, rec.cost)
                except Exception:
                    pass
                store.release_claim(digest, exp.identifier)
                raise
            except BaseException:
                store.release_claim(digest, exp.identifier)
                raise
            if exp.predicted:
                predicted_any = True
            else:
                measured_any = True
    except MeasurementError as err:
        return "failed", err
    except BaseException as err:
        # unexpected (an experiment bug, a store error): poison only this
        # slot — in-process backends re-raise it from the driver, isolating
        # backends convert it to a failed slot
        return "crashed", err
    if measured_any:
        return "measured", None
    if predicted_any and not reused_any:
        return "predicted", None
    return "reused", None
