"""The Discovery Space: ``D = (P, Ω) ⊗ A`` (paper §III-B, §III-C).

The class below is the concrete data model of the paper's Fig. 3: it is
composed of the configuration probability space, the Action space, and is
backed by the common-context :class:`~repro.core.store.SampleStore` for the
sample store + sampling records.

TRACE characteristics, and where they live:

* **Encapsulated** — :meth:`sample` validates configurations against Ω and
  only runs/records experiments in A; :meth:`read` only returns values whose
  provenance is in A.
* **Actionable** — the space itself knows how to obtain measurements
  (:meth:`sample` with no stored data runs the experiments) and what remains
  to measure (:meth:`remaining_configurations`).
* **Time-Resolved** — every sample event appends to the per-operation
  sampling record with a sequence number and timestamp
  (:meth:`timeseries`).
* **Common Context** — all values go through the shared store in the generic
  schema; nothing is kept privately on the object (operations are stateless).
  :meth:`sample_batch` exploits this: because the store is the only state,
  experiment execution fans out over a worker pool — and over independent
  worker *processes* sharing one database (§III-D) — with per-cell
  measurement claims guaranteeing each (configuration, experiment) is
  measured exactly once no matter how many investigators race for it.
* **Reconcilable** — data written by *another* space for the same
  configuration is invisible here until *this* space's :meth:`sample`
  generates that configuration; at that point the stored values are reused
  rather than re-measured (paper §III-C4, and §III-C5's
  reuse-once-sampled default).
"""

from __future__ import annotations

import uuid
from concurrent.futures import Executor
from dataclasses import dataclass
from typing import Iterator, Mapping, Optional, Sequence, Union

import numpy as np

from . import tracing
from .actions import ActionSpace, Experiment, MeasurementError, SurrogateExperiment
from .clock import Clock
from .entities import Configuration, Sample, content_hash
from .execution import (AutoscalePolicy, ExecutionBackend, ExecutionContext,
                        WorkItem, make_backend)
from .space import ProbabilitySpace
from .store import RecordEntry, SampleStore, StoreBackend

__all__ = ["DiscoverySpace", "BatchResult"]


@dataclass
class BatchResult:
    """Outcome of one slot of a :meth:`DiscoverySpace.sample_batch` call.

    ``action`` is the sampling-record tag (``measured`` / ``reused`` /
    ``predicted`` / ``failed``); ``sample`` is None iff the measurement
    failed, in which case ``error`` holds the :class:`MeasurementError`.
    """

    configuration: Configuration
    sample: Optional[Sample]
    action: str
    error: Optional[MeasurementError] = None

    @property
    def ok(self) -> bool:
        return self.sample is not None


class DiscoverySpace:
    """A configuration search study's data model: space ⊗ actions, stored."""

    def __init__(
        self,
        space: ProbabilitySpace,
        actions: ActionSpace,
        store: Optional[StoreBackend] = None,
        space_id: Optional[str] = None,
        claim_timeout_s: float = 60.0,
        lease_s: float = 15.0,
        clock: Optional[Clock] = None,
        autoscale: Optional[AutoscalePolicy] = None,
        meta: Optional[Mapping] = None,
    ):
        self.space = space
        self.actions = actions
        self.store = store if store is not None else SampleStore(":memory:")
        # How long a concurrent investigator's in-flight measurement of the
        # same cell is waited for before its claim is presumed abandoned.
        # Size this to the action space: it should exceed the slowest
        # experiment's expected duration (cloud deployments: minutes).
        self.claim_timeout_s = claim_timeout_s
        # Heartbeat-lease horizon for owners that renew (queue/process
        # workers): their death is detected within ~lease_s even when
        # claim_timeout_s is minutes.  Compared across hosts' wall clocks —
        # on multi-machine deployments size it above the worst expected
        # clock skew (see ExecutionContext).
        self.lease_s = lease_s
        # Injectable time source for every timing decision (leases, sweeps,
        # autoscaling); defaults to the store's clock so one FakeClock at
        # the store flows through the whole stack.
        self.clock = clock if clock is not None else self.store.clock
        # Fleet-sizing policy applied by autoscaling backends (None => each
        # backend's default).
        self.autoscale = autoscale
        # Identity: the space is defined by (Ω, A).  Two DiscoverySpace objects
        # over the same store with the same (Ω, A) are views of the same study.
        self.space_id = space_id or content_hash(
            {"space": space.digest, "actions": actions.digest}
        )
        # Catalog registration: the Ω-only digest + entity metadata are what
        # SpaceCatalog.find_related matches on — a target investigation can
        # discover this space as a transfer source without reconstructing
        # its (code-only) experiments.  Caller-supplied ``meta`` (e.g. a
        # workload family's identity block) is merged in first; the reserved
        # keys below always reflect this space's actual (Ω, A).
        self.extra_meta = dict(meta) if meta else {}
        registered_meta = dict(self.extra_meta)
        registered_meta.update({
            "dimensions": list(space.names),
            "size": space.size if space.finite else None,
            "properties": list(actions.observed_properties),
        })
        self.store.register_space(
            self.space_id, space.to_json(), actions.identifiers,
            space_digest=space.digest,
            meta=registered_meta,
        )
        # Stale-claim GC pacing: the batch/pipelined drivers sweep at most
        # once per lease interval — and the FIRST call always sweeps, so
        # short-lived runs (CI smoke, --quick benches) get at least one GC
        # pass instead of skipping it entirely (see _maybe_sweep_claims).
        self._last_claim_sweep: Optional[float] = None

    # -------------------------------------------------------------- execution

    def execution_context(self) -> ExecutionContext:
        """What a backend needs to execute this space's measurements."""
        return ExecutionContext(
            store=self.store,
            experiments=self.actions.experiments,
            claim_timeout_s=self.claim_timeout_s,
            space_id=self.space_id,
            lease_s=self.lease_s,
            clock=self.clock,
            autoscale=self.autoscale,
        )

    def execution_backend(
        self,
        backend: Union[ExecutionBackend, str, None] = None,
        workers: int = 1,
        executor: Optional[Executor] = None,
    ) -> ExecutionBackend:
        """Resolve an execution backend bound to this space.

        ``backend`` is an :class:`ExecutionBackend` instance (used as-is; the
        caller keeps ownership), one of ``"serial" | "thread" | "process" |
        "queue"``, or None — then the legacy ``workers``/``executor`` knobs
        pick serial vs thread execution, matching the pre-backend engine.
        """
        return make_backend(backend, self.execution_context(),
                            workers=workers, executor=executor)

    def _maybe_sweep_claims(self) -> None:
        """Periodic stale-claim GC (ROADMAP item): reap claims from crashed
        investigators up front instead of making every waiter burn its full
        timeout.  Lease-based — a heartbeating owner is never reaped; a dead
        one is gone within its lease — and paced off the *injected* clock at
        one sweep per lease interval, with the first call sweeping
        unconditionally (wall-clock pacing used to skip GC entirely on runs
        shorter than the claim timeout, e.g. ``--quick`` CI benches)."""
        now = self.clock.monotonic()
        if (self._last_claim_sweep is None
                or now - self._last_claim_sweep >= self.lease_s):
            self._last_claim_sweep = now
            self.store.sweep_stale_claims()

    # ------------------------------------------------------------------ sample

    def sample(
        self,
        configuration: Optional[Configuration] = None,
        rng: Optional[np.random.Generator] = None,
        operation_id: str = "adhoc",
    ) -> Sample:
        """Sample one point of D (paper Fig. 3 right-hand flow).

        If ``configuration`` is None, draw from (P, Ω).  Then, for every
        experiment in A: if the common context already holds that
        experiment's values for this configuration, *reuse* them; otherwise
        *measure* (execute the experiment) and store the results.  Either
        way the event is appended to this space's sampling record — this is
        the only way data becomes visible to :meth:`read`.
        """
        if configuration is None:
            rng = rng if rng is not None else np.random.default_rng()
            configuration = self.space.sample_configuration(rng)
        result = self.sample_batch([configuration], operation_id=operation_id)[0]
        if not result.ok:
            raise result.error
        return result.sample

    def sample_batch(
        self,
        configurations: Sequence[Configuration],
        operation_id: str = "adhoc",
        workers: int = 1,
        executor: Optional[Executor] = None,
        backend: Union[ExecutionBackend, str, None] = None,
        priorities: Optional[Sequence[float]] = None,
    ) -> list:
        """Sample a batch of points, fanning experiment execution out over an
        execution backend (paper §III-D: distributed investigation through
        the shared sample store).

        Semantics are *serial-equivalent*: the reconciled sample set and the
        sampling record are identical to sampling the same configurations one
        by one — duplicates within the batch are measured once and recorded
        as ``reused`` thereafter, reuse/measure decisions go through the
        common context, and record events are appended in submission order
        (atomic per-operation ``seq`` allocation makes this safe alongside
        concurrent writers in other threads or processes).

        Only experiment execution is parallel: each distinct configuration is
        one :class:`~repro.core.execution.WorkItem` on the resolved backend —
        ``backend`` names one of ``serial | thread | process | queue`` or is
        a ready :class:`~repro.core.execution.ExecutionBackend`; with None
        the legacy ``workers``/``executor`` knobs pick serial vs thread
        execution.  ``priorities`` (optional, one score per configuration —
        the optimizer's acquisition) rides on the work items: scheduling
        backends measure best-first, while results, records, and the
        reconciled sample set stay in submission order regardless.  Failed
        measurements do not abort the batch; they yield a
        :class:`BatchResult` with ``action='failed'`` carrying the error.
        Crash-isolating backends (process, queue) also contain *unexpected*
        experiment errors and worker deaths to their own slot as ``failed``
        results, instead of re-raising from the batch.
        """
        configs = list(configurations)
        if not configs:
            return []
        if priorities is not None and len(priorities) != len(configs):
            raise ValueError(
                f"priorities must match configurations: "
                f"{len(priorities)} != {len(configs)}")
        # Encapsulated: reject configurations outside Ω before any work runs.
        for config in configs:
            self.space.validate(config)
        self._maybe_sweep_claims()
        # one interning transaction/round-trip for the whole batch
        with tracing.span("store.intern"):
            digests = self.store.put_configurations(configs)

        # Duplicates measure once: the first slot of each digest does the
        # experiment work, later slots transparently reuse (§III-C5).
        first_slot: dict = {}
        for i, digest in enumerate(digests):
            first_slot.setdefault(digest, i)
        unique = [i for i, digest in enumerate(digests) if first_slot[digest] == i]

        owned = not isinstance(backend, ExecutionBackend)
        engine = self.execution_backend(backend, workers=workers,
                                        executor=executor)
        try:
            for i in unique:
                engine.submit(WorkItem(
                    configs[i], digests[i], i,
                    priority=(float(priorities[i]) if priorities is not None
                              else 0.0)))
            completed = engine.drain()
        finally:
            if owned:
                engine.close()
        by_digest = {digests[r.item.tag]: (r.action, r.error)
                     for r in completed}

        # Time-Resolved: record events in submission order, one transaction.
        # Like the serial loop, a slot that crashed with a non-measurement
        # error gets no record; every other slot's event still lands before
        # the error propagates (its values are already durable).
        results, events, recorded = [], [], []
        crash: Optional[BaseException] = None
        for i, (config, digest) in enumerate(zip(configs, digests)):
            action, err = by_digest[digest]
            if action == "crashed":
                crash = crash if crash is not None else err
                continue
            if err is None and first_slot[digest] != i:
                action = "reused"
            events.append((digest, action))
            recorded.append(digest)
            results.append(BatchResult(config, None, action, err))
        with tracing.span("store.record"):
            self.store.append_records(self.space_id, operation_id, events)
        if crash is not None:
            raise crash
        with tracing.span("store.read"):
            for result, digest in zip(results, recorded):
                if result.error is None:
                    result.sample = self._reconstruct(digest,
                                                      result.configuration)
        return results

    def record_result(self, configuration: Configuration, digest: str,
                      action: str, error: Optional[MeasurementError],
                      operation_id: str) -> BatchResult:
        """Record ONE completed work item and reconstruct its sample.

        The pipelined ask/tell driver's tell path: unlike
        :meth:`sample_batch`, which barriers and records a whole batch in
        submission order, the pipelined engine records each trial the moment
        its backend reports completion — so events land in completion order,
        which *is* the submission order when ``max_inflight=1``.
        """
        with tracing.span("store.record"):
            self.store.append_record(self.space_id, operation_id, digest,
                                     action)
        result = BatchResult(configuration, None, action, error)
        if error is None:
            with tracing.span("store.read"):
                result.sample = self._reconstruct(digest, configuration)
        return result

    # -------------------------------------------------------------------- read

    def read(self) -> list:
        """The reconciled sample set {x}: only configurations in *this*
        space's sampling record, with values restricted to *this* action
        space's experiments."""
        out = []
        for digest in self.store.sampled_digests(self.space_id):
            config = self.store.get_configuration(digest)
            if config is None:  # pragma: no cover - store corruption guard
                continue
            out.append(self._reconstruct(digest, config))
        return out

    def read_one(self, configuration: Configuration) -> Optional[Sample]:
        digest = configuration.digest
        # indexed point query — not a rebuild of the full sampled-digest set
        # (RSSC's surrogate lookup calls this once per predicted point)
        if not self.store.has_record(self.space_id, digest):
            return None
        return self._reconstruct(digest, configuration)

    def _reconstruct(self, digest: str, config: Configuration) -> Sample:
        values = self.store.get_values(digest, self.actions.identifiers)
        props = {}
        for v in values:
            # last write wins within an experiment; measured values win over
            # predictions for the same property
            if v.name in props and props[v.name].predicted is False and v.predicted:
                continue
            props[v.name] = v
        return Sample(configuration=config, properties=props)

    # ------------------------------------------------------------- time series

    def timeseries(self, operation_id: Optional[str] = None) -> list:
        """The time-resolved sampling record (TRACE: Time-Resolved)."""
        return self.store.records_for(self.space_id, operation_id)

    def begin_operation(self, kind: str, meta: Optional[Mapping] = None) -> str:
        operation_id = f"{kind}-{uuid.uuid4().hex[:12]}"
        self.store.register_operation(operation_id, self.space_id, kind, meta)
        return operation_id

    # -------------------------------------------------------------- actionable

    def sampled_configurations(self) -> list:
        return [self.store.get_configuration(d)
                for d in self.store.sampled_digests(self.space_id)]

    def remaining_configurations(self) -> Iterator[Configuration]:
        """What has not been sampled yet, and (via A) how to measure it."""
        seen = set(self.store.sampled_digests(self.space_id, include_failed=True))
        for config in self.space.all_configurations():
            if config.digest not in seen:
                yield config

    def count_sampled(self) -> int:
        return len(self.store.sampled_digests(self.space_id))

    def failure_summary(self) -> dict:
        """Failed trials in this space by actuation phase, with the
        provisioned cost they still charged:
        ``{phase: {"count": n, "cost": charged}}``.  Failed rows recorded
        before failure provenance existed surface under phase ``"unknown"``
        with zero cost (the backfill contract — see
        :meth:`~repro.core.store.base.StoreBackend.failure_summary`)."""
        return self.store.failure_summary(self.space_id)

    def failures_for(self, configuration: Configuration) -> list:
        """Full failure provenance rows recorded for one configuration,
        restricted to this space's experiments (zombie retries included —
        the history is honest even where the summary de-duplicates)."""
        rows = self.store.failures_for(configuration.digest)
        ids = set(self.actions.identifiers)
        return [r for r in rows if r.get("experiment_id") in ids]

    # ------------------------------------------------------------ derived space

    def with_predictor(self, surrogate: SurrogateExperiment) -> "DiscoverySpace":
        """``A*_pred``: a *new* Discovery Space whose action space adds a
        surrogate predictor (paper §IV-4).  Provenance is preserved — the
        surrogate's values are marked ``predicted``, the original experiments
        remain in the action space as *deferred* (apply-on-demand), and
        measured values win over predictions on read."""
        from .actions import DeferredExperiment  # local: avoid cycle at import

        deferred = tuple(
            e if e.deferred else DeferredExperiment(e) for e in self.actions.experiments
        )
        return DiscoverySpace(
            space=self.space,
            actions=ActionSpace(experiments=(surrogate,) + deferred),
            store=self.store,
            claim_timeout_s=self.claim_timeout_s,
            lease_s=self.lease_s,
            clock=self.clock,
            autoscale=self.autoscale,
            meta=self.extra_meta,
        )

    def related(self, mapping: Mapping[str, Mapping], actions: Optional[ActionSpace] = None,
                ) -> "DiscoverySpace":
        """Define a target space A* differing by a value mapping (paper §IV-1)."""
        return DiscoverySpace(
            space=self.space.map_values(mapping),
            actions=actions if actions is not None else self.actions,
            store=self.store,
            claim_timeout_s=self.claim_timeout_s,
            lease_s=self.lease_s,
            clock=self.clock,
            autoscale=self.autoscale,
            meta=self.extra_meta,
        )

    def __repr__(self) -> str:  # pragma: no cover
        size = self.space.size if self.space.finite else "inf"
        return (f"DiscoverySpace(id={self.space_id[:8]}, |Ω|={size}, "
                f"|A|={len(self.actions.experiments)}, sampled={self.count_sampled()})")
