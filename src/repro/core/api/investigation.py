"""The Investigation: ONE engine behind every way this repo searches a space.

Four PRs of growth left four front doors — ``run_optimizer`` (solo
batched/pipelined ask/tell), ``Campaign`` (cooperative fleets), ``rssc_transfer``
(cross-space surrogates), and raw ``DiscoverySpace.sample_batch`` — exactly
the fragmentation the paper's formal problem description is meant to prevent.
:class:`Investigation` re-expresses them as *configurations* of one engine:

* a :class:`~repro.core.api.spec.InvestigationSpec` (declarative, JSON
  round-trippable) names the space, experiments, optimizer fleet, execution
  backend, budget, and transfer policy;
* :meth:`Investigation.plan` describes what would run — including which
  catalog spaces transfer could reuse — without paying for anything;
* :meth:`Investigation.run` executes: an optional §IV transfer stage
  (discover related measured spaces via the
  :class:`~repro.core.api.catalog.SpaceCatalog`, measure a representative
  sub-space, apply the r/p criteria, warm-start every member's history with
  surrogate predictions), then the search itself — the barriered batch loop
  for a solo ``batch_size`` run, or the
  :func:`~repro.core.campaign._drive_fleet` coordinator for pipelined and
  multi-optimizer runs;
* :meth:`Investigation.resume` re-enters a space whose store already holds
  history: everything recorded is folded into each member's model before the
  first ask, and re-proposals come back as free ``reused`` trials.

The legacy entrypoints are thin shims over this class —
``run_optimizer`` builds an Investigation from components and returns its
single member's run; ``Campaign.run`` hands its prebuilt members to one.
Their trajectories are regression-gated draw-for-draw, so the re-expression
is behaviour-preserving by test.
"""

from __future__ import annotations

from dataclasses import dataclass, field, replace
from typing import List, Optional, Sequence

import numpy as np

from .. import tracing
from ..campaign import MemberResult, _drive_fleet, _Member
from ..clustering import select_indices
from ..discovery import DiscoverySpace
from ..execution import ExecutionBackend, ProcessBackend, QueueBackend
from ..optimizers.base import (OptimizerRun, SearchAdapter, _StoppingRule,
                               as_scored)
from ..store import StoreBackend, open_store
from ..transfer import (PredictionQuality, TransferAssessment,
                        TransferCriteria, assess_transfer, prediction_quality)
from .catalog import SpaceCatalog
from .spec import InvestigationSpec, TransferSpec

__all__ = ["Investigation", "InvestigationPlan", "InvestigationResult",
           "TransferReport"]


@dataclass
class TransferReport:
    """What the §IV transfer stage found, measured, and folded."""

    applied: bool = False
    source_space_id: Optional[str] = None
    mapping: dict = field(default_factory=dict)
    assessment: Optional[TransferAssessment] = None
    n_source_samples: int = 0
    n_representatives: int = 0
    # paid work across EVERY candidate attempt, not just the one that
    # transferred: a rep pass that then failed the criteria still deployed
    # real experiments, and hiding that would bias warm-vs-cold comparisons
    n_rep_measured: int = 0
    n_rep_failed: int = 0
    n_warm_trials: int = 0       # entries folded into EACH member's history
    # the §IV-4 predict-remaining sweep (transfer.predict_remaining): how
    # many still-unmeasured configurations got surrogate predictions, and
    # the A*_pred space id they were recorded under (None = sweep not run)
    n_predicted: int = 0
    predicted_space_id: Optional[str] = None
    operation_id: Optional[str] = None
    #: digest -> surrogate-predicted value for warm entries that were NOT
    #: measured during the rep pass: the out-of-sample predictions that
    #: prediction-quality scoring pairs against later real measurements.
    warm_predictions: dict = field(default_factory=dict, repr=False)
    #: per-candidate outcome, in the order sources were tried
    attempts: list = field(default_factory=list)

    @property
    def paid(self) -> int:
        return self.n_rep_measured + self.n_rep_failed

    def summary(self) -> dict:
        out = {
            "applied": self.applied,
            "source_space_id": self.source_space_id,
            "n_source_samples": self.n_source_samples,
            "n_representatives": self.n_representatives,
            "rep_measurements_paid": self.paid,
            "warm_trials_per_member": self.n_warm_trials,
            "predicted": self.n_predicted,
            "predicted_space_id": self.predicted_space_id,
            "attempts": list(self.attempts),
        }
        if self.assessment is not None:
            out["criteria"] = self.assessment.summary()
        return out


@dataclass
class InvestigationPlan:
    """The dry-run answer: what :meth:`Investigation.run` would do."""

    name: str
    space_id: str
    engine: str                  # 'batched' | 'pipelined' | 'campaign'
    metric: str
    mode: str
    members: list                # labels, in fleet order
    backend: Optional[str]
    workers: int
    batch_size: int
    max_inflight: Optional[int]
    budget: dict
    share_history: bool
    warm_start: bool
    transfer_enabled: bool
    transfer_predict_remaining: bool = False
    transfer_candidates: list = field(default_factory=list)
    constraints: list = field(default_factory=list)  # SLA bound descriptions
    #: prior failed trials already recorded in the space, by lifecycle phase:
    #: ``{phase: {"count": n, "cost": charged}}`` (legacy rows → "unknown")
    failures: dict = field(default_factory=dict)

    def describe(self) -> str:
        objective = f"{self.mode} {self.metric}"
        if self.constraints:
            objective += "  s.t. " + ", ".join(self.constraints)
        lines = [
            f"investigation {self.name!r} on space {self.space_id[:12]}…",
            f"  objective : {objective}",
            f"  engine    : {self.engine} (backend="
            f"{self.backend or 'default'}, workers={self.workers}, "
            f"batch_size={self.batch_size}, max_inflight={self.max_inflight})",
            f"  members   : {', '.join(self.members)}",
            f"  budget    : max_trials={self.budget['max_trials']}/member, "
            f"patience={self.budget['patience']}, "
            f"min_trials={self.budget['min_trials']}",
            f"  sharing   : share_history={self.share_history}, "
            f"warm_start={self.warm_start}",
        ]
        if self.failures:
            parts = [f"{phase}={s['count']} (${s['cost']:.4g})"
                     for phase, s in sorted(self.failures.items())]
            lines.append(f"  failures  : {sum(s['count'] for s in self.failures.values())}"
                         f" prior failed trial(s) — {', '.join(parts)}")
        if not self.transfer_enabled:
            lines.append("  transfer  : disabled")
        elif not self.transfer_candidates:
            lines.append("  transfer  : enabled — no related measured space "
                         "in the catalog (search runs cold)")
        else:
            sweep = (" (+ predict-remaining sweep)"
                     if self.transfer_predict_remaining else "")
            lines.append(f"  transfer  : enabled{sweep} — "
                         f"{len(self.transfer_candidates)} candidate "
                         f"source(s):")
            for c in self.transfer_candidates:
                mapped = (f", renames {c['mapped_dimensions']}"
                          if c["mapped_dimensions"] else "")
                lines.append(f"    - {c['space_id'][:12]}… overlap="
                             f"{c['overlap']} measured={c['measured']}"
                             f"{mapped}")
        return "\n".join(lines)


@dataclass
class InvestigationResult:
    """Outcome of one :meth:`Investigation.run`."""

    name: str
    space_id: str
    metric: str
    mode: str
    engine: str
    members: List[MemberResult]
    #: ``(member_label, Trial)`` in tell order — the fleet event trace
    events: list = field(default_factory=list)
    transfer: Optional[TransferReport] = None
    #: failed trials in the space by lifecycle phase, with the provisioned
    #: cost they still charged: ``{phase: {"count": n, "cost": charged}}``.
    #: Rows that predate failure provenance surface as phase "unknown".
    failures: dict = field(default_factory=dict)

    @property
    def best(self):
        """Best *feasible* trial (SLA violators are real measurements but
        never incumbents; warm predictions never appear in events)."""
        sign = 1.0 if self.mode == "min" else -1.0
        valued = [t for _, t in self.events
                  if t.value is not None and t.feasible is not False]
        if not valued:
            return None
        return min(valued, key=lambda t: sign * t.value)

    @property
    def num_infeasible(self) -> int:
        return sum(1 for _, t in self.events if t.feasible is False)

    @property
    def num_trials(self) -> int:
        return len(self.events)

    @property
    def num_measured(self) -> int:
        return sum(1 for _, t in self.events if t.action == "measured")

    @property
    def paid_measurements(self) -> int:
        """Everything that cost a real deployment: measured + failed search
        trials, plus the transfer stage's representative measurements."""
        paid = sum(1 for _, t in self.events
                   if t.action in ("measured", "failed"))
        if self.transfer is not None:
            paid += self.transfer.paid
        return paid

    def prediction_quality(self) -> Optional[PredictionQuality]:
        """§V-B2 metrics of the transfer surrogate, scored OUT of sample:
        each warm prediction is paired with the real value the search later
        measured for the same configuration.  None when transfer was not
        applied or fewer than two predictions were ever verified.  The
        ``%savings`` field reports the §IV sampling-cost analogue — the
        fraction of the warm-covered target history that needed no real
        measurement."""
        if self.transfer is None or not self.transfer.applied:
            return None
        preds = self.transfer.warm_predictions
        pairs = {}
        for _, t in self.events:
            d = t.configuration.digest
            if t.value is not None and t.action == "measured" and d in preds:
                pairs[d] = (preds[d], t.value)  # last measurement wins
        if len(pairs) < 2:
            return None
        predicted = np.array([p for p, _ in pairs.values()])
        actual = np.array([a for _, a in pairs.values()])
        q = prediction_quality(predicted, actual, n_measured=0,
                               mode=self.mode)
        covered = self.transfer.n_warm_trials
        paid = self.transfer.paid
        savings = 1.0 - paid / max(covered + paid, 1)
        return replace(q, savings_pct=savings)

    def measurements_to_best(self) -> Optional[int]:
        """Paid measurements spent until the final best value first landed
        (transfer representative measurements included — they were paid)."""
        best = self.best
        if best is None:
            return None
        paid = self.transfer.paid if self.transfer is not None else 0
        for _, t in self.events:
            if t.action in ("measured", "failed"):
                paid += 1
            if t.value is not None and t.feasible is not False \
                    and t.value == best.value:
                return paid
        return paid  # pragma: no cover - best always appears in events

    def summary(self) -> dict:
        best = self.best
        q = self.prediction_quality()
        return {
            "name": self.name,
            "space_id": self.space_id,
            "engine": self.engine,
            "metric": self.metric,
            "mode": self.mode,
            "trials": self.num_trials,
            "measured": self.num_measured,
            "paid_measurements": self.paid_measurements,
            "infeasible": self.num_infeasible,
            "failures": {phase: dict(s)
                         for phase, s in sorted(self.failures.items())},
            "failed_cost": sum(s.get("cost", 0.0)
                               for s in self.failures.values()),
            "best": None if best is None else {
                "value": best.value,
                "configuration": best.configuration.as_dict(),
            },
            "members": [{
                "optimizer": m.optimizer,
                "operation_id": m.operation_id,
                "trials": m.run.num_trials,
                "measured": m.run.num_measured,
                "foreign_trials": m.foreign_trials,
                "warm_trials": m.warm_trials,
                "best": None if m.best is None else m.best.value,
            } for m in self.members],
            "transfer": None if self.transfer is None
            else self.transfer.summary(),
            "prediction_quality": None if q is None else q.summary(),
        }


class Investigation:
    """Declarative front door: build from a spec (or components), then
    ``plan()`` / ``run()`` / ``resume()``.

    Three construction paths share the engine:

    * ``Investigation(spec, store=...)`` — fully declarative: the Discovery
      Space is built from the spec's dimensions + experiment factories over
      the given store — or, when none is passed, over the backend the
      spec's ``store`` field names via
      :func:`repro.core.store.open_store` (a path opens SQLite, a
      ``tcp://``/``unix://`` URL connects to a store server; ``None`` means
      a fresh in-memory store);
    * ``Investigation(spec, ds=...)`` — programmatic space, declarative
      everything else (the spec's experiments may then be empty);
    * :meth:`from_components` / :meth:`for_members` — the legacy-shim paths
      used by ``run_optimizer`` and ``Campaign.run``.
    """

    def __init__(self, spec: InvestigationSpec,
                 store: Optional[StoreBackend] = None,
                 ds: Optional[DiscoverySpace] = None):
        self.spec = spec
        if ds is None:
            if not spec.experiments and not spec.connectors:
                raise ValueError(
                    "spec has no experiments; pass a ready DiscoverySpace "
                    "or add experiment/connector factories to the spec")
            from ..actions import ActionSpace
            built = [e.build() for e in spec.experiments] \
                + [c.build() for c in spec.connectors]
            ds = DiscoverySpace(
                space=spec.space,
                actions=ActionSpace.make(built),
                store=store if store is not None
                else open_store(spec.store or ":memory:"),
                meta=spec.meta or None)
        self.ds = ds
        # programmatic overrides (shim paths); None => build from the spec
        self._optimizers: Optional[list] = None
        self._rngs: Optional[list] = None
        self._members: Optional[list] = None
        self._backend = spec.execution.backend
        self._manage_history = True

    # ------------------------------------------------------------ shim paths

    @classmethod
    def from_components(cls, ds: DiscoverySpace, optimizers: Sequence,
                        metric: str, mode: str = "min",
                        rngs: Optional[Sequence] = None,
                        max_trials: int = 200, patience: int = 5,
                        min_trials: int = 1, batch_size: int = 1,
                        workers: int = 1, max_inflight: Optional[int] = None,
                        backend=None, share_history: bool = False,
                        warm_start: bool = False,
                        transfer: Optional[TransferSpec] = None,
                        objective=None,
                        name: str = "adhoc") -> "Investigation":
        """Build from prebuilt objects (optimizer instances, a ready space,
        possibly an ExecutionBackend instance) — the ``run_optimizer`` path.
        The spec's ``optimizers`` field stays declaratively empty-ish; the
        instances override it."""
        from .spec import BudgetSpec, ExecutionSpec
        spec = InvestigationSpec(
            name=name, space=ds.space, metric=metric, mode=mode,
            objective=objective,
            execution=ExecutionSpec(
                backend=backend if isinstance(backend, (str, type(None)))
                else None,
                workers=workers, max_inflight=max_inflight,
                batch_size=batch_size),
            budget=BudgetSpec(max_trials=max_trials, patience=patience,
                              min_trials=min_trials),
            transfer=transfer if transfer is not None else TransferSpec(),
            share_history=share_history, warm_start=warm_start)
        inv = cls(spec, ds=ds)
        inv._optimizers = list(optimizers)
        inv._rngs = list(rngs) if rngs is not None else None
        if isinstance(backend, ExecutionBackend):
            inv._backend = backend
        return inv

    @classmethod
    def for_members(cls, ds: DiscoverySpace, members: Sequence[_Member],
                    metric: str, mode: str, max_trials: int,
                    share_history: bool, backend,
                    name: str = "campaign") -> "Investigation":
        """Wrap prebuilt fleet members — the ``Campaign.run`` path.  The
        caller owns member construction, watermarks, and warm-start
        semantics; the Investigation only drives and reports."""
        from .spec import BudgetSpec, ExecutionSpec
        spec = InvestigationSpec(
            name=name, space=ds.space, metric=metric, mode=mode,
            execution=ExecutionSpec(
                backend=backend if isinstance(backend, (str, type(None)))
                else None,
                max_inflight=max(m.max_inflight for m in members)),
            budget=BudgetSpec(max_trials=max_trials),
            share_history=share_history)
        inv = cls(spec, ds=ds)
        inv._members = list(members)
        inv._manage_history = False
        if isinstance(backend, ExecutionBackend):
            inv._backend = backend
        return inv

    # -------------------------------------------------------------- planning

    @property
    def engine(self) -> str:
        n = len(self._members) if self._members is not None else (
            len(self._optimizers) if self._optimizers is not None
            else len(self.spec.optimizers))
        if n > 1:
            return "campaign"
        return "batched" if self.spec.execution.max_inflight is None \
            else "pipelined"

    def _member_labels(self) -> list:
        if self._members is not None:
            return [m.label for m in self._members]
        optimizers = (self._optimizers if self._optimizers is not None
                      else list(self.spec.optimizers))
        counts: dict = {}
        labels = []
        for opt in optimizers:
            n = counts.get(opt.name, 0)
            counts[opt.name] = n + 1
            labels.append(opt.name if n == 0 else f"{opt.name}#{n + 1}")
        return labels

    def plan(self) -> InvestigationPlan:
        """Describe the run without measuring anything: engine dispatch,
        fleet, budget, and — when transfer is enabled — the related spaces
        the catalog would offer as warm-start sources."""
        self._check_device_owner()
        spec = self.spec
        candidates = []
        if spec.transfer.enabled:
            candidates = [rel.summary()
                          for rel in self._transfer_candidates()]
        return InvestigationPlan(
            name=spec.name, space_id=self.ds.space_id, engine=self.engine,
            metric=spec.objective_label(), mode=spec.mode,
            members=self._member_labels(),
            backend=(spec.execution.backend
                     if not isinstance(self._backend, ExecutionBackend)
                     else type(self._backend).__name__),
            workers=spec.execution.workers,
            batch_size=spec.execution.batch_size,
            max_inflight=spec.execution.max_inflight,
            budget=spec.budget.to_json(),
            share_history=spec.share_history, warm_start=spec.warm_start,
            transfer_enabled=spec.transfer.enabled,
            transfer_predict_remaining=spec.transfer.predict_remaining,
            transfer_candidates=candidates,
            constraints=[] if spec.objective is None else
            [c.describe() for c in spec.objective.constraints],
            failures=self._failure_summary())

    def _check_device_owner(self) -> None:
        """Refuse to measure a device-driving connector in child processes.

        An accelerator belongs to one process: the parent that touched it
        holds it, and the ``process`` and ``queue`` backends measure in
        children that would fail or hang reaching it."""
        backend = self._backend
        isolated = (backend in ("process", "queue") if isinstance(backend, str)
                    else isinstance(backend, (ProcessBackend, QueueBackend)))
        if not isolated:
            return
        owners = [e.name for e in self.ds.actions.experiments
                  if getattr(getattr(e, "connector", None), "needs_device",
                             False)]
        if owners:
            label = backend if isinstance(backend, str) \
                else type(backend).__name__
            raise ValueError(
                f"{', '.join(owners)} drive(s) the accelerator, which one "
                f"process owns; the {label!r} backend measures in child "
                f"processes that cannot reach it — use the 'serial' or "
                f"'thread' backend")

    def _failure_summary(self) -> dict:
        """Per-phase failed-trial counts and charged provisioned cost for
        this space (``{phase: {"count", "cost"}}``) — best-effort: a store
        backend without failure provenance just reports nothing."""
        try:
            summary = self.ds.store.failure_summary(self.ds.space_id)
        except Exception:
            return {}
        return {str(phase): {"count": int(s["count"]),
                             "cost": float(s["cost"])}
                for phase, s in summary.items()}

    # ------------------------------------------------------------- execution

    def _build_members(self) -> list:
        spec = self.spec
        optimizers = (self._optimizers if self._optimizers is not None
                      else [o.build() for o in spec.optimizers])
        rngs = (self._rngs if self._rngs is not None
                else [np.random.default_rng(opt.seed) for opt in optimizers])
        if len(rngs) != len(optimizers):
            raise ValueError(f"rngs must match optimizers: "
                             f"{len(rngs)} != {len(optimizers)}")
        members = []
        for label, opt, rng in zip(self._member_labels(), optimizers, rngs):
            adapter = SearchAdapter(self.ds, spec.objective_label(),
                                    spec.mode, optimizer_name=label,
                                    objective=spec.objective)
            member = _Member(label, opt, adapter, rng, None,
                             spec.execution.max_inflight or 1)
            # the floor counts the member's OWN trials: warm-start and
            # foreign-folded history never satisfies a budget the caller
            # asked this member to spend itself
            member.rule = _StoppingRule(adapter, spec.budget.patience,
                                        spec.budget.min_trials,
                                        count=(lambda m=member: m.own_told))
            members.append(member)
        return members

    def run(self, resume: bool = False) -> InvestigationResult:
        """Execute the investigation (see class docstring for the stages).

        With ``resume=True`` (or ``spec.warm_start``), every sampling event
        already in the space's record is folded into each member's history
        before the first ask — the cross-session continuation path; reuse
        makes re-proposals free, so only new ground costs money.
        """
        self._check_device_owner()
        spec = self.spec
        ds = self.ds
        members = (self._members if self._members is not None
                   else self._build_members())
        share = spec.share_history and (len(members) > 1
                                        or not self._manage_history)
        transfer_report: Optional[TransferReport] = None
        if self._manage_history:
            warm = resume or spec.warm_start
            if warm:
                with tracing.span("engine.resume"):
                    for m in members:
                        m.adapter.record_watermark = 0
                        m.foreign_told += m.adapter.sync_foreign()
            if spec.transfer.enabled:
                transfer_report = self._apply_transfer(members)
            # fleet sharing starts at "now": pre-run records are covered by
            # the warm fold above (or deliberately invisible), and the
            # transfer stage's representative records are already in every
            # history as warm trials — advancing the watermark keeps them
            # from double-folding as foreign tells
            tail = ds.store.last_record_rowid(ds.space_id)
            for m in members:
                m.adapter.record_watermark = tail

        if self.engine == "batched":
            events, crash = self._run_batched(members[0])
        else:
            state = _drive_fleet(ds, members, spec.budget.max_trials,
                                 share_history=share, backend=self._backend)
            events, crash = state.events, state.crash
        if crash is not None:
            raise crash
        if share:
            # final fold so every member's reported history covers the
            # fleet's last completions (models queried post-run see the
            # full union)
            for m in members:
                m.foreign_told += m.adapter.sync_foreign()
        return InvestigationResult(
            name=spec.name, space_id=ds.space_id,
            metric=spec.objective_label(),
            mode=spec.mode, engine=self.engine,
            members=[self._member_result(m) for m in members],
            events=events, transfer=transfer_report,
            failures=self._failure_summary())

    def resume(self) -> InvestigationResult:
        """Continue an investigation whose store already holds history."""
        return self.run(resume=True)

    def _run_batched(self, member: _Member):
        """The barriered batch engine (the classic ``run_optimizer`` loop):
        each step asks for up to ``batch_size`` candidates and evaluates
        them with ``workers`` parallel experiment workers, telling the whole
        batch before the next ask.  With the defaults this is the serial
        suggest/evaluate loop, draw-for-draw."""
        from concurrent.futures import ThreadPoolExecutor

        spec = self.spec
        adapter, optimizer, rng, rule = (member.adapter, member.optimizer,
                                         member.rng, member.rule)
        batch_size = spec.execution.batch_size
        workers = spec.execution.workers
        backend = self._backend
        max_trials = spec.budget.max_trials
        events: list = []
        # one worker pool / backend for the whole run, not one per batch
        owned = not isinstance(backend, ExecutionBackend)
        pool = (ThreadPoolExecutor(max_workers=workers)
                if workers > 1 and backend is None else None)
        engine = (self.ds.execution_backend(backend, workers=workers)
                  if backend is not None else None)
        try:
            while not rule.stop and member.own_told < max_trials:
                n = min(batch_size, max_trials - member.own_told)
                # a step's trial id is the seq of the first trial it tells
                tracing.trial(len(adapter.trials))
                with tracing.span("trial"):
                    with tracing.span("ask"):
                        batch = optimizer.ask(adapter, rng, n=n)
                    if not as_scored(batch):
                        member.exhausted = True
                        break
                    before = len(adapter.trials)
                    adapter.evaluate_batch(batch, workers=workers,
                                           executor=pool, backend=engine)
                    told = adapter.trials[before:]
                    member.own_told += len(told)
                    for t in told:
                        rule.observe(t.value, t.feasible)
                        events.append((member.label, t))
        finally:
            if pool is not None:
                pool.shutdown(wait=False)
            if engine is not None and owned:
                engine.close()
        return events, None

    def frontier(self, properties: Sequence[str],
                 modes: Optional[Sequence[str]] = None) -> list:
        """The space's measured Pareto frontier over ``properties`` —
        ``[(configuration, values), ...]`` straight from the store backend
        (:meth:`~repro.core.store.base.StoreBackend.frontier`), restricted
        to this investigation's action-space provenance."""
        return self.ds.store.frontier(
            self.ds.space_id, properties, modes,
            list(self.ds.actions.identifiers))

    def _member_result(self, member: _Member) -> MemberResult:
        spec = self.spec
        run = OptimizerRun(
            optimizer=member.label, metric=spec.objective_label(),
            mode=spec.mode,
            trials=member.own_trials(),
            operation_id=member.adapter.operation_id,
            batch_size=(spec.execution.batch_size
                        if self.engine == "batched" else 1),
            max_inflight=(None if self.engine == "batched"
                          else member.max_inflight))
        return MemberResult(
            optimizer=member.label,
            operation_id=member.adapter.operation_id,
            run=run, foreign_trials=member.foreign_told,
            history_size=len(member.adapter.trials),
            warm_trials=member.adapter.warm_told)

    # -------------------------------------------------------------- transfer

    def _transfer_candidates(self) -> list:
        spec = self.spec
        catalog = SpaceCatalog(self.ds.store)
        candidates = catalog.find_related(
            self.ds.space, exclude=[self.ds.space_id],
            mappings=spec.transfer.mapping_dicts(), min_overlap=1.0,
            metric=spec.metric, min_measured=3)
        if spec.transfer.sources:
            allowed = set(spec.transfer.sources)
            candidates = [c for c in candidates
                          if c.entry.space_id in allowed]
        return candidates

    def _apply_transfer(self, members: list) -> TransferReport:
        """The §IV RSSC procedure, automated end to end: discover a related
        measured space in the catalog, measure its representative sub-space
        here, apply the transfer criteria, and (on pass) warm-start every
        member with surrogate predictions over the source's full history.
        Candidates are tried best-related-first until one transfers; a run
        where none does reports the attempts and searches cold."""
        spec = self.spec
        t = spec.transfer
        ds = self.ds
        catalog = SpaceCatalog(ds.store)
        report = TransferReport()
        rng = np.random.default_rng(t.seed)
        sign = 1.0 if spec.mode == "min" else -1.0
        for rel in self._transfer_candidates():
            pairs = catalog.measured_pairs(rel.entry, spec.metric)
            if len(pairs) < 3:
                report.attempts.append(
                    {"space_id": rel.entry.space_id,
                     "outcome": "skipped: <3 measured source samples"})
                continue
            values = np.array([v for _, v in pairs])
            idx = select_indices(values, t.selection, rng)
            if t.max_representatives is not None \
                    and len(idx) > t.max_representatives:
                # budget the paid rep pass: keep points evenly spaced over
                # the value ranking so the extremes that pin the linear
                # fit's slope survive (deterministic)
                order = sorted(idx, key=lambda i: (values[i], i))
                keep = np.linspace(0, len(order) - 1,
                                   num=t.max_representatives)
                idx = sorted({order[int(round(k))] for k in keep})
            rep_pairs = [pairs[i] for i in idx]
            translated = [rel.entry.space.translate(c, rel.mapping)
                          for c, _ in rep_pairs]
            op = ds.begin_operation("transfer", {
                "source_space": rel.entry.space_id,
                "metric": spec.metric, "selection": t.selection,
                "mapping": {d: sorted(m.items()) for d, m in
                            rel.mapping.items()} if rel.mapping else {}})
            results = ds.sample_batch(translated, operation_id=op)
            kept_src, kept_tgt = [], []
            measured_values: dict = {}
            failed_digests: set = set()
            n_meas = n_fail = 0
            for (src_c, src_v), tgt_c, r in zip(rep_pairs, translated,
                                                results):
                if r.action == "measured":
                    n_meas += 1
                elif r.action == "failed":
                    n_fail += 1
                if not r.ok:
                    failed_digests.add(tgt_c.digest)
                    continue
                if not r.sample.has(spec.metric):
                    continue
                tgt_v = float(r.sample.value(spec.metric))
                kept_src.append(src_v)
                kept_tgt.append(tgt_v)
                measured_values[tgt_c.digest] = tgt_v
            # every attempt's rep pass deployed real experiments — charge
            # them even when the criteria then reject the candidate
            report.n_rep_measured += n_meas
            report.n_rep_failed += n_fail
            assessment = assess_transfer(
                kept_src, kept_tgt, TransferCriteria(t.min_r, t.max_p))
            report.attempts.append({
                "space_id": rel.entry.space_id,
                "outcome": "transfer" if assessment.transferable
                else "criteria not met",
                "rep_paid": n_meas + n_fail,
                **assessment.summary()})
            if not assessment.transferable:
                continue
            surrogate = assessment.surrogate
            warm, predictions = [], {}
            for src_c, src_v in pairs:
                tgt_c = rel.entry.space.translate(src_c, rel.mapping)
                digest = tgt_c.digest
                if digest in failed_digests:
                    # the rep pass just OBSERVED this configuration fail in
                    # the target: a plausible surrogate value would steer
                    # every member toward a known-infeasible point
                    continue
                if digest in measured_values:
                    warm.append((tgt_c, measured_values[digest]))
                else:
                    pred = float(surrogate(src_v))
                    predictions[digest] = pred
                    warm.append((tgt_c, pred))
            if t.max_warm is not None and len(warm) > t.max_warm:
                # deterministic truncation, best-predicted first: the most
                # informative region of the source survives the cap
                warm.sort(key=lambda cv: (sign * cv[1], cv[0].digest))
                warm = warm[:t.max_warm]
                kept = {c.digest for c, _ in warm}
                predictions = {d: v for d, v in predictions.items()
                               if d in kept}
            for m in members:
                m.adapter.warm_start(warm)
            report.applied = True
            report.source_space_id = rel.entry.space_id
            report.mapping = rel.mapping
            report.assessment = assessment
            report.n_source_samples = len(pairs)
            report.n_representatives = len(rep_pairs)
            report.n_warm_trials = len(warm)
            report.operation_id = op
            report.warm_predictions = predictions
            if t.predict_remaining and ds.space.finite:
                self._predict_remaining(report, rel, pairs, assessment, op)
            return report
        return report

    def _predict_remaining(self, report: TransferReport, rel, pairs,
                           assessment, fit_op: str) -> None:
        """The RSSC step-⑧ sweep as a spec mode (``transfer.
        predict_remaining``): build ``A*_pred`` — this space plus a
        :class:`~repro.core.actions.SurrogateExperiment` wrapping the fitted
        line over the source's measured values — and sweep it over every
        configuration the search has not touched, so the store ends up
        holding a full predicted surface (provenance-marked ``predicted``)
        next to the paid measurements.  A target point whose source sibling
        was never measured fails its prediction (terminal, recorded), same
        as the serial RSSC sweep."""
        from ..actions import MeasurementError, SurrogateExperiment

        spec = self.spec
        src_values = {rel.entry.space.translate(c, rel.mapping).digest:
                      float(v) for c, v in pairs}

        def lookup(target_config):
            digest = target_config.digest
            if digest not in src_values:
                raise MeasurementError(
                    f"no source value of {spec.metric!r} for "
                    f"{target_config!r}")
            return src_values[digest]

        surrogate = SurrogateExperiment(
            source=lookup,
            model=assessment.surrogate,
            property_name=spec.metric,
            name=f"transfer-{spec.metric}",
            version="1",
            params={"slope": assessment.surrogate.slope,
                    "intercept": assessment.surrogate.intercept,
                    "source_space": rel.entry.space_id,
                    "fit_op": fit_op})
        predicted_space = self.ds.with_predictor(surrogate)
        pred_op = predicted_space.begin_operation("transfer-predict")
        results = predicted_space.sample_batch(
            list(predicted_space.remaining_configurations()),
            operation_id=pred_op)
        report.n_predicted = sum(1 for r in results
                                 if r.action == "predicted")
        report.predicted_space_id = predicted_space.space_id
