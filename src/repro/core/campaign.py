"""Cooperative multi-optimizer campaigns over one shared store (paper §V).

The paper's first headline claim is "safe, transparent sharing of data
between executions of best-of-breed optimizers increasing the efficiency of
optimal configuration detection".  No single optimizer family wins across
workloads (Lazuka et al. 2022), and reusing other investigators'
measurements slashes search cost (Scout, Hsu et al. 2018) — so instead of
picking one optimizer, a :class:`Campaign` runs N heterogeneous optimizers
*concurrently* over one :class:`~repro.core.discovery.DiscoverySpace` and
lets every participant train on the union of the fleet's history:

* each member keeps its own operation (its own sampling record, its own
  rng, its own stopping rule) — runs stay attributable and individually
  reproducible;
* every completed measurement — no matter which member asked for it — is
  told to *all* members: before each ask, a member folds the other
  operations' new sampling events into its history via
  :meth:`~repro.core.optimizers.base.SearchAdapter.sync_foreign`, an
  incremental, watermark-paged read of the shared record
  (:meth:`~repro.core.store.SampleStore.records_since`, O(new rows) per
  sync).  Because the sync goes through the store, members may equally live
  in different processes sharing the database file;
* all members submit through ONE execution backend, so a campaign shares a
  single worker fleet: acquisition scores ride
  :class:`~repro.core.execution.WorkItem` priorities into the scheduler
  exactly as they do for a solo run, and the store's measurement-claim
  arbitration guarantees a configuration proposed by two members
  concurrently is still measured exactly once (the second tell lands as a
  transparent ``reused``).

Determinism guarantees
----------------------

Sharing is strictly additive: a member's rng stream is consumed only by its
own asks, and ``sync_foreign`` never touches the rng.  A single-member
campaign (nothing foreign to fold) reproduces
``run_optimizer(max_inflight=1)`` — and therefore the classic serial loop —
draw-for-draw; this is regression-gated per optimizer family in
``tests/test_campaign.py``.  With multiple members the *interleaving* of
foreign tells depends on completion order (as in any pipelined run), but
every value a member trains on comes from the store's reconciled sample
set, so histories never diverge from the durable data.

Reproducing the §V sharing-efficiency result: ``python -m
benchmarks.campaign_bench`` measures time-to-best-cost for a shared-history
campaign vs the same optimizers isolated, writing ``BENCH_sharing.json``.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import List, Optional, Sequence, Union

import numpy as np

from . import tracing
from .discovery import DiscoverySpace
from .execution import ExecutionBackend, WorkItem
from .optimizers.base import (FOREIGN_ACTION, WARM_ACTION, Optimizer,
                              OptimizerRun, SearchAdapter, Trial,
                              _StoppingRule, as_scored)

__all__ = ["Campaign", "CampaignResult", "MemberResult", "run_campaign"]


@dataclass
class MemberResult:
    """One member's view of a finished campaign/investigation."""

    optimizer: str
    operation_id: str
    run: OptimizerRun          # own trials only (what this member asked for)
    foreign_trials: int        # fleet history folded into its model
    history_size: int          # own + foreign + warm: what the model fit saw
    warm_trials: int = 0       # cross-space transfer trials folded pre-run

    @property
    def best(self) -> Optional[Trial]:
        return self.run.best


@dataclass
class CampaignResult:
    """Fleet-level outcome: per-member results + the global tell order."""

    metric: str
    mode: str
    members: List[MemberResult]
    #: ``(member_label, Trial)`` in fleet-wide tell (completion) order —
    #: the trace the sharing-efficiency bench computes time-to-best on.
    events: list = field(default_factory=list)

    @property
    def num_measured(self) -> int:
        return sum(1 for _, t in self.events if t.action == "measured")

    @property
    def num_trials(self) -> int:
        return len(self.events)

    @property
    def best(self) -> Optional[Trial]:
        sign = 1.0 if self.mode == "min" else -1.0
        valued = [t for _, t in self.events if t.value is not None]
        if not valued:
            return None
        return min(valued, key=lambda t: sign * t.value)

    def measurements_to_best(self) -> Optional[int]:
        """Measured experiments spent until the final best value first
        appeared (1-based) — the fleet's time-to-best-cost."""
        best = self.best
        if best is None:
            return None
        measured = 0
        for _, t in self.events:
            if t.action == "measured":
                measured += 1
            if t.value is not None and t.value == best.value:
                return measured
        return measured  # pragma: no cover - best always appears in events


class _Member:
    """Per-optimizer fleet state: one asker on the shared coordinator loop.

    Also the unit a solo pipelined run
    (``run_optimizer(max_inflight=N)`` via
    :class:`~repro.core.api.investigation.Investigation`) wraps itself in —
    the caller supplies a ready adapter/rule/rng, so the solo engine and
    the campaign share one state machine (and one set of
    submit/tell/crash-drain semantics) by construction.
    """

    def __init__(self, label: str, optimizer: Optimizer,
                 adapter: SearchAdapter, rng: np.random.Generator,
                 rule: _StoppingRule, max_inflight: int):
        self.label = label
        self.optimizer = optimizer
        self.adapter = adapter
        self.rng = rng
        self.rule = rule
        self.max_inflight = max_inflight
        self.inflight = 0          # this member's outstanding work items
        self.own_told = 0          # trials this member asked for and got back
        self.exhausted = False
        self.foreign_told = 0

    def wants_more(self, max_trials: int) -> bool:
        return (not self.rule.stop and not self.exhausted
                and self.inflight < self.max_inflight
                and self.own_told + self.inflight < max_trials)

    def own_trials(self) -> list:
        """Trials this member asked for itself — the foreign-folded fleet
        history and warm-start transfer trials live only in the adapter."""
        return [t for t in self.adapter.trials
                if t.action not in (FOREIGN_ACTION, WARM_ACTION)]


class _RunState:
    """Mutable coordinator-loop state shared with :func:`_absorb`."""

    def __init__(self):
        self.inflight: dict = {}   # tag -> (member, configuration, digest)
        self.events: list = []     # (member_label, Trial) in tell order
        self.tag = 0
        self.crash: Optional[BaseException] = None


def _absorb(ds: DiscoverySpace, completed, state: _RunState) -> bool:
    """Tell a batch of backend completions into their members' histories
    (record under the asking member's operation, observe its stopping rule,
    append to the fleet event trace).  Returns True if anything landed."""
    for res in completed:
        member, config, digest = state.inflight.pop(res.item.tag)
        member.inflight -= 1
        member.adapter.pending.discard(digest)
        if res.action == "crashed":
            state.crash = state.crash if state.crash is not None else res.error
            continue
        result = ds.record_result(config, digest, res.action, res.error,
                                  member.adapter.operation_id)
        with tracing.span("tell"):
            trial = member.adapter.tell_result(result)
            member.own_told += 1
            member.rule.observe(trial.value, trial.feasible)
        state.events.append((member.label, trial))
    return bool(completed)


def _drive_fleet(ds: DiscoverySpace, members: Sequence[_Member],
                 max_trials: int, share_history: bool,
                 backend: Union[ExecutionBackend, str, None]) -> _RunState:
    """THE coordinator state machine: N askers multiplexed over one backend.

    A solo pipelined ``run_optimizer(max_inflight=N)`` — routed through
    :class:`~repro.core.api.investigation.Investigation` — is this loop
    with a single member and ``share_history=False`` (``max_inflight=1``
    then reproduces the serial trajectory draw-for-draw — regression-gated
    per optimizer); :meth:`Campaign.run` is the same loop with N members
    and foreign-tell syncs.  One implementation means one set of
    submit/tell/crash-drain semantics to maintain.

    Round-robin, one submission per member per pass — each member with
    in-flight headroom syncs foreign history (campaigns only), asks once,
    and submits; completions are drained *between* submissions, so with a
    synchronous backend every ask trains on every measurement the fleet
    has finished (full-information sharing, the §V measurement-efficiency
    setting), while concurrent backends pipeline naturally with at most
    ``max_inflight`` staleness per member.  A crash surfaced by an
    in-process backend stops new submissions fleet-wide, drains what is in
    flight (those measurements are paid for and durable), and is returned
    on the state for the caller to raise.
    """
    total_inflight = sum(m.max_inflight for m in members)
    owned = not isinstance(backend, ExecutionBackend)
    engine = ds.execution_backend(backend, workers=total_inflight)
    state = _RunState()
    pause = 0.0005
    try:
        while True:
            submitted = False
            if state.crash is None:
                for member in members:
                    if state.crash is not None:
                        # a completion absorbed mid-pass surfaced a crash:
                        # stop submitting immediately — the remaining
                        # members must not start new paid measurements
                        break
                    if not member.wants_more(max_trials):
                        continue
                    # a fleet trial's id is its work item's tag
                    tracing.trial(state.tag)
                    with tracing.span("trial"):
                        if share_history:
                            member.foreign_told += \
                                member.adapter.sync_foreign()
                        with tracing.span("ask"):
                            batch = as_scored(member.optimizer.ask(
                                member.adapter, member.rng, n=1))
                        if not batch:
                            member.exhausted = True
                            continue
                        cand = batch[0]
                        with tracing.span("store.intern"):
                            digest = ds.store.put_configuration(
                                cand.configuration)
                        member.adapter.pending.add(digest)
                        engine.submit(WorkItem(
                            cand.configuration, digest, state.tag,
                            priority=(0.0 if cand.score is None
                                      else float(cand.score))))
                        state.inflight[state.tag] = (
                            member, cand.configuration, digest)
                        member.inflight += 1
                        state.tag += 1
                        submitted = True
                        # drain anything already finished before the next
                        # member's ask: synchronous backends hand every ask
                        # the complete fleet history
                        if _absorb(ds, engine.poll(), state):
                            pause = 0.0005
            if not state.inflight and not submitted:
                break
            if _absorb(ds, engine.poll(), state) or submitted:
                pause = 0.0005
                continue
            ds._maybe_sweep_claims()
            time.sleep(pause)
            pause = min(pause * 2, 0.005)
    finally:
        if owned:
            engine.close()
    return state


class Campaign:
    """Run N heterogeneous optimizers cooperatively over one Discovery Space.

    ``optimizers`` are the campaign members (any mix of families; the same
    family twice with different seeds is fine — labels are made unique).
    Each member runs the pipelined ask/tell protocol with its own operation,
    rng, and stopping rule (§V-B1: ``patience`` trials without improvement),
    up to ``max_trials`` *own* trials per member; all members share one
    execution backend resolved from ``backend`` (a name, an instance, or
    None for the default), sized to the fleet's total in-flight budget.

    ``share_history=True`` (the cooperative mode) folds every other
    operation's completed measurements into each member's history before
    each ask; ``False`` runs the same fleet with isolated models — members
    then interact only through the store's transparent measure-once reuse,
    which is the paper's baseline sharing level.  ``warm_start=True``
    additionally folds sampling events that were already in the store
    *before* the campaign began (cross-campaign reuse, paper Fig. 7).

    ``rngs`` fixes per-member randomness (defaults derive from each
    optimizer's own seed, matching ``run_optimizer``'s default).
    """

    def __init__(
        self,
        ds: DiscoverySpace,
        optimizers: Sequence[Optimizer],
        metric: str,
        mode: str = "min",
        max_trials: int = 50,
        patience: int = 5,
        min_trials: int = 1,
        max_inflight: int = 1,
        share_history: bool = True,
        warm_start: bool = False,
        backend: Union[ExecutionBackend, str, None] = None,
        rngs: Optional[Sequence[np.random.Generator]] = None,
    ):
        if not optimizers:
            raise ValueError("a campaign needs at least one optimizer")
        if max_inflight < 1:
            raise ValueError(f"max_inflight must be >= 1, got {max_inflight}")
        if rngs is not None and len(rngs) != len(optimizers):
            raise ValueError(f"rngs must match optimizers: "
                             f"{len(rngs)} != {len(optimizers)}")
        self.ds = ds
        self.metric = metric
        self.mode = mode
        self.max_trials = max_trials
        self.share_history = share_history
        self.backend = backend
        counts: dict = {}
        self.members: List[_Member] = []
        for i, opt in enumerate(optimizers):
            n = counts.get(opt.name, 0)
            counts[opt.name] = n + 1
            label = opt.name if n == 0 else f"{opt.name}#{n + 1}"
            rng = (rngs[i] if rngs is not None
                   else np.random.default_rng(opt.seed))
            adapter = SearchAdapter(ds, metric, mode, optimizer_name=label)
            member = _Member(label, opt, adapter, rng, None, max_inflight)
            # min_trials floors this member's OWN trial count: foreign-
            # folded history must never satisfy a floor the caller asked of
            # this member
            member.rule = _StoppingRule(adapter, patience, min_trials,
                                        count=(lambda m=member: m.own_told))
            self.members.append(member)
        if not warm_start:
            # start the sync watermark at the current record tail: members
            # share what the fleet produces, not pre-campaign history
            watermark = ds.store.last_record_rowid(ds.space_id)
            for m in self.members:
                m.adapter.record_watermark = watermark

    # ------------------------------------------------------------------ run

    def run(self) -> CampaignResult:
        """Drive the fleet to completion and return the campaign result.

        Thin shim over the declarative engine: hands the prebuilt members
        to an :class:`~repro.core.api.investigation.Investigation`
        (:meth:`~repro.core.api.investigation.Investigation.for_members`),
        which runs :func:`_drive_fleet` — the coordinator state machine
        shared with the solo pipelined engine — with foreign-tell syncing
        per ``share_history`` and a final fold so every member's reported
        history covers the fleet's last completions.  A crash surfaced by
        an in-process backend propagates after the surviving in-flight
        trials drain, exactly the solo pipelined contract.  Trajectories
        are regression-gated draw-for-draw against the pre-shim engine.
        """
        from .api.investigation import Investigation  # local: avoid cycle

        inv = Investigation.for_members(
            self.ds, self.members, self.metric, self.mode, self.max_trials,
            share_history=self.share_history, backend=self.backend)
        res = inv.run()
        return CampaignResult(
            metric=self.metric,
            mode=self.mode,
            members=res.members,
            events=res.events,
        )


def run_campaign(ds: DiscoverySpace, optimizers: Sequence[Optimizer],
                 metric: str, **kwargs) -> CampaignResult:
    """Convenience wrapper: build a :class:`Campaign` and :meth:`~Campaign.run` it."""
    return Campaign(ds, optimizers, metric, **kwargs).run()
