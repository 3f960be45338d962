"""Optimizer base classes + the Discovery Space compatibility wrapper.

Mirrors the paper's design (§III-D): optimization algorithms are decoupled
from workload experiments — they only see the ``sample`` method of a
Discovery Space through :class:`SearchAdapter`.  The adapter also implements
the paper's stopping rule (§V-B1: stop when the incumbent has not improved
for five consecutive trials) and reports, per trial, whether the sample was
*measured* or transparently *reused* from the common context — the raw data
behind the paper's Fig. 7 incremental-sampling evaluation.

Ask/tell protocol
-----------------

Optimizers implement ``ask(adapter, rng, n) -> [ScoredCandidate]``: propose
up to ``n`` distinct unsampled candidates *without* evaluating them, each
carrying the optimizer's acquisition score (None when the proposal is
unscored, e.g. random draws).  Scores ride along as work-item *priorities*:
queue-rendezvous workers measure the highest-acquisition configurations
first (Lynceus-style), while results and records stay in submission/tell
order, so scoring never perturbs the trajectory.  Evaluation is the
driver's job: :meth:`SearchAdapter.evaluate_batch` routes the batch through
``DiscoverySpace.sample_batch`` (fanning experiments over a worker pool)
and *tells* the resulting :class:`Trial` list back into the adapter's
history, which is the only state optimizers observe.  ``ask`` with ``n=1``
is the classic suggest step — :meth:`Optimizer.suggest` remains as that thin
wrapper, and :func:`run_optimizer` with ``batch_size=1`` reproduces the
serial trajectory draw-for-draw.
"""

from __future__ import annotations

import abc
import math
from dataclasses import dataclass, field
from typing import Callable, List, Optional, Sequence, Tuple, Union

import numpy as np

from .. import tracing
from ..actions import MeasurementError
from ..discovery import BatchResult, DiscoverySpace
from ..entities import Configuration
from ..execution import ExecutionBackend

__all__ = ["Trial", "OptimizerRun", "ScoredCandidate", "SearchAdapter",
           "Optimizer", "run_optimizer", "hypergeom_p_found", "as_scored",
           "FOREIGN_ACTION", "WARM_ACTION"]

#: Action tag of a trial folded into an adapter's history from ANOTHER
#: operation's sampling record (a campaign foreign tell).  Deliberately not
#: part of the sampling-record vocabulary: foreign trials exist only in the
#: optimizer-visible history — the store record of the originating operation
#: is the single source of truth, so nothing is double-recorded.
FOREIGN_ACTION = "foreign"

#: Action tag of a trial folded by :meth:`SearchAdapter.warm_start` — a value
#: transferred from a *related* space (paper §IV-3/4): typically a surrogate
#: prediction, sometimes a re-measured representative.  Like foreign trials
#: these exist only in the optimizer-visible history; unlike them, warm
#: digests are NOT marked seen, so the optimizer may still propose (and truly
#: measure) a warm-predicted configuration — predictions guide the model,
#: they never veto a measurement.
WARM_ACTION = "warm"


@dataclass(frozen=True)
class ScoredCandidate:
    """One proposed configuration + the acquisition score behind it.

    ``score`` is in *maximization* orientation (higher = more informative:
    EI for GP-BO, log l/g for TPE) and becomes the work item's scheduling
    priority; None marks an unscored proposal (random draws, init phase),
    which schedules at priority 0.  The wrapper is deliberately thin —
    ``digest`` proxies through so candidate bookkeeping (dedup sets, BOHB's
    interleaved exclude) reads the same as for a bare configuration.
    """

    configuration: Configuration
    score: Optional[float] = None

    @property
    def digest(self) -> str:
        return self.configuration.digest


def as_scored(batch: Sequence) -> List[ScoredCandidate]:
    """Normalize an ask batch to :class:`ScoredCandidate`s.

    :meth:`Optimizer.ask` documents a ScoredCandidate return, but the
    tolerance :meth:`Optimizer.suggest` extends — a subclass still returning
    bare configurations — must hold at *every* driver boundary, or a legacy
    optimizer works under the batch engine and crashes the pipelined engine
    (or the campaign foreign-tell path) the first time something reads
    ``.configuration``/``.score`` off its batch.  Drivers call this once on
    each ask result; everything downstream sees ScoredCandidates only.
    None (another legacy exhaustion signal, tolerated by the batched driver)
    normalizes to [].
    """
    return [c if isinstance(c, ScoredCandidate) else ScoredCandidate(c)
            for c in (batch if batch is not None else [])]


def _split_scored(batch: Sequence) -> Tuple[List[Configuration], Optional[List[float]]]:
    """Normalize an ask batch (ScoredCandidates and/or bare Configurations)
    into parallel (configurations, priorities) lists; priorities is None
    when nothing in the batch carried a score (all-FIFO, no point tagging)."""
    scored = as_scored(batch)
    configs = [c.configuration for c in scored]
    if all(c.score is None for c in scored):
        return configs, None
    return configs, [0.0 if c.score is None else float(c.score)
                     for c in scored]


@dataclass
class Trial:
    configuration: Configuration
    value: Optional[float]  # objective value (None => non-deployable)
    action: str             # 'measured' | 'reused' | 'predicted' | 'failed'
    seq: int
    # SLA verdict under the adapter's objective constraints: True/False when
    # evaluated against one, None when unconstrained or unknowable (warm
    # predictions carry no constraint properties).  Infeasible trials are
    # real evidence — they train models — but are never incumbents.
    feasible: Optional[bool] = None


@dataclass
class OptimizerRun:
    optimizer: str
    metric: str
    mode: str
    trials: list = field(default_factory=list)
    operation_id: str = ""
    batch_size: int = 1
    max_inflight: Optional[int] = None  # set when the pipelined engine ran

    @property
    def num_trials(self) -> int:
        return len(self.trials)

    @property
    def num_measured(self) -> int:
        return sum(1 for t in self.trials if t.action == "measured")

    @property
    def num_reused(self) -> int:
        return sum(1 for t in self.trials if t.action in ("reused", "predicted"))

    @property
    def num_infeasible(self) -> int:
        return sum(1 for t in self.trials if t.feasible is False)

    @staticmethod
    def _incumbent_eligible(t: Trial) -> bool:
        """Incumbents are REAL, SLA-meeting observations: warm trials are
        surrogate predictions (an unmeasured guess must never be reported as
        the best found), and constraint-violating trials are infeasible."""
        return (t.value is not None and t.action != WARM_ACTION
                and t.feasible is not False)

    @property
    def best(self) -> Optional[Trial]:
        vals = [t for t in self.trials if self._incumbent_eligible(t)]
        if not vals:
            return None
        key = (lambda t: t.value) if self.mode == "min" else (lambda t: -t.value)
        return min(vals, key=key)

    @property
    def normalized_cost(self) -> float:
        """Paper §V-B1: new measurements / samples this run itself told.
        Foreign- and warm-folded history is other operations' spending (or
        free predictions) — counting it in the denominator understates the
        member's own cost."""
        own = sum(1 for t in self.trials
                  if t.action not in (FOREIGN_ACTION, WARM_ACTION))
        if not own:
            return 0.0
        return self.num_measured / own

    def best_value_by_step(self) -> list:
        out, best = [], None
        sign = 1.0 if self.mode == "min" else -1.0
        for t in self.trials:
            if self._incumbent_eligible(t):
                v = sign * t.value
                best = v if best is None else min(best, v)
            out.append(None if best is None else sign * best)
        return out


class SearchAdapter:
    """The 'Ray Tune wrapper' of §III-D: optimizer-facing view of a study.

    The driver asks an optimizer for a candidate batch, evaluates it here
    (:meth:`evaluate_batch` routes everything through
    ``DiscoverySpace.sample_batch`` so all TRACE bookkeeping happens — with
    ``workers > 1`` the experiments run on a thread pool), and the resulting
    trials are *told* back into :attr:`trials`, the only optimizer-visible
    state.  :meth:`evaluate` is the batch-of-one convenience used by legacy
    serial loops.
    """

    def __init__(self, ds: DiscoverySpace, metric: str, mode: str = "min",
                 operation_id: Optional[str] = None, optimizer_name: str = "opt",
                 objective=None):
        if mode not in ("min", "max"):
            raise ValueError(f"mode must be min|max, got {mode}")
        self.ds = ds
        self.metric = metric
        self.mode = mode
        # Optional ObjectiveSpec (repro.core.api.spec): scalarizes the
        # trial value from several measured properties and/or attaches hard
        # SLA constraints.  None keeps the single-metric behavior exactly.
        self.objective = objective
        self._constrained = objective is not None and bool(objective.constraints)
        meta = {"optimizer": optimizer_name, "metric": metric, "mode": mode}
        if self._constrained:
            meta["constraints"] = [c.describe() for c in objective.constraints]
        self.operation_id = operation_id or ds.begin_operation(
            "optimization", meta
        )
        self.trials: list = []
        # Digests proposed but not yet told (in-flight on an execution
        # backend).  The pipelined driver marks/clears these so ``ask`` never
        # re-proposes an outstanding candidate.
        self.pending: set = set()
        # Foreign-tell sync state: the highest sampling-record ``rowid`` this
        # adapter has folded, plus the value-None *failed* trials (own and
        # foreign alike — registered by tell()) that are provisional:
        # failures can be transient, so if a later foreign record shows the
        # configuration was successfully measured, sync_foreign upgrades the
        # trial's value in place instead of masking it.  Solo drivers never
        # sync, so both are inert outside campaigns.
        self.record_watermark: int = 0
        self._provisional_failed: dict = {}
        # Incrementally-maintained digest set over ``trials`` (tell() adds;
        # nothing ever leaves a history), so per-sync dedup is O(new rows)
        # instead of rebuilding a set over the whole history every call.
        self._history_digests: set = set()
        # Trials folded by warm_start (cross-space transfer): counted apart
        # from told trials so budgets/stopping rules never charge for them.
        self.warm_told: int = 0
        # Lazily-built {digest: configuration} of the finite space's
        # not-yet-told configurations, in enumeration order.  tell() evicts
        # told digests, so ``ask`` filters O(pool) instead of re-enumerating
        # O(|Ω|) every call (see Optimizer._unseen_candidates).  Pending and
        # warm digests stay IN the cache — pending clears on tell/requeue and
        # warm configurations may legitimately be re-proposed — and are
        # filtered per-ask.  The same walk records every digest's position
        # in the enumeration ({digest: row}, never evicted) and a mask over
        # those positions that tell() clears with the cache: the cached
        # pool's positions are the mask's true entries, in the same order,
        # found by one flatnonzero per ask rather than a lookup per
        # candidate.
        self._unseen_cache: Optional[dict] = None
        self._enumeration_rows: Optional[dict] = None
        self._unseen_mask: Optional[np.ndarray] = None
        # Unit-cube encodings, each configuration encoded once per adapter,
        # and only when an optimizer asks for them (random search, TPE and
        # the warm fold never do, so they pay nothing):
        # * one row per entry of ``trials``, in order — the history is
        #   append-only (tell, warm_start and the foreign fold append;
        #   nothing leaves it), so each call encodes just the trials
        #   appended since the last one, into a buffer that doubles as it
        #   grows (see encoded_trials);
        # * the |Ω| × d matrix of a finite space's enumeration (see
        #   encoded_enumeration).
        # Rows are found by position — a trial's index, a digest's row from
        # the walk above — and never by hashing a configuration again:
        # ``Configuration.digest`` is a sha256 of canonical JSON and costs
        # more than the encode it would save.
        self._encoded: Optional[np.ndarray] = None
        self._n_encoded = 0
        self._enumeration: Optional[np.ndarray] = None

    def unseen_pool(self) -> dict:
        """The cached not-yet-told enumeration of a finite space."""
        if self._unseen_cache is None:
            rows, unseen, mask = {}, {}, []
            for i, c in enumerate(self.space.all_configurations()):
                d = c.digest
                rows[d] = i
                mask.append(d not in self._history_digests)
                if mask[-1]:
                    unseen[d] = c
            self._enumeration_rows, self._unseen_cache = rows, unseen
            self._unseen_mask = np.array(mask, bool)
        return self._unseen_cache

    def unseen_rows(self, skip: set) -> np.ndarray:
        """Positions in the enumeration of :meth:`unseen_pool`'s
        configurations whose digest is not in ``skip``, in pool order."""
        mask = self._unseen_mask
        if skip:
            mask = mask.copy()
            for d in skip:
                row = self._enumeration_rows.get(d)
                if row is not None:
                    mask[row] = False
        return np.flatnonzero(mask)

    def encoded_enumeration(self, rows: np.ndarray) -> np.ndarray:
        """Unit-cube rows ``rows`` of the finite space's enumeration.

        The matrix is built on the first call, as the cartesian product of
        each dimension's unit grid (``Dimension.to_unit`` of its values) in
        ``all_configurations`` order — equal row for row to
        ``space.encode`` — and every later call gathers from it."""
        if self._enumeration is None:
            grids = [np.array([d.to_unit(v) for v in d.values], np.float64)
                     for d in self.space.dimensions]
            mesh = np.meshgrid(*grids, indexing="ij")
            self._enumeration = np.stack([m.ravel() for m in mesh], axis=1)
            tracing.count("encode.rows", len(self._enumeration))
        else:
            tracing.count("encode.rows_reused", len(rows))
        return self._enumeration[rows]

    def encoded_trials(self, keep: Sequence[bool]) -> np.ndarray:
        """Unit-cube rows of the trials where ``keep`` (one flag per entry
        of ``trials``) is true, in order; only trials appended since the
        last call run through ``space.encode``."""
        n, k = len(self.trials), self._n_encoded
        if n > k:
            buf = self._encoded
            if buf is None or len(buf) < n:
                buf = np.empty((max(n, 2 * k, 64),
                                len(self.space.dimensions)), np.float64)
                if k:
                    buf[:k] = self._encoded[:k]
                self._encoded = buf
            encode = self.space.encode
            for i in range(k, n):
                buf[i] = encode(self.trials[i].configuration)
            self._n_encoded = n
            tracing.count("encode.rows", n - k)
        tracing.count("encode.rows_reused", sum(keep[:k]))
        return self._encoded[:n][np.array(keep, bool)]

    @property
    def space(self):
        return self.ds.space

    # -- ask/tell -----------------------------------------------------------

    def tell(self, trials: Sequence[Trial]) -> None:
        """Record externally-evaluated trials into the optimizer-visible
        history (the 'tell' half of the protocol).  Partial batches are fine:
        the pipelined engine tells each trial as its backend completes it,
        without waiting for stragglers.

        Value-None failed trials (own failures and foreign-folded ones) are
        registered as *provisional*: a failure can be transient, and if a
        later sampling record shows another operation measured the
        configuration successfully, :meth:`sync_foreign` upgrades the
        trial's value in place rather than letting the failure mask it.
        """
        for t in trials:
            digest = t.configuration.digest
            self._history_digests.add(digest)
            if self._unseen_cache is not None:
                self._unseen_cache.pop(digest, None)
                row = self._enumeration_rows.get(digest)
                if row is not None:
                    self._unseen_mask[row] = False
            if t.value is None and t.action in ("failed", FOREIGN_ACTION):
                self._provisional_failed[digest] = t
        self.trials.extend(trials)

    def _objective_properties(self) -> tuple:
        """Properties the trial value is computed from."""
        if self.objective is not None and self.objective.scalarized:
            return self.objective.objective_properties()
        return (self.metric,)

    def _sample_objective(self, sample):
        """``(value, feasible)`` of a sample under this adapter's objective,
        or None when the sample lacks the properties the value needs (e.g. a
        foreign operation measured a different action space)."""
        obj = self.objective
        if obj is None or not obj.scalarized:
            if not sample.has(self.metric):
                return None
            value = sample.value(self.metric)
        else:
            if not all(sample.has(p) for p in obj.objective_properties()):
                return None
            value = obj.value(sample.value)
        feasible = None
        if self._constrained:
            feasible = obj.feasible(
                lambda p: sample.value(p) if sample.has(p) else None)
        return value, feasible

    def _make_trial(self, result: BatchResult, seq: int) -> Trial:
        if not result.ok:
            # a non-deployable configuration certainly does not meet an SLA
            return Trial(result.configuration, None, "failed", seq,
                         feasible=False if self._constrained else None)
        vf = self._sample_objective(result.sample)
        if vf is None:
            raise KeyError(
                f"objective properties {self._objective_properties()!r} not "
                f"all among action-space properties "
                f"{self.ds.actions.observed_properties}"
            )
        value, feasible = vf
        return Trial(result.configuration, value, result.action, seq,
                     feasible=feasible)

    def tell_result(self, result: BatchResult) -> Trial:
        """Tell ONE completed evaluation (the pipelined engine's tell path)."""
        trial = self._make_trial(result, len(self.trials))
        self.tell([trial])
        return trial

    def warm_start(self, entries: Sequence[Tuple[Configuration, float]]) -> int:
        """Fold cross-space transferred values into the model-visible history
        (the paper's §IV-3/4 reuse: surrogate predictions over a related,
        already-measured space warm-starting a fresh search).

        Each ``(configuration, value)`` entry becomes an
        ``action='warm'`` :class:`Trial`, appended in the given order — the
        caller supplies a deterministic order, and this method is rng-free,
        so warm-started trajectories are exactly reproducible.  Unlike
        :meth:`tell`, warm digests are NOT added to the seen set: a warm
        value is (usually) a *prediction*, and excluding its configuration
        from proposals would let an approximate surrogate veto ever
        measuring the true best.  The optimizer trains on warm values
        immediately (they count toward model-phase thresholds like
        ``n_initial``, exactly as foreign trials do) and re-proposing a warm
        configuration measures it for real — the measured trial then joins
        the history alongside the prediction, correcting the model.

        Warm trials are never told to the store (no sampling-record event:
        the source space's record is the single source of truth, as with
        foreign tells) and never charged against budgets or stopping rules
        — drivers count *own* told trials.  Returns the number folded.
        """
        folded = 0
        for config, value in entries:
            self.trials.append(
                Trial(config, float(value), WARM_ACTION, len(self.trials)))
            folded += 1
        self.warm_told += folded
        return folded

    def sync_foreign(self) -> int:
        """Fold other operations' sampling events into this history — the
        campaign foreign-tell path (paper §V: transparent sharing between
        concurrently-executing optimizers).

        Reads the space's record incrementally from ``record_watermark``
        (:meth:`SampleStore.records_since`: O(new rows), indexed) and
        appends one ``action='foreign'`` :class:`Trial` per *new* foreign
        configuration, so the optimizer's next model fit trains on the union
        of the fleet's history.  Digest-deduplicated against everything this
        adapter already knows — own trials, in-flight proposals, and
        previously folded foreign tells — so a configuration enters the
        history at most once no matter how many operations sampled it.
        Foreign ``failed`` events fold as value-None trials: the optimizer
        learns the configuration is non-deployable without re-paying for
        it.  A value-None failed trial is *provisional*, though — failures
        can be transient (quota, preemption) and the store permits
        re-measurement — so if a later record shows another operation
        successfully measured the same configuration, a foreign *recovery*
        trial carrying the measured value is appended at the current
        history position (never mutating the already-told failure: trial
        objects are shared with fleet event traces and per-member results,
        and rewriting them would retroactively falsify time-to-best
        metrics).  Recovery is the one case a digest legitimately appears
        twice in a history — once failed-None, once valued — and each
        digest recovers at most once.

        Safe to call at any time (records are appended only after their
        values are durable, so every folded trial's value is readable), and
        works identically when the foreign operations live in *other
        processes* sharing the store file.  Returns the number of trials
        folded; solo drivers never call this, which keeps their trajectories
        byte-identical.
        """
        store = self.ds.store
        # Snapshot the committed tail FIRST: everything at or below it is
        # either returned below or our own (already in the history), so the
        # watermark can safely jump to it even when own rows dominate the
        # range — repeated syncs never re-scan them.  Rows committing after
        # this read get higher rowids (single-writer id allocation) and are
        # picked up next sync.
        tail = store.last_record_rowid(self.ds.space_id)
        if tail <= self.record_watermark:
            return 0
        folded = 0
        # Page the range instead of materializing it: each page holds at
        # most RECORD_PAGE_SIZE entries and its configurations are
        # prefetched in ONE batched (cache-assisted) read — at 10⁶-record
        # depth a first sync streams the record in bounded memory, and on
        # the served backend a page costs two round-trips, not 2·page_size.
        for page in self._record_pages(store, tail):
            interesting = [
                rec.config_digest for rec in page
                if rec.config_digest not in self._history_digests
                or rec.config_digest in self._provisional_failed]
            configs = store.get_configurations(interesting)
            folded += self._fold_page(store, page, configs)
        self.record_watermark = tail
        return folded

    def _record_pages(self, store, tail: int):
        """Snapshot-bounded pages of foreign records in (watermark, tail]."""
        from ..store.base import RECORD_PAGE_SIZE
        watermark = self.record_watermark
        while watermark < tail:
            page = store.records_since(self.ds.space_id, watermark,
                                       limit=RECORD_PAGE_SIZE,
                                       exclude_operation=self.operation_id,
                                       upto_rowid=tail)
            if page:
                yield page
            if len(page) < RECORD_PAGE_SIZE:
                return  # LIMIT not hit: the remaining range is exhausted
            watermark = page[-1].rowid

    def _fold_page(self, store, records, configs: dict) -> int:
        folded = 0
        for rec in records:
            provisional = self._provisional_failed.get(rec.config_digest)
            seen = (rec.config_digest in self._history_digests
                    or rec.config_digest in self.pending)
            if seen and provisional is None:
                continue
            config = configs.get(rec.config_digest) \
                or store.get_configuration(rec.config_digest)
            if config is None:  # pragma: no cover - store corruption guard
                continue
            if rec.action == "failed":
                if seen:
                    continue  # a trial (provisional or not) already stands
                self.tell([Trial(
                    config, None, FOREIGN_ACTION, len(self.trials),
                    feasible=False if self._constrained else None,
                )])  # registers provisional
                folded += 1
                continue
            sample = self.ds._reconstruct(rec.config_digest, config)
            vf = self._sample_objective(sample)
            if vf is None:
                # foreign operation measured a different action space's
                # properties; nothing this study can train on
                continue
            value, feasible = vf
            if provisional is not None:
                # the earlier failure (own or foreign) was transient:
                # another operation since measured this configuration —
                # append a recovery trial at the CURRENT position (the
                # failed trial stays untouched; see docstring), at most
                # once per digest
                del self._provisional_failed[rec.config_digest]
            self.tell([Trial(config, value, FOREIGN_ACTION, len(self.trials),
                             feasible=feasible)])
            folded += 1
        return folded

    def evaluate_batch(self, configurations: Sequence,
                       workers: int = 1, executor=None,
                       backend=None) -> List[Optional[float]]:
        """Evaluate a candidate batch and tell the results.

        Accepts :class:`ScoredCandidate` lists (the ``ask`` contract) or
        bare configurations; acquisition scores are forwarded as work-item
        priorities so scheduling backends measure best-first.  Experiments
        fan out over an execution backend (``workers`` threads, a
        caller-owned ``executor`` reused across batches, or any backend
        accepted by ``DiscoverySpace.sample_batch``); trials are appended in
        submission order so the history (and therefore every subsequent
        ``ask``) is deterministic regardless of completion order.  Failed
        measurements become ``action='failed'`` trials with value None.
        """
        configs, priorities = _split_scored(configurations)
        results = self.ds.sample_batch(
            configs, operation_id=self.operation_id, workers=workers,
            executor=executor, backend=backend, priorities=priorities)
        with tracing.span("tell"):
            batch = [self._make_trial(result, len(self.trials) + i)
                     for i, result in enumerate(results)]
            self.tell(batch)
        return [t.value for t in batch]

    def evaluate(self, configuration) -> Optional[float]:
        return self.evaluate_batch([configuration])[0]

    def seen_digests(self) -> set:
        return self._history_digests | self.pending

    def signed(self, value: float) -> float:
        """Value in canonical minimization orientation."""
        return value if self.mode == "min" else -value


class Optimizer(abc.ABC):
    """Ask-only optimizer interface (observation happens via history).

    Implementations propose candidate *batches*; they never evaluate.  The
    contract for :meth:`ask`:

    * return up to ``n`` configurations, all distinct and none already in the
      adapter's history (an exhausted finite space returns fewer, possibly
      ``[]`` which stops the run);
    * with ``n=1`` the rng consumption must match the classic one-step
      suggest exactly, so serial trajectories are reproducible;
    * model state must come from ``adapter.trials`` only — pending proposals
      within the batch are accounted for by excluding them from the pool, not
      by mutating shared state (the paper's multi-worker setting: another
      process may append to the store between ask and tell).
    """

    name = "optimizer"

    def __init__(self, seed: int = 0, backend: str = "numpy",
                 max_candidates: int = 512):
        """``backend`` selects the ask-scoring implementation (``numpy`` —
        the reference — or the accelerated ``jax``/``pallas`` paths, see
        :mod:`.accel`); unavailable accelerators degrade to numpy rather
        than raise.  ``max_candidates`` caps the per-ask candidate pool the
        acquisition is scored over (the accelerated backends score the
        whole pool in one device call, so large pools are cheap there)."""
        from .accel import resolve_backend
        self.seed = seed
        self.backend = resolve_backend(backend)
        if max_candidates < 1:
            raise ValueError(
                f"max_candidates must be >= 1, got {max_candidates}")
        self.max_candidates = max_candidates

    @abc.abstractmethod
    def ask(self, adapter: SearchAdapter, rng: np.random.Generator,
            n: int = 1) -> List[ScoredCandidate]:
        """Propose up to ``n`` next candidates ([] => space exhausted).

        Each candidate carries the acquisition score that ranked it (None
        for unscored proposals); drivers forward scores as scheduling
        priorities.  Scoring must never change rng consumption — the n=1
        stream stays draw-for-draw identical to the classic suggest step.
        """

    def suggest(self, adapter: SearchAdapter, rng: np.random.Generator) -> Optional[Configuration]:
        """Single-candidate convenience wrapper over :meth:`ask` — returns
        the bare configuration (the classic suggest contract; the score is
        scheduling metadata with no meaning for a batch of one).  Tolerates
        subclasses whose ``ask`` still returns bare configurations, like
        every other consumer of the ask batch."""
        batch = as_scored(self.ask(adapter, rng, n=1))
        return batch[0].configuration if batch else None

    # -- helpers shared by concrete optimizers ---------------------------------

    @staticmethod
    def _unseen_candidates(adapter: SearchAdapter, rng: np.random.Generator,
                           max_candidates: int = 512,
                           exclude: Optional[set] = None) -> list:
        """Candidate pool: unsampled configurations of a finite space (or
        random draws for continuous spaces).  ``exclude`` removes candidates
        already proposed earlier in the current batch.

        Finite spaces are ALWAYS enumerated and filtered, whatever their
        size: the old ``size <= 4096`` cutoff sent large finite spaces
        through the rejection-sampling loop below, whose try cap made a
        near-exhausted pool (most digests seen, so almost every draw
        rejects) return ``[]`` — falsely reporting exhaustion and stopping
        the run with unsampled configurations still on the table.
        Enumeration finds exactly the unseen remainder; when it exceeds
        ``max_candidates``, a uniform subsample keeps the pool bounded.
        The rejection loop now serves only truly continuous spaces, where
        ``[]`` genuinely cannot mean exhaustion.

        Finite enumeration is served from the adapter's told-invalidated
        cache when it has one (:meth:`SearchAdapter.unseen_pool`): the space
        is walked ONCE per adapter instead of once per ask — at depth d over
        |Ω| that is O(|Ω| + Σ pool) instead of O(d·|Ω|).  Dict insertion
        order preserves enumeration order, so the filtered pool (and the
        subsample drawn from it) is draw-for-draw identical to a fresh
        enumeration.  Adapters without the cache (ask-only stubs, legacy
        wrappers) fall back to enumerating.  :meth:`_unseen_candidates_rows`
        returns the same pool with each candidate's enumeration row."""
        return Optimizer._unseen_candidates_rows(
            adapter, rng, max_candidates, exclude)[0]

    @staticmethod
    def _unseen_candidates_rows(adapter: SearchAdapter,
                                rng: np.random.Generator,
                                max_candidates: int = 512,
                                exclude: Optional[set] = None) -> tuple:
        """``(pool, rows)``: :meth:`_unseen_candidates`'s pool, the same rng
        draws, and each candidate's position in the space's enumeration (an
        index array threaded through the filter and the subsample), so a
        scorer gathers the encoded pool from
        :meth:`SearchAdapter.encoded_enumeration` instead of encoding it.
        ``rows`` is None where the pool does not come from the cached
        enumeration: a continuous or mixed space, whose pool is sampled, or
        an adapter without the cache."""
        with tracing.span("ask.pool"):
            space = adapter.space
            if space.finite:
                unseen = getattr(adapter, "unseen_pool", None)
                rows = None
                if unseen is not None:
                    skip = adapter.pending if not exclude \
                        else adapter.pending | exclude
                    pool = [c for d, c in unseen().items() if d not in skip]
                    rows = adapter.unseen_rows(skip)
                else:
                    seen = adapter.seen_digests()
                    if exclude:
                        seen = seen | exclude
                    pool = [c for c in space.all_configurations()
                            if c.digest not in seen]
                if len(pool) > max_candidates:
                    idx = rng.choice(len(pool), size=max_candidates,
                                     replace=False)
                    pool = [pool[i] for i in idx]
                    if rows is not None:
                        rows = rows[idx]
                return pool, rows
            seen = adapter.seen_digests()
            if exclude:
                seen |= exclude
            out, tries = [], 0
            while len(out) < max_candidates and tries < max_candidates * 4:
                c = space.sample_configuration(rng)
                if c.digest not in seen:
                    # the draw itself joins `seen`: without this, a continuous
                    # space that happens to re-draw the same point (coarse
                    # dimensions, near-exhausted pools) returns a pool with
                    # duplicates and `ask` can emit a non-distinct batch,
                    # breaking its documented contract
                    seen.add(c.digest)
                    out.append(c)
                tries += 1
            return out, None

    @staticmethod
    def _encoded_trials(adapter: SearchAdapter, keep: list) -> np.ndarray:
        """Unit-cube rows of the trials where ``keep`` is true: gathered
        from the adapter's encoded history
        (:meth:`SearchAdapter.encoded_trials`), or encoded row by row for
        an adapter without one (ask-only stubs)."""
        gather = getattr(adapter, "encoded_trials", None)
        if gather is not None:
            return gather(keep)
        tracing.count("encode.rows", sum(keep))
        return np.stack([adapter.space.encode(t.configuration)
                         for t, k in zip(adapter.trials, keep) if k])

    @staticmethod
    def _history_arrays(adapter: SearchAdapter):
        """(X, y) over successful trials, y in minimization orientation.

        X gathers the valued trials' rows of the adapter's encoded history
        (each trial encoded once, see :meth:`SearchAdapter.encoded_trials`),
        bit-identical to encoding each trial on every ask; y is ``signed``
        applied to the value array at once, bit-identical to signing each
        value (a negation is exact)."""
        values = [t.value for t in adapter.trials]
        keep = [v is not None for v in values]
        if not any(keep):
            return np.zeros((0, len(adapter.space.dimensions))), np.zeros((0,))
        with tracing.span("ask.encode.history"):
            X = Optimizer._encoded_trials(adapter, keep)
            y = adapter.signed(np.array([v for v in values if v is not None]))
        return X, y

    @staticmethod
    def _constrained(adapter: SearchAdapter) -> bool:
        """True when the adapter's objective carries hard SLA constraints
        (duck-typed: ask-only stubs without an objective are unconstrained)."""
        obj = getattr(adapter, "objective", None)
        return obj is not None and bool(obj.constraints)

    @staticmethod
    def _feasibility_arrays(adapter: SearchAdapter):
        """(X, z) over trials with a KNOWN feasibility verdict, z = ±1.

        Failed trials count (labelled infeasible at tell time under a
        constrained objective); warm predictions carry None and are skipped
        — the feasibility classifier trains on evidence only."""
        feasible = [t.feasible for t in adapter.trials]
        keep = [f is not None for f in feasible]
        if not any(keep):
            return (np.zeros((0, len(adapter.space.dimensions))),
                    np.zeros((0,)))
        X = Optimizer._encoded_trials(adapter, keep)
        z = np.array([1.0 if f else -1.0 for f in feasible if f is not None])
        return X, z

    @staticmethod
    def _best_feasible(adapter: SearchAdapter) -> Optional[float]:
        """Best (signed, minimization-oriented) value over trials not known
        to violate a constraint — the incumbent a constrained acquisition
        improves on.  None when no such value exists yet."""
        vals = [adapter.signed(t.value) for t in adapter.trials
                if t.value is not None and t.feasible is not False]
        return min(vals) if vals else None

    @staticmethod
    def _top_n(candidates: list, score: np.ndarray, n: int) -> List[ScoredCandidate]:
        """The n best-scoring candidates (with their acquisition scores), in
        score order.  Stable on ties so ``_top_n(c, s, 1)[0].configuration
        == c[np.argmax(s)]`` exactly."""
        with tracing.span("ask.rank"):
            order = np.argsort(-score, kind="stable")
            return [ScoredCandidate(candidates[i], float(score[i]))
                    for i in order[:n]]

    @staticmethod
    def _random_n(pool: Sequence[Configuration], rng: np.random.Generator,
                  n: int) -> List[ScoredCandidate]:
        """Up to n unscored draws without replacement, one ``rng.integers``
        call per pick — the shared init-phase sampler, draw-for-draw
        identical to the classic single-suggest draw at n=1."""
        pool = list(pool)
        out: List[ScoredCandidate] = []
        for _ in range(min(n, len(pool))):
            out.append(ScoredCandidate(pool.pop(int(rng.integers(len(pool))))))
        return out


class _StoppingRule:
    """The paper's §V-B1 stopping rule, shared by both engines: halt when the
    incumbent best has not improved for ``patience`` consecutive trials.

    ``count`` supplies the trial count the ``min_trials`` floor is checked
    against; the default — everything in the adapter's history — is right
    for solo runs, but campaign members pass their OWN told-trial count so
    foreign-folded history can never satisfy a floor the caller asked this
    member to reach itself.
    """

    def __init__(self, adapter: SearchAdapter, patience: int, min_trials: int,
                 count: Optional[Callable[[], int]] = None):
        self.adapter = adapter
        self.patience = patience
        self.min_trials = min_trials
        self.count = count if count is not None else (
            lambda: len(adapter.trials))
        self.best: Optional[float] = None
        self.stall = 0
        self.stop = False

    def observe(self, value: Optional[float],
                feasible: Optional[bool] = None) -> None:
        """One trial's outcome.  ``feasible=False`` marks an SLA-violating
        trial: whatever its value, it can never improve the incumbent — the
        rule tracks the best *feasible* value, so a streak of ever-cheaper
        constraint violators still counts as stalling."""
        if value is not None and feasible is not False:
            sv = self.adapter.signed(value)
            if self.best is None or sv < self.best - 1e-12:
                self.best = sv
                self.stall = 0
            else:
                self.stall += 1
        else:
            self.stall += 1
        if self.count() >= self.min_trials and self.stall >= self.patience:
            self.stop = True


def run_optimizer(
    optimizer: Optimizer,
    ds: DiscoverySpace,
    metric: str,
    mode: str = "min",
    max_trials: int = 200,
    patience: int = 5,
    rng: Optional[np.random.Generator] = None,
    min_trials: int = 1,
    batch_size: int = 1,
    workers: int = 1,
    max_inflight: Optional[int] = None,
    backend: Union[ExecutionBackend, str, None] = None,
) -> OptimizerRun:
    """Run one optimization operation on a Discovery Space.

    Thin shim over the declarative engine: builds a one-member
    :class:`~repro.core.api.investigation.Investigation`
    (:meth:`~repro.core.api.investigation.Investigation.from_components`)
    and returns its member's run — trajectories are regression-gated
    draw-for-draw against the pre-shim engines.  Two engine shapes share
    the ask/tell protocol and the stopping rule:

    * **batched** (default): each step asks for a ``batch_size`` candidate
      batch and evaluates it with ``workers`` parallel experiment workers,
      barrier-synchronizing per batch (with the defaults this is the classic
      serial loop, draw-for-draw);
    * **pipelined** (``max_inflight=N``): up to N trials stay outstanding on
      an execution backend (a one-member fleet on the campaign coordinator,
      :func:`repro.core.campaign._drive_fleet`); completed trials are told
      and replaced immediately, so slow experiments never stall the next
      ask.  ``max_inflight=1`` reproduces the serial trajectory
      draw-for-draw.

    ``backend`` routes experiment execution (``serial | thread | process |
    queue`` or an :class:`~repro.core.execution.ExecutionBackend`); None
    keeps thread execution sized to the engine's parallelism.

    Stopping rule follows the paper (§V-B1): halt when the incumbent best has
    not improved for ``patience`` consecutive trials (or after ``max_trials``,
    or when the space is exhausted).  Trials are assessed in tell order, so
    the stopping decision is identical for serial and parallel execution of
    the same proposals.
    """
    if batch_size < 1:
        raise ValueError(f"batch_size must be >= 1, got {batch_size}")
    if max_inflight is not None and max_inflight < 1:
        raise ValueError(f"max_inflight must be >= 1, got {max_inflight}")
    from ..api.investigation import Investigation  # local: avoid cycle

    inv = Investigation.from_components(
        ds, [optimizer], metric, mode=mode,
        rngs=[rng if rng is not None
              else np.random.default_rng(optimizer.seed)],
        max_trials=max_trials, patience=patience, min_trials=min_trials,
        batch_size=batch_size, workers=workers, max_inflight=max_inflight,
        backend=backend)
    return inv.run().members[0].run


def hypergeom_p_found(space_size: int, target_count: int, n_draws: int) -> float:
    """P(≥1 target configuration after n draws without replacement).

    The paper's random-walk baseline (§V-B1) 'analytically described by the
    hypergeometric distribution':  1 - C(N-K, n) / C(N, n).
    """
    n_draws = min(n_draws, space_size)
    log_p_none = 0.0
    for i in range(n_draws):
        good_left = space_size - target_count - i
        total_left = space_size - i
        if good_left <= 0:
            return 1.0
        log_p_none += math.log(good_left) - math.log(total_left)
    return 1.0 - math.exp(log_p_none)
