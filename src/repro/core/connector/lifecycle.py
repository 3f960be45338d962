"""Adapter: any `ExperimentConnector` as a standard `Experiment`.

This is the seam that keeps the rest of the system unchanged: the Discovery
Space, the claims machinery, and all four execution backends see an ordinary
``measure()`` call, while underneath the lifecycle drives provision / run /
parse / teardown with retries, billing, and structured failure provenance.
"""

from __future__ import annotations

from typing import Any, Mapping, Optional, Sequence

from .. import tracing
from ..actions import (Experiment, FailureRecord, MeasurementError,
                       ProvisioningError)
from ..clock import SYSTEM_CLOCK, Clock
from ..entities import Configuration
from .base import Deployment, ExperimentConnector
from .pricing import PricingModel
from .retry import RetryPolicy

__all__ = ["LifecycleExperiment", "PROVISIONED_COST"]

#: Property name under which the billed provisioned cost of a *successful*
#: trial is stored (failed trials carry their cost on the failure row).
PROVISIONED_COST = "provisioned_cost"


class LifecycleExperiment(Experiment):
    """Drive an :class:`ExperimentConnector` through the actuation lifecycle.

    Identity (name / version / parameterization) delegates to the connector,
    so converting a monolithic experiment into a connector behind this
    adapter leaves stored provenance — and therefore draw-for-draw optimizer
    trajectories — untouched.  A :class:`PricingModel`, when present, *does*
    join the parameterization (it changes the observed surface by adding the
    ``provisioned_cost`` property); the :class:`RetryPolicy` does not (it
    changes robustness, not the measured values).

    Failure semantics: ``ProvisioningError`` from ``provision`` is retried
    per the policy (fresh infrastructure each try, backoff on the injected
    clock); once exhausted, the trial fails as a ``MeasurementError``
    carrying a :class:`FailureRecord` with ``phase="provision"``, the attempt
    count, and every billed second — failed trials are not free.  ``run`` /
    ``parse`` failures tear down first, then fail with their own phase
    provenance.  Teardown is always attempted, once, even on crash paths.
    """

    def __init__(self, connector: ExperimentConnector,
                 retry: Optional[RetryPolicy] = None,
                 pricing: Optional[PricingModel] = None,
                 clock: Clock = SYSTEM_CLOCK):
        self.connector = connector
        self.retry = retry or RetryPolicy()
        self.pricing = pricing
        self.clock = clock

    # -- identity delegates to the connector --------------------------------

    @property
    def name(self) -> str:  # type: ignore[override]
        return self.connector.name

    @property
    def version(self) -> str:  # type: ignore[override]
        return self.connector.version

    @property
    def parameterization(self) -> Mapping[str, Any]:
        params = dict(self.connector.parameterization)
        if self.pricing is not None:
            params["pricing"] = self.pricing.to_json()
        return params

    @property
    def observed_properties(self) -> Sequence[str]:
        props = tuple(self.connector.observed_properties)
        if self.pricing is not None and PROVISIONED_COST not in props:
            props = props + (PROVISIONED_COST,)
        return props

    # -- the lifecycle -------------------------------------------------------

    def measure(self, configuration: Configuration) -> Mapping[str, float]:
        clock = self.clock
        digest = configuration.digest
        charged = 0.0

        def bill(t0: float) -> None:
            nonlocal charged
            if self.pricing is not None:
                charged += self.pricing.cost(configuration, clock.time() - t0)

        # -- provision: infrastructure faults retry on fresh resources ------
        deployment: Optional[Deployment] = None
        tries = 0
        while deployment is None:
            tries += 1
            t0 = clock.time()
            try:
                with tracing.span("connector.provision"):
                    deployment = self.connector.provision(configuration)
                bill(t0)  # the successful attempt's window is provisioned time
            except ProvisioningError as err:
                bill(t0)  # partially provisioned time is still billed
                if tries >= self.retry.provision_attempts:
                    raise MeasurementError(
                        f"provisioning failed after {tries} attempts: {err}",
                        failure=FailureRecord("provision", str(err), tries, charged),
                    ) from err
                clock.sleep(self.retry.delay(tries, digest))
            except MeasurementError as err:
                bill(t0)  # the configuration itself is non-deployable: terminal
                raise MeasurementError(
                    str(err),
                    failure=err.failure
                    or FailureRecord("provision", str(err), tries, charged),
                ) from err

        # -- run / parse: teardown always attempted, window fully billed ----
        t0 = clock.time()
        phase = "run"
        try:
            with tracing.span("connector.run"):
                raw = self._run(deployment, digest)
            phase = "parse"
            with tracing.span("connector.parse"):
                props = dict(self.connector.parse(raw))
        except ProvisioningError as err:
            self._teardown(deployment)
            bill(t0)
            raise MeasurementError(
                f"{phase} failed after {self.retry.run_attempts} attempts: {err}",
                failure=FailureRecord(phase, str(err), self.retry.run_attempts, charged),
            ) from err
        except MeasurementError as err:
            self._teardown(deployment)
            bill(t0)
            rec = err.failure or FailureRecord(phase, str(err), 1, 0.0)
            raise MeasurementError(
                str(err),
                failure=FailureRecord(rec.phase, rec.reason, rec.attempts, charged),
            ) from err
        except BaseException:
            self._teardown(deployment)  # crashes still release infrastructure
            raise
        self._teardown(deployment)
        bill(t0)

        out = {k: float(v) for k, v in props.items()}
        if self.pricing is not None:
            out[PROVISIONED_COST] = charged
        return out

    def _run(self, deployment: Deployment, digest: str) -> Any:
        """Run phase; infrastructure flakes retry on the same deployment."""
        tries = 0
        while True:
            tries += 1
            try:
                return self.connector.run(deployment)
            except ProvisioningError:
                if tries >= self.retry.run_attempts:
                    raise
                self.clock.sleep(self.retry.delay(tries, digest + ":run"))

    def _teardown(self, deployment: Deployment) -> None:
        """Idempotent teardown: attempted exactly once per deployment, and
        teardown's own failures never mask the trial's outcome."""
        if deployment.torn_down:
            return
        deployment.torn_down = True
        try:
            with tracing.span("connector.teardown"):
                self.connector.teardown(deployment)
        except Exception:
            pass
