"""The four-phase actuation interface and its deployment handle."""

from __future__ import annotations

import abc
from dataclasses import dataclass, field
from typing import Any, Mapping, Sequence

from ..entities import Configuration

__all__ = ["Deployment", "ExperimentConnector"]


@dataclass
class Deployment:
    """A handle on provisioned infrastructure for one trial.

    ``handle`` is whatever the connector needs to run against / tear down
    (a compiled executable, a Terraform state path, an instance id);
    ``meta`` carries free-form annotations.  ``torn_down`` makes teardown
    idempotent at the lifecycle level: a second teardown of the same handle
    is a no-op, so retry paths and zombie cleanups can always call it.
    """

    ident: str
    configuration: Configuration
    created_at: float = 0.0
    handle: Any = None
    meta: dict = field(default_factory=dict)
    torn_down: bool = False


class ExperimentConnector(abc.ABC):
    """A phased cloud actuation: provision -> run -> parse -> teardown.

    Identity mirrors :class:`~repro.core.actions.Experiment`:
    ``(name, version, parameterization)`` — the adapting
    :class:`~repro.core.connector.lifecycle.LifecycleExperiment` exposes it
    unchanged, so stored provenance for a connector-backed experiment is
    byte-identical to its monolithic predecessor's.

    Phase contract:

    * ``provision`` raises :class:`~repro.core.actions.ProvisioningError`
      for infrastructure faults (retryable) and
      :class:`~repro.core.actions.MeasurementError` when the configuration
      itself cannot be deployed (terminal).
    * ``run`` returns an opaque raw result; infrastructure flakes mid-run may
      raise ``ProvisioningError`` (retried on the same deployment up to the
      policy's ``run_attempts``).
    * ``parse`` maps the raw result to ``{property: float}``; the default
      passes a mapping through.
    * ``teardown`` must be idempotent; the lifecycle always attempts it,
      on success, failure, and crash paths alike.
    """

    name: str = "connector"
    version: str = "1"
    #: True when the connector runs on the accelerator itself.  A device
    #: belongs to one process, so such a connector must measure in the
    #: process that owns it: ``Investigation`` refuses to hand it to the
    #: ``process`` or ``queue`` execution backends.
    needs_device: bool = False

    @property
    def parameterization(self) -> Mapping[str, Any]:
        return {}

    @property
    @abc.abstractmethod
    def observed_properties(self) -> Sequence[str]:
        """Names of the properties ``parse`` produces."""

    @abc.abstractmethod
    def provision(self, configuration: Configuration) -> Deployment:
        """Stand up infrastructure for one trial."""

    @abc.abstractmethod
    def run(self, deployment: Deployment) -> Any:
        """Execute the benchmark; returns a raw result for ``parse``."""

    def parse(self, raw: Any) -> Mapping[str, float]:
        """Extract property values from a raw result."""
        return dict(raw)

    def teardown(self, deployment: Deployment) -> None:
        """Release the deployment's resources (idempotent; default free)."""
