"""Faults planted under the timed path, each of which the check must catch:

* ``scale_ei`` — the ask's expected improvement altered where the device
  produces it (scaled by 1.3);
* ``half_history`` — half of the history left out of the surrogate's fit;
* ``alter_store`` — a value altered on its way into the store.

Each is a context manager that patches the program while it is open.  The
cells run on one chip and train nothing, so the faults that need a
training step or several chips (a state returned unchanged, an exchange
between chips left out) cannot occur in them.
"""

from __future__ import annotations

import contextlib
import dataclasses
from unittest import mock


@contextlib.contextmanager
def scale_ei():
    from repro.core.optimizers import accel
    real = accel.gp_ei
    with mock.patch.object(accel, "gp_ei", lambda *a, **k: 1.3 * real(*a, **k)):
        yield


@contextlib.contextmanager
def half_history():
    from repro.core.optimizers.base import Optimizer
    real = Optimizer._history_arrays

    def half(adapter):
        X, y = real(adapter)
        return X[: len(y) // 2 + 1], y[: len(y) // 2 + 1]
    with mock.patch.object(Optimizer, "_history_arrays", staticmethod(half)):
        yield


@contextlib.contextmanager
def alter_store():
    from repro.core.store.sqlite import SampleStore
    real = SampleStore.put_values

    def put(self, digest, values):
        return real(self, digest, [dataclasses.replace(v, value=v.value * 1.0001)
                                   for v in values])
    with mock.patch.object(SampleStore, "put_values", put):
        yield


FAULTS = {"scale_ei": scale_ei, "half_history": half_history,
          "alter_store": alter_store}
