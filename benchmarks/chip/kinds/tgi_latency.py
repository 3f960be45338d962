"""The mean request latency of a TGI inference server over its settings.

A closed-form surface over the configuration file's finite space, shaped
as the paper describes MI-OPT: a batch times a context over
``oom_batch_tokens`` without flash attention runs out of memory and cannot
deploy (the experiment raises, and the engine records a failed trial with
no value); throughput grows with the batch the concurrency admits; a small
batch weight queues, a large one thrashes without flash attention; flash
attention is faster; a long context with a large batch pays a bump.  The
seed draws one noise value per configuration, in enumeration order, so
every seed has the same non-deployable points and the same landscape up to
that noise.
"""

from __future__ import annotations

import numpy as np

from repro.core import ActionSpace, Dimension, FunctionExperiment, ProbabilitySpace
from repro.core.actions import MeasurementError


def make_space(config: dict) -> ProbabilitySpace:
    make = {"discrete": Dimension.discrete, "categorical": Dimension.categorical}
    return ProbabilitySpace.make([make[d["kind"]](d["name"], d["values"])
                                  for d in config["dimensions"]])


def latency(values: dict, s: dict, noise: float) -> float | None:
    """Mean latency in ms at this point; None where it cannot deploy."""
    batch, seq = values["max_batch"], values["max_seq"]
    weight, flash = values["max_batch_weight"], values["flash_attention"]
    if not flash and batch * seq > s["oom_batch_tokens"]:
        return None
    t = s["work_ms"] / min(batch, values["max_concurrent"]) ** s["concurrency_exponent"]
    t += s["ms_per_new_token"] * values["max_new_tokens"]
    if weight < s["queueing_below_batch_weight"]:
        t += s["queueing_ms"]
    elif weight > s["thrashing_above_batch_weight"] and not flash:
        t += s["thrashing_ms"]
    if flash:
        t *= s["flash_factor"]
    if seq == s["long_seq"] and batch >= s["long_seq_min_batch"]:
        t *= s["long_seq_factor"]
    return t + noise


class Kind:
    """What the harness needs of a configuration kind: the space, the
    experiment and its actions, the metric, and each point's true value."""

    def __init__(self, config: dict, rng: np.random.Generator, spans):
        self.space = make_space(config)
        self.metric = config["metric"]
        self.mode = config["mode"]
        s = config["surface"]
        self.truth = {
            c.digest: latency(c.as_dict(), s, float(e)) for c, e in zip(
                self.space.all_configurations(),
                rng.normal(0.0, s["noise_ms"], self.space.size))}
        truth, metric = self.truth, self.metric

        def measure(configuration):
            with spans.span("measure"):
                value = truth[configuration.digest]
            if value is None:
                raise MeasurementError("non-deployable: out of memory")
            return {metric: value}

        self.experiment = FunctionExperiment(
            fn=measure, properties=(metric,), name="tgi-latency",
            params={"config": config["name"]})
        self.actions = ActionSpace.make([self.experiment])

    def expected_value(self, configuration) -> float | None:
        return self.truth[configuration.digest]
