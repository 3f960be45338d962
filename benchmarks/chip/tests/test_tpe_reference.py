"""The TPE reference (``reference.tpe_split``, ``reference.tpe_score``),
written from the published method and the program's docstrings, agrees
with the program's own numpy Parzen ratio to rounding, on seeded
configurations of the benchmark's space."""

import json

import numpy as np
import pytest

from conftest import BENCH


@pytest.fixture(scope="module")
def space_and_dims():
    import kinds.tgi_latency as tgi
    config = json.loads((BENCH / "configs" / "tgi-mi-opt.json").read_text())
    return tgi.make_space(config), config["dimensions"]


@pytest.mark.parametrize("n_good,n_bad", [(30, 90), (7, 0), (1, 3)])
@pytest.mark.parametrize("seed", [3, 2 ** 31 + 9])
def test_reference_matches_the_program(space_and_dims, seed, n_good, n_bad):
    import reference
    from repro.core.optimizers.tpe import tpe_score
    space, dims = space_and_dims
    configs = list(space.all_configurations())
    rng = np.random.default_rng(seed)
    pick = [configs[i] for i in rng.choice(len(configs), n_good + n_bad + 200,
                                           replace=False)]
    good, bad, cands = pick[:n_good], pick[n_good:n_good + n_bad], pick[-200:]
    got = tpe_score(space, good, bad, cands, bw=0.12)
    want = reference.tpe_score(dims, *([c.as_dict() for c in s]
                                       for s in (good, bad, cands)),
                               bandwidth=0.12)
    np.testing.assert_allclose(want, got, rtol=0, atol=1e-9)


def test_split_is_the_gamma_quantile():
    import reference
    good, bad = reference.tpe_split([5.0, 1.0, 4.0, 2.0, 3.0], 0.25)
    assert list(good) == [1, 3] and list(bad) == [4, 2, 0]
    good, bad = reference.tpe_split([2.0], 0.25)      # at least one good
    assert list(good) == [0] and len(bad) == 0


def test_control_departs_from_the_reference(space_and_dims):
    """bfloat16 moves the score by far more than float32 rounding does."""
    import reference
    space, dims = space_and_dims
    configs = [c.as_dict() for c in space.all_configurations()]
    rng = np.random.default_rng(5)
    pick = [configs[i] for i in rng.choice(len(configs), 600, replace=False)]
    sets = (pick[:100], pick[100:400], pick[400:])
    want = reference.tpe_score(dims, *sets, bandwidth=0.12)
    control = reference.tpe_score_device(dims, *sets, bandwidth=0.12)
    assert np.abs(control - want).max() > 1e-2
