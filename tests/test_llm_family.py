"""Tests for the LLM deployment-space family (:mod:`repro.workloads.llm`).

The family turns in-repo models into related Discovery Spaces: five shared
deployment dimensions, member knobs (seq_len, devices) in the connector
parameterization, a catalog ``family`` block marking siblings.  Pinned
here: member space construction, the dryrun tier's measurement and its
non-deployable paths, catalog relatedness across the family's member
shifts (exact seq-shift match, positionally inferred mesh/kernel renames,
disjoint-dimension and family-filter exclusion of non-siblings), the spec
round-trip with the new ``meta``/``predict_remaining`` fields, and the
end-to-end sibling transfer with the step-⑧ predict-remaining sweep.
"""

import dataclasses
import math

import pytest

from repro.core import (ActionSpace, Configuration, DiscoverySpace,
                        Dimension, FunctionExperiment, Investigation,
                        MeasurementError, ProbabilitySpace, SampleStore,
                        SpaceCatalog)
from repro.core.api.spec import InvestigationSpec, TransferSpec
from repro.workloads.llm import (DeploymentSpaceFamily, FAMILY_NAME,
                                 LLMDryrunConnector, LLMWalltimeConnector)

ARCH = "nano-100m"


@pytest.fixture(scope="module")
def family():
    return DeploymentSpaceFamily(ARCH)


def a_config(mesh="2x2", sharding="fsdp", batch=2, kernel="xla",
             precision="bf16"):
    return Configuration.make({"mesh": mesh, "sharding": sharding,
                               "batch": batch, "kernel": kernel,
                               "precision": precision})


# ------------------------------------------------------------- construction


def test_member_space_has_the_five_deployment_dimensions(family):
    space = family.space(4)
    assert list(space.names) == ["mesh", "sharding", "batch", "kernel",
                                 "precision"]
    assert space.dimension("mesh").values == ("1x4", "2x2", "4x1")
    assert space.size == 3 * 2 * 4 * 3 * 2

    # topology-shift sibling: mesh labels move, cardinality and order stay
    assert family.space(8).dimension("mesh").values == ("1x8", "2x4", "8x1")
    assert family.space(8).size == space.size


def test_family_rejects_unknown_arch_kind_and_tier(family):
    with pytest.raises(ValueError):
        DeploymentSpaceFamily("no-such-model")
    with pytest.raises(ValueError):
        DeploymentSpaceFamily(ARCH, kind="finetune")
    with pytest.raises(ValueError):
        family.family_meta(512, 4, tier="quantum")
    with pytest.raises(ValueError):
        family.connector(512, 4, tier="quantum")


def test_members_share_the_family_block_and_differ_in_member_knobs(family):
    a = family.family_meta(512, 4, "dryrun")
    b = family.family_meta(1024, 8, "walltime")
    assert a["family"] == b["family"] == {
        "name": FAMILY_NAME, "arch": ARCH, "kind": "train"}
    assert a["member"] != b["member"]
    assert a["member"]["tier"] == "dryrun" and b["member"]["tier"] == "walltime"


def test_member_registers_family_meta_in_the_catalog(family):
    store = SampleStore(":memory:")
    ds = family.member(seq_len=512, devices=4, store=store)
    entry = SpaceCatalog(store).get(ds.space_id)
    assert entry.family == {"name": FAMILY_NAME, "arch": ARCH, "kind": "train"}
    assert entry.meta["member"] == {"seq_len": 512, "devices": 4,
                                    "tier": "dryrun", "hw": "tpu-v5e"}
    # the reserved registration keys are still the space's own
    assert entry.meta["size"] == ds.space.size


def test_same_member_knobs_different_seq_len_are_distinct_spaces(family):
    store = SampleStore(":memory:")
    a = family.member(seq_len=512, devices=4, store=store)
    b = family.member(seq_len=1024, devices=4, store=store)
    # identical Ω (same digest), distinct Discovery Spaces: the member knob
    # lives in the experiment parameterization (the FT-TRANS pattern)
    assert a.space.digest == b.space.digest
    assert a.space_id != b.space_id


# ---------------------------------------------------------------- measurement


def test_dryrun_member_measures_end_to_end(family):
    ds = family.member(seq_len=512, devices=4, store=SampleStore(":memory:"))
    results = ds.sample_batch(list(ds.remaining_configurations())[:6],
                              operation_id="op")
    assert all(r.ok for r in results)
    for r in results:
        s = r.sample
        assert s.value("step_time_s") > 0
        assert s.value("tokens_per_s") > 0
        assert s.value("cost_per_1m_tokens") > 0
        # max-of-terms roofline: the step is at least its compute term
        assert s.value("step_time_s") >= s.value("compute_s")


def test_dryrun_hbm_cap_is_a_non_deployable_point():
    conn = LLMDryrunConnector(ARCH, seq_len=512, devices=4,
                              hbm_fraction=1e-6)
    dep = conn.provision(a_config())
    raw = conn.run(dep)
    with pytest.raises(MeasurementError, match="over HBM"):
        conn.parse(raw)


def test_mesh_topology_mismatch_is_terminal_at_provision():
    conn = LLMDryrunConnector(ARCH, seq_len=512, devices=8)
    with pytest.raises(MeasurementError, match="non-deployable"):
        conn.provision(a_config(mesh="2x2"))  # 4 chips on an 8-chip member


def test_walltime_more_devices_than_host_is_non_deployable():
    conn = LLMWalltimeConnector(ARCH, seq_len=32, devices=4096)
    with pytest.raises(MeasurementError, match="non-deployable"):
        conn.provision(a_config(mesh="1x4096"))


@pytest.mark.parametrize("message, terminal", [
    ("RESOURCE_EXHAUSTED: Ran out of memory in memory space hbm", True),
    ("INTERNAL: Mosaic failed to compile TPU kernel", False),
])
def test_walltime_only_out_of_memory_is_non_deployable(monkeypatch, message,
                                                       terminal):
    """A configuration that does not fit the device is a search result; any
    other failure is the program's or the device's, and must surface."""
    from repro.models.model import LMModel

    def fail(self, params, batch):
        raise RuntimeError(message)

    monkeypatch.setattr(LMModel, "loss", fail)
    conn = LLMWalltimeConnector(ARCH, seq_len=32)
    config = a_config(mesh="1x1", batch=1)
    if terminal:
        with pytest.raises(MeasurementError, match="non-deployable"):
            conn.provision(config)
    else:
        with pytest.raises(RuntimeError, match="Mosaic"):
            conn.provision(config)


@pytest.mark.parametrize("backend", ["process", "queue"])
def test_walltime_member_refuses_child_process_backends(family, backend):
    """The chip belongs to the process that touched it: a walltime member
    may not be measured by child workers."""
    spec = family.investigation_spec(seq_len=32, devices=1, tier="walltime",
                                     max_trials=1)
    spec = dataclasses.replace(
        spec, execution=dataclasses.replace(spec.execution, backend=backend))
    inv = Investigation(spec)
    with pytest.raises(ValueError, match="accelerator"):
        inv.plan()
    with pytest.raises(ValueError, match="accelerator"):
        inv.run()


def test_device_free_tiers_keep_every_backend(family):
    dryrun = family.investigation_spec(seq_len=32, devices=1, max_trials=1)
    dryrun = dataclasses.replace(
        dryrun, execution=dataclasses.replace(dryrun.execution,
                                              backend="process"))
    assert Investigation(dryrun).plan().backend == "process"
    walltime = family.investigation_spec(seq_len=32, devices=1,
                                         tier="walltime", max_trials=1)
    walltime = dataclasses.replace(
        walltime, execution=dataclasses.replace(walltime.execution,
                                                backend="serial"))
    assert Investigation(walltime).plan().backend == "serial"


def test_walltime_parse_survives_zero_elapsed_time():
    # a virtual clock can legitimately observe zero elapsed seconds; the
    # parse guard must keep tokens_per_s finite instead of dividing by zero
    conn = LLMWalltimeConnector(ARCH, seq_len=32)
    out = conn.parse((0.0, {"batch": 2, "seq": 32}))
    assert out["step_time_s"] > 0
    assert math.isfinite(out["tokens_per_s"])


# -------------------------------------------------------------- relatedness


def seeded_member(family, store, seq_len, devices, n=8):
    ds = family.member(seq_len=seq_len, devices=devices, store=store)
    ds.sample_batch(list(ds.remaining_configurations())[:n],
                    operation_id="op")
    return ds


def test_seq_shift_sibling_is_an_exact_dimension_match(family):
    store = SampleStore(":memory:")
    src = seeded_member(family, store, 512, 4)
    tgt = family.member(seq_len=1024, devices=4, store=store)
    rel = SpaceCatalog(store).find_related(tgt.space, exclude=[tgt.space_id],
                                           metric="step_time_s")
    assert [r.entry.space_id for r in rel] == [src.space_id]
    assert rel[0].exact and rel[0].mapping == {}


def test_topology_shift_bridged_by_positional_mesh_rename(family):
    store = SampleStore(":memory:")
    src = seeded_member(family, store, 512, 4)
    tgt_space = family.space(8)
    rel = SpaceCatalog(store).find_related(tgt_space, metric="step_time_s")
    assert [r.entry.space_id for r in rel] == [src.space_id]
    # the mesh labels changed but kept cardinality and semantic order, so
    # the catalog inferred the positional rename (§IV-1) and flagged it
    assert rel[0].mapping == {"mesh": {"1x4": "1x8", "2x2": "2x4",
                                       "4x1": "8x1"}}
    assert rel[0].inferred_dims == ("mesh",)
    assert not rel[0].exact


def test_kernel_variant_rename_is_positionally_inferred(family):
    store = SampleStore(":memory:")
    src = seeded_member(family, store, 512, 4)
    # the same member knobs with a renamed kernel dimension (e.g. a vendor
    # kernel suite): same cardinality, same semantic order
    variant = DeploymentSpaceFamily(
        ARCH, kernels=("vendor-ref", "vendor-xla", "vendor-flash"))
    rel = SpaceCatalog(store).find_related(variant.space(4),
                                           metric="step_time_s")
    assert [r.entry.space_id for r in rel] == [src.space_id]
    assert rel[0].mapping == {"kernel": {"ref": "vendor-ref",
                                         "xla": "vendor-xla",
                                         "flash": "vendor-flash"}}
    assert rel[0].inferred_dims == ("kernel",)


def test_non_sibling_model_spaces_with_disjoint_dimensions_never_match(family):
    store = SampleStore(":memory:")
    seeded_member(family, store, 512, 4)
    # a different workload's deployment space: no shared dimension names
    other = ProbabilitySpace.make([
        Dimension.categorical("instance", ["m5.large", "c5.xlarge"]),
        Dimension.discrete("workers", [1, 2, 4]),
    ])
    cat = SpaceCatalog(store)
    assert cat.find_related(other, metric="step_time_s") == []
    assert cat.find_related(other, min_overlap=0.0) == []


def test_family_filter_excludes_dimension_twins_outside_the_family(family):
    store = SampleStore(":memory:")
    src = seeded_member(family, store, 512, 4)
    # an impostor space with the SAME five dimensions but no family block
    # (a different model that happens to share knob names)
    exp = FunctionExperiment(fn=lambda c: {"step_time_s": 1.0},
                             properties=("step_time_s",), name="impostor")
    twin = DiscoverySpace(space=family.space(4),
                          actions=ActionSpace.make([exp]), store=store)
    twin.sample_batch(list(twin.remaining_configurations())[:4],
                      operation_id="op")
    cat = SpaceCatalog(store)
    unfiltered = cat.find_related(family.space(8), metric="step_time_s")
    assert {r.entry.space_id for r in unfiltered} == {src.space_id,
                                                      twin.space_id}
    filtered = cat.find_related(family.space(8), metric="step_time_s",
                                family=family.family_meta(512, 4,
                                                          "dryrun")["family"])
    assert [r.entry.space_id for r in filtered] == [src.space_id]


# --------------------------------------------------------------------- spec


def test_investigation_spec_roundtrips_with_meta_and_predict_remaining(family):
    spec = family.investigation_spec(
        seq_len=512, devices=4, optimizer="tpe", max_trials=5, patience=5,
        transfer=TransferSpec(enabled=True, predict_remaining=True))
    d = spec.to_json()
    spec2 = InvestigationSpec.from_json(d)
    assert spec2.to_json() == d
    assert spec2.meta == family.family_meta(512, 4, "dryrun")
    assert spec2.transfer.predict_remaining is True
    assert spec2.connectors[0].factory == "llm-dryrun"
    assert spec2.connectors[0].params["arch"] == ARCH
    # predict_remaining defaults off and survives an explicit false
    assert TransferSpec.from_json(
        TransferSpec(enabled=True).to_json()).predict_remaining is False


def test_spec_path_builds_the_same_experiment_identity(family):
    store = SampleStore(":memory:")
    programmatic = family.member(seq_len=512, devices=4, store=store)
    spec = family.investigation_spec(seq_len=512, devices=4, max_trials=2,
                                     patience=3)
    inv = Investigation(spec, store=store)
    assert inv.ds.space_id == programmatic.space_id


def test_e2e_sibling_transfer_with_predict_remaining_sweep(family):
    store = SampleStore(":memory:")
    # the prior study: the short-sequence member, measured exhaustively at
    # the fast tier
    src = family.member(seq_len=512, devices=4, store=store)
    src.sample_batch(list(src.remaining_configurations()),
                     operation_id="historical-study")
    spec = family.investigation_spec(
        seq_len=1024, devices=4, optimizer="random", seed=0,
        max_trials=6, patience=7,
        transfer=TransferSpec(enabled=True, selection="clustering",
                              max_representatives=8, predict_remaining=True))
    res = Investigation(spec, store=store).run()
    t = res.transfer
    assert t is not None and t.applied
    assert t.source_space_id == src.space_id
    # the step-⑧ sweep landed the predicted surface in its own A*_pred
    # space, distinct from the member being searched
    assert t.n_predicted > 0
    assert t.predicted_space_id is not None
    assert t.predicted_space_id != Investigation(spec, store=store).ds.space_id
    assert t.summary()["predicted"] == t.n_predicted
    assert t.summary()["predicted_space_id"] == t.predicted_space_id
