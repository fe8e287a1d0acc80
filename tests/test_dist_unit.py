"""Direct unit tests for repro.dist edge cases the seed suite doesn't cover:
elastic_plan under ragged/underscale device counts, StragglerMonitor warmup
and baseline hygiene, resolve_spec on empty/scalar shapes, hints role
resolution, and pipeline input validation."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.dist import sharding as shd
from repro.dist.fault_tolerance import (Heartbeat, PreemptionHandler,
                                        StragglerMonitor, elastic_plan)
from repro.dist.hints import constrain, resolve, sharding_hints
from repro.dist.pipeline import pipeline_forward, stack_stage_params
from repro.nn.module import LogicalSpec, logical, resolve_spec


class FakeMesh:
    def __init__(self, shape):
        self.shape = shape


# ------------------------------------------------------------- elastic_plan
def _prod(xs):
    out = 1
    for x in xs:
        out *= x
    return out


@pytest.mark.parametrize("n,tp", [(248, 16), (24, 16), (7, 16), (1, 16),
                                  (512, 16), (17, 4), (256, 1)])
def test_elastic_plan_accounts_for_every_device(n, tp):
    plan = elastic_plan(n, tp=tp)
    assert _prod(plan["shape"]) + plan["devices_idle"] == n
    assert plan["devices_idle"] >= 0
    assert len(plan["shape"]) == len(plan["axes"])


def test_elastic_plan_non_divisible_host_counts():
    # lose 1 host (8 chips) of 31 in a tp=16 pod slice: data shrinks, tp holds
    p = elastic_plan(248, tp=16)
    assert p["shape"] == (15, 16)
    assert p["devices_idle"] == 8
    # 24 devices can't fill even two tp=16 rows: one row, 8 idle
    p = elastic_plan(24, tp=16)
    assert p["shape"] == (1, 16)
    assert p["devices_idle"] == 8


def test_elastic_plan_tp_larger_than_device_count():
    # tp > surviving devices: tp shrinks to what exists, nothing idles
    p = elastic_plan(4, tp=16)
    assert p["shape"] == (1, 4)
    assert p["tp"] == 4
    assert p["devices_idle"] == 0
    p = elastic_plan(7, tp=16)
    assert p["shape"] == (1, 7)


def test_elastic_plan_pods_only_when_divisible():
    assert elastic_plan(512, tp=16, want_pods=True)["axes"] == \
        ("pod", "data", "model")
    # data = 17 doesn't split into pods of 16: stays 2-axis
    p = elastic_plan(17 * 16, tp=16, want_pods=True)
    assert p["shape"] == (17, 16)
    assert p["axes"] == ("data", "model")


def test_elastic_plan_rejects_zero_devices():
    with pytest.raises(ValueError):
        elastic_plan(0)


# -------------------------------------------------------- straggler monitor
def test_straggler_monitor_never_flags_during_warmup():
    mon = StragglerMonitor(z_threshold=3.0, warmup_steps=5)
    # wild variation inside warmup must not flag (baseline not trusted yet)
    for i, dt in enumerate([0.1, 5.0, 0.1, 9.0, 0.1]):
        assert not mon.record(i, dt)


def test_straggler_monitor_constant_baseline_flags_outlier():
    # identical step times -> variance 0; the std floor must keep z finite
    # for normal steps yet still flag a 15x stall
    mon = StragglerMonitor(z_threshold=3.0, warmup_steps=3)
    for i in range(10):
        assert not mon.record(i, 0.1)
    assert mon.record(10, 1.5)
    assert not mon.record(11, 0.1)     # back to normal


def test_straggler_monitor_excludes_events_from_baseline():
    mon = StragglerMonitor(z_threshold=3.0, warmup_steps=3)
    for i in range(10):
        mon.record(i, 0.1)
    assert mon.record(10, 2.0)
    # the stall must not have raised the baseline: the next stall still flags
    assert mon.record(11, 2.0)
    s = mon.summary()
    assert s["straggler_events"] == 2
    assert s["healthy_steps"] == 10
    assert abs(s["mean_step_s"] - 0.1) < 1e-9


# -------------------------------------------------------------- resolve_spec
MESH = FakeMesh({"data": 4, "model": 8})


def test_resolve_spec_scalar_shape():
    assert resolve_spec((), LogicalSpec(()), {"mlp": "model"}, MESH) == P()


def test_resolve_spec_none_spec():
    assert resolve_spec((8, 8), None, {"mlp": "model"}, MESH) == P()


def test_resolve_spec_empty_rules():
    assert resolve_spec((8, 8), logical("embed", "mlp"), {}, MESH) == P()


def test_resolve_spec_zero_sized_dim_replicates():
    # a 0-length dim is never divisible-shardable; must not raise
    assert resolve_spec((0, 8), logical("mlp", None), {"mlp": "model"},
                        MESH) == P()


# --------------------------------------------------------------------- hints
def test_hints_resolve_outside_context_is_none():
    assert resolve((4, 8), ("dp", "tp")) is None


def test_hints_resolve_trims_dp_axes():
    mesh = FakeMesh({"pod": 2, "data": 16, "model": 4})
    with sharding_hints(mesh=mesh):
        # batch 8: (pod, data) product 32 doesn't divide -> pod alone does
        assert resolve((8, 64), ("dp", "tp")) == P("pod", "model")
        # batch 1: nothing divides; model divides 64
        assert resolve((1, 64), ("dp", "tp")) == P(None, "model")
        # nothing resolves at all -> None (constrain becomes identity)
        assert resolve((1, 3), ("dp", "tp")) is None


def test_hints_no_mesh_axis_reuse_across_dims():
    mesh = FakeMesh({"data": 2, "model": 4})
    with sharding_hints(mesh=mesh):
        # both dims ask for tp; the second must not reuse "model"
        assert resolve((8, 8), ("tp", "tp")) == P("model")


def test_hints_literal_axis_role_passthrough():
    mesh = FakeMesh({"data": 2, "model": 4})
    with sharding_hints(mesh=mesh):
        assert resolve((8, 8), (None, "model")) == P(None, "model")
        # unknown axis name -> replicated, not an error
        assert resolve((8, 8), ("pipe", None)) is None


def test_hints_constrain_roundtrip_values():
    mesh = shd.make_mesh((1, 1), ("data", "model"))
    x = jnp.arange(12.0).reshape(3, 4)
    with sharding_hints(mesh=mesh):
        y = constrain(x, ("dp", "tp"))
    np.testing.assert_array_equal(np.asarray(y), np.asarray(x))


# ----------------------------------------------------------------- sharding
def test_fit_axes_prefers_outer_axes():
    mesh = FakeMesh({"pod": 2, "data": 16, "model": 16})
    assert shd.fit_axes(256, ("pod", "data"), mesh) == ("pod", "data")
    assert shd.fit_axes(16, ("pod", "data"), mesh) == ("pod",)
    assert shd.fit_axes(1, ("pod", "data"), mesh) == ()
    # axes absent from the mesh are ignored
    assert shd.fit_axes(8, ("pipe", "data"), FakeMesh({"data": 4})) == \
        ("data",)


def test_unknown_rule_set_raises():
    with pytest.raises(KeyError):
        shd.dp_axes(FakeMesh({"data": 2}), "nope")
    with pytest.raises(KeyError):
        shd.mesh_rules(FakeMesh({"data": 2}), "nope")


def test_mesh_rules_drops_absent_axes():
    rules = shd.mesh_rules(FakeMesh({"data": 2, "model": 4}), "fsdp_tp")
    assert rules["embed"] == ("data",)          # pod absent -> filtered
    assert rules["mlp"] == ("model",)
    assert rules["expert_mlp"] is None


# -------------------------------------------------------- heartbeat / signal
def test_heartbeat_multiple_ranks(tmp_path):
    for r in (0, 2, 5):
        Heartbeat(str(tmp_path), rank=r).beat(1)
    assert Heartbeat.stale_ranks(str(tmp_path), timeout_s=3600) == []
    assert Heartbeat.stale_ranks(str(tmp_path), timeout_s=0) == [0, 2, 5]
    # a directory with no heartbeats has no stale ranks
    assert Heartbeat.stale_ranks(str(tmp_path / "empty"), timeout_s=0) == []


def test_preemption_handler_restore_is_idempotent():
    import signal as signal_lib
    before = signal_lib.getsignal(signal_lib.SIGTERM)
    h = PreemptionHandler()
    assert not h.requested
    h.restore()
    h.restore()
    assert signal_lib.getsignal(signal_lib.SIGTERM) is before


# ------------------------------------------------------------------ pipeline
def test_stack_stage_params_shapes():
    stacked = stack_stage_params([{"w": jnp.ones((3, 3)) * i}
                                  for i in range(4)])
    assert stacked["w"].shape == (4, 3, 3)
    np.testing.assert_array_equal(np.asarray(stacked["w"][2]),
                                  np.full((3, 3), 2.0))


def test_pipeline_forward_validates_inputs():
    mesh = jax.make_mesh((1,), ("pipe",))
    params = stack_stage_params([{"w": jnp.eye(4)}])
    x = jnp.ones((6, 4))

    def stage(p, a):
        return a @ p["w"]

    with pytest.raises(ValueError):
        pipeline_forward(stage, params, x, mesh=mesh, n_microbatches=4)
    with pytest.raises(ValueError):
        pipeline_forward(stage, params, x, mesh=mesh, n_microbatches=2,
                         axis="pod")
    bad = stack_stage_params([{"w": jnp.eye(4)}, {"w": jnp.eye(4)}])
    with pytest.raises(ValueError):
        pipeline_forward(stage, bad, x, mesh=mesh, n_microbatches=2)


def test_pipeline_forward_single_stage_matches_direct():
    mesh = jax.make_mesh((1,), ("pipe",))
    w = jax.random.normal(jax.random.PRNGKey(0), (8, 8)) * 0.3
    params = stack_stage_params([{"w": w}])
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 8))

    def stage(p, a):
        return jnp.tanh(a @ p["w"]) + a

    y = pipeline_forward(stage, params, x, mesh=mesh, n_microbatches=2)
    np.testing.assert_allclose(np.asarray(y), np.asarray(stage({"w": w}, x)),
                               atol=1e-6)
