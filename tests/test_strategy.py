"""choose_strategy edge cases: 1-D meshes, SASG off, replication threshold."""
from repro import compat
from repro.dist.strategy import (
    REPLICA_OVERHEAD,
    choose_strategy,
    worker_replication_fits,
)


def test_flat_on_2d_mesh(mesh2d):
    s = choose_strategy(mesh2d, sasg_enabled=True)
    assert s.name == "flat"
    assert s.uses_shard_map
    assert s.upload_axes == ("data",) and s.grad_axes == ("data",)
    assert s.fsdp_axis is None and s.inner_dp is None
    assert s.tp_axis == "model" and s.num_workers == 4


def test_hierarchical_on_3d_mesh(mesh3d):
    s = choose_strategy(mesh3d, sasg_enabled=True)
    assert s.name == "hierarchical"
    assert s.upload_axes == ("pod",) and s.grad_axes == ("pod", "data")
    # TP-only workaround: FSDP inside the manual pod region is a known
    # XLA SPMD partitioner limit (tests/test_known_limits.py)
    assert s.fsdp_axis is None
    assert s.inner_dp == "data" and s.num_workers == 2


def test_1d_mesh_no_model_axis():
    mesh = compat.make_mesh((8,), ("data",))
    s = choose_strategy(mesh, sasg_enabled=True)
    assert s.name == "flat"
    assert s.tp_axis is None
    assert s.num_workers == 8
    assert s.batch_axes == ("data",) and s.worker_axes == ("data",)


def test_sasg_disabled_gives_plain(mesh2d):
    s = choose_strategy(mesh2d, sasg_enabled=False)
    assert s.name == "plain"
    assert not s.uses_shard_map and s.upload_axes == ()
    assert s.grad_axes == ("data",)
    assert s.inner_dp is None


def test_plain_on_3d_mesh_shards_over_both_data_axes(mesh3d):
    s = choose_strategy(mesh3d, sasg_enabled=False)
    assert s.name == "plain"
    assert s.grad_axes == ("pod", "data")
    assert s.fsdp_axis == ("pod", "data")
    assert s.num_workers == 4  # DP degree, not SASG workers


def test_params_bytes_threshold_boundary(mesh3d):
    budget = 10_000
    tp = 2  # model axis size on mesh3d
    at_boundary = int(budget * tp / REPLICA_OVERHEAD)  # replica == budget
    assert worker_replication_fits(at_boundary, tp, budget)
    assert not worker_replication_fits(at_boundary + tp, tp, budget)

    s_fit = choose_strategy(
        mesh3d, sasg_enabled=True, params_bytes=at_boundary,
        replica_budget_bytes=budget,
    )
    assert s_fit.name == "hierarchical"  # boundary value still fits
    s_over = choose_strategy(
        mesh3d, sasg_enabled=True, params_bytes=at_boundary + tp,
        replica_budget_bytes=budget,
    )
    assert s_over.name == "plain"
