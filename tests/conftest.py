"""Shared fixtures. NOTE: device count is NOT forced here (smoke tests and
benches must see the real 1-CPU environment; only dryrun.py forces 512) —
tests that need a mesh spawn fake devices in their own module via an
env-guarded subprocess or use the 8-device modules below."""
import importlib.util
import os
import sys

# tests that need multiple devices are grouped in files that set this flag
# BEFORE importing jax (pytest imports conftest first, so set it here for the
# whole test session: 8 fake devices is small enough not to distort smoke
# perf, and lets sharding/integration tests build meshes).
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402

jax.config.update("jax_enable_x64", False)

import repro.compat  # noqa: E402  (make_mesh for the mesh fixtures below)

import pytest  # noqa: E402

# hypothesis fallback: the test image may not ship hypothesis (and cannot
# install it); load the deterministic stub so the property-test modules
# still collect and run. The real package always wins when present.
try:
    import hypothesis  # noqa: F401
except ImportError:
    _spec = importlib.util.spec_from_file_location(
        "hypothesis", os.path.join(os.path.dirname(__file__), "_hypothesis_stub.py")
    )
    _mod = importlib.util.module_from_spec(_spec)
    _spec.loader.exec_module(_mod)
    sys.modules["hypothesis"] = _mod

# bounded hypothesis profiles: CI runs the property suites with a fixed,
# smaller example budget (HYPOTHESIS_PROFILE=ci in .github/workflows/ci.yml).
# hasattr-guarded: the deterministic stub above has no profile machinery and
# simply runs each test's own max_examples.
from hypothesis import settings as _hyp_settings  # noqa: E402

if hasattr(_hyp_settings, "register_profile"):
    _hyp_settings.register_profile("ci", max_examples=20, deadline=None)
    _hyp_settings.register_profile("dev", deadline=None)
    _hyp_settings.load_profile(os.environ.get("HYPOTHESIS_PROFILE", "dev"))


def pytest_addoption(parser):
    parser.addoption(
        "--runslow", action="store_true", default=False,
        help="run tests marked slow (multi-minute known-limits XLA compiles)",
    )


def pytest_collection_modifyitems(config, items):
    if config.getoption("--runslow"):
        return
    skip_slow = pytest.mark.skip(reason="slow known-limits compile; pass --runslow")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip_slow)


def max_param_diff(sa, sb):
    """Host-side max-abs param difference between two TrainStates (the two
    states may live on different (sub)meshes, so compare as numpy)."""
    import numpy as np

    return max(
        float(np.max(np.abs(np.asarray(a, np.float32) - np.asarray(b, np.float32))))
        for a, b in zip(jax.tree.leaves(sa.params), jax.tree.leaves(sb.params))
    )


@pytest.fixture(scope="session")
def flat_pipe_check():
    """Shared flat-vs-pipelined equality harness (the acceptance check of the
    pipeline x SASG composition, promoted from tests/test_pipeline_sasg.py so
    the stage-sharded-EF suite reuses it verbatim).

    Builds the flat and pipelined train steps for the same (model, config),
    asserts the static bit counters and initial states are identical, runs
    every batch through both, and asserts per step: identical send/skip
    decisions, losses within ``loss_rtol``, params within ``param_tol``
    (fp32-reassociation / top-k tie-flip tiers — test_pipeline_sasg module
    docstring), and that only the pipelined run surfaces the stage-axis
    traffic split (pipe_bits_step == pipe_ring_bits_step +
    pipe_gather_bits_step). Finishes by asserting the cumulative rounds/bits
    counters agree. Returns the built steps, final states, and the per-step
    send history for test-specific follow-up asserts.

    ``overlap_leg=True`` (the default) additionally builds the SAME
    pipelined config with ``overlap=True`` — the per-bucket dispatch +
    double-buffered EF commit (``Transport.exchange_overlapped``) — and
    asserts it is BIT-IDENTICAL to the synchronous pipelined run every step
    (params, sends, losses): overlapping the exchange with backward compute
    must not move a single bit of error-feedback state.
    """
    import dataclasses as _dc

    import numpy as np

    from repro.dist.strategy import choose_strategy
    from repro.optim import constant
    from repro.train import build_train_step

    def run(model, scfg, mesh_flat, mesh_pipe, stages, batches, lr=0.05,
            param_tol=2e-2, loss_rtol=1e-2, overlap_leg=True):
        s_flat = choose_strategy(mesh_flat, sasg_enabled=True)
        s_pipe = choose_strategy(
            mesh_pipe, sasg_enabled=True, pipeline_stages=stages,
            trunk_layers=model.pipeline.n_layers,
        )
        assert s_pipe.pipelined and s_pipe.pipeline_stages == stages
        bf = build_train_step(model, scfg, mesh_flat, s_flat, constant(lr))
        bp = build_train_step(model, scfg, mesh_pipe, s_pipe, constant(lr))
        assert bf.bits_wire == bp.bits_wire and bf.bits_paper == bp.bits_paper
        sf, sp = bf.init(jax.random.PRNGKey(0)), bp.init(jax.random.PRNGKey(0))
        assert max_param_diff(sf, sp) == 0.0
        bo = so = None
        if overlap_leg:
            bo = build_train_step(model, _dc.replace(scfg, overlap=True),
                                  mesh_pipe, s_pipe, constant(lr))
            assert bo.bits_wire == bp.bits_wire
            so = bo.init(jax.random.PRNGKey(0))
        sents = []
        for batch in batches:
            sf, mf = bf.jit_step(sf, batch)
            sp, mp = bp.jit_step(sp, batch)
            assert float(mf["num_sent"]) == float(mp["num_sent"])
            sents.append(float(mp["num_sent"]))
            np.testing.assert_allclose(float(mf["loss"]), float(mp["loss"]),
                                       rtol=loss_rtol)
            assert max_param_diff(sf, sp) < param_tol
            if overlap_leg:
                so, mo = bo.jit_step(so, batch)
                assert float(mo["num_sent"]) == float(mp["num_sent"])
                assert float(mo["loss"]) == float(mp["loss"])
                assert max_param_diff(so, sp) == 0.0
            # only pipelined runs surface the stage-axis traffic, split into
            # the activation ring and the gradient payload gather
            assert "pipe_bits_step" not in mf
            assert float(mp["pipe_ring_bits_step"]) > 0
            assert float(mp["pipe_bits_step"]) == pytest.approx(
                float(mp["pipe_ring_bits_step"])
                + float(mp["pipe_gather_bits_step"])
            )
        if overlap_leg:
            # the double-buffered EF commit leaves the FULL worker state —
            # error buffers, stale payload cache, taus — bit-identical
            for a, b in zip(jax.tree.leaves(so.wstate),
                            jax.tree.leaves(sp.wstate)):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert float(sf.counters.rounds) == float(sp.counters.rounds)
        np.testing.assert_allclose(float(sf.counters.bits_wire),
                                   float(sp.counters.bits_wire), rtol=1e-6)
        np.testing.assert_allclose(float(sf.counters.bits_paper),
                                   float(sp.counters.bits_paper), rtol=1e-6)
        return {"bf": bf, "bp": bp, "sf": sf, "sp": sp, "sents": sents,
                "bo": bo, "so": so}

    return run


@pytest.fixture(scope="session")
def mesh2d():
    return repro.compat.make_mesh((4, 2), ("data", "model"))


@pytest.fixture(scope="session")
def mesh3d():
    return repro.compat.make_mesh((2, 2, 2), ("pod", "data", "model"))
