"""The comparison that decides ``correct`` fails what it has to, at a size
a test run holds, on the CPU (the look for a chip is skipped; every other
part of a run is the benchmark's own):

- the control: the plain reference computed in float8 in the program's
  place;
- a step that leaves the parameters where they were;
- a skip step that applies nothing where it should apply the cached
  payload again;
- a selection rule that always uploads;
- half of each worker's rows left out, the mean taken over the rest.

  JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/tests/test_faults.py

The limits are the cell's own (``limits/``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

import tiny
from harness import common as C
from harness import train as TR


def _checks(cell, nums: dict) -> list:
    return [C.Check(k, float(nums[k]), float(cell.limits[k])) for k in cell.limits]


def _run_train(cell, seed=11):
    return TR.run(cell, tiny.Args(seed=seed, seconds=0.5), jax.devices()[:1],
                  C.peak_table()["TPU v5 lite"], C.CompileCounter())


def _failed(res) -> set:
    return {c.name for c in res.checks if not c.ok}


def test_train_sound_run_is_correct():
    res = _run_train(tiny.mamba_cell())
    assert res.correct, res.checks
    # the last set-up step is a skip, so skip_change_gap reads the
    # cached payload applied again
    assert res.notes["program_sent"][-1] == 0


def test_train_control_fails():
    cell = tiny.mamba_cell()
    batches = TR.batch_list(cell, 11, TR.SETUP_STEPS)
    ctl = TR.reference_readings(cell, 11, prec="fp8", batches=batches)
    ref = TR.reference_readings(cell, 11, batches=batches, forced=ctl["sent"])
    assert not all(c.ok for c in _checks(cell, TR.compare(ctl, ref)))


def test_train_unchanged_state_fails(monkeypatch):
    import repro.train.step as step

    monkeypatch.setattr(step, "apply_updates", lambda params, updates: params)
    cell = tiny.mamba_cell()
    res = _run_train(cell)
    assert not res.correct
    leaf_numbers = {"change1_median_gap", "change_median_gap", "skip_change_median_gap"}
    assert _failed(res) >= leaf_numbers & set(cell.limits)


def _wrap_exchange(monkeypatch, wrap):
    import repro.train.step as step

    build = step.build_exchange

    def built(*a, **k):
        ex = build(*a, **k)
        return ex._replace(run=wrap(ex.run))

    monkeypatch.setattr(step, "build_exchange", built)


def test_train_skip_applies_nothing_fails(monkeypatch):
    def wrap(run):
        def skip_nothing(*a, **k):
            update, ws, info = run(*a, **k)
            update = jax.tree.map(lambda u: jnp.where(info.send, u, jnp.zeros_like(u)), update)
            return update, ws, info
        return skip_nothing

    _wrap_exchange(monkeypatch, wrap)
    cell = tiny.mamba_cell()
    res = _run_train(cell)
    assert not res.correct
    assert _failed(res) >= {"window_gap", "skip_change_median_gap"} & set(cell.limits)


def test_train_always_upload_fails(monkeypatch):
    import repro.core.sasg as sasg

    monkeypatch.setattr(sasg, "should_send", lambda *a, **k: jnp.ones((), bool))
    res = _run_train(tiny.mamba_cell())
    assert res.notes["program_sent"] == [1] * TR.SETUP_STEPS
    assert not res.correct
    assert "rule_mismatch" in _failed(res)


def test_train_half_batch_fails(monkeypatch):
    import repro.models as models

    build = models.build

    def half(cfg, remat="none"):
        m = build(cfg, remat)
        loss = m.loss_fn
        return m._replace(loss_fn=lambda p, b: loss(
            p, {k: v[: v.shape[0] // 2] for k, v in b.items()}))

    monkeypatch.setattr(models, "build", half)
    res = _run_train(tiny.mamba_cell(rows=4))
    assert not res.correct
