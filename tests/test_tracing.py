"""The training step's device scopes and the Trainer's host spans
(``repro.obs``).

Device side: the scopes reach the compiled step's ``op_name`` metadata on
the flat SASG path, with the rule on a probe and on the plain path; the
model's matrix products sit in the two gradient scopes and the fused
top-k/EF kernel in ``step.exchange/encode`` (compiled for a described TPU
v5e); and with the metadata stripped the step compiles to the same program
as with the scopes made no-ops. Host side: a short Trainer run records its
spans in order, nested and with their steps, in a bounded buffer, with and
without a profiler session.
"""
import contextlib
import gc
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro import obs
from repro.compat import make_mesh
from repro.configs import get_config
from repro.core import CompressorConfig, SASGConfig, SelectionConfig, sasg_config, sgd_config
from repro.core.types import tree_bytes
from repro.dist.strategy import choose_strategy
from repro.models import build
from repro.optim import constant
from repro.train import Trainer, TrainerConfig, build_train_step

SCOPES = ("step.grad", "step.rule_grads", "step.exchange", "step.apply")
EXCHANGE = tuple(f"step.exchange/{c}" for c in ("rule", "encode", "collective", "commit"))
BATCH = {"tokens": jax.ShapeDtypeStruct((2, 64), jnp.int32),
         "labels": jax.ShapeDtypeStruct((2, 64), jnp.int32)}
_DEF = re.compile(r"^\s*(?:ROOT\s+)?%?([^\s=]+) = (.*)$")
_OPCODE = re.compile(r"(?<![\w.])([a-z][\w-]*)\(")
_OPNAME = re.compile(r'op_name="([^"]*)"')
_DEBUG = re.compile(r'^(\d+ [{"]|FileNames$|FunctionNames$|FileLocations$|StackFrames$)')


def _instructions(text):
    """(name, opcode, op_name) of every instruction of a compiled module."""
    for line in text.splitlines():
        m = _DEF.match(line)
        if not m:
            continue
        op = _OPCODE.search(m.group(2))
        on = _OPNAME.search(line)
        yield m.group(1), op.group(1) if op else "", on.group(1) if on else ""


def _strip(text):
    """The module without its debug information (op_name metadata, source
    locations and stack frames)."""
    text = re.sub(r",? metadata=\{[^}]*\}", "", text)
    return "\n".join(l for l in text.splitlines() if not _DEBUG.match(l))


def _step(mesh, algo, params_bytes=None):
    """The step of a two-layer Mamba-2 with full remat; ``params_bytes`` past
    what a worker can hold makes the strategy plain."""
    model = build(get_config("mamba2_370m").reduced(), remat="full")
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    strat = choose_strategy(mesh, sasg_enabled=True,
                            params_bytes=params_bytes or tree_bytes(shapes),
                            trunk_layers=model.pipeline.n_layers)
    return build_train_step(model, algo, mesh, strat, constant(0.01))


def _placed(tree, shardings):
    return jax.tree.map(lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
                        tree, shardings)


def _compiled_text(built):
    state = _placed(jax.eval_shape(built.init, jax.random.PRNGKey(0)), built.state_shardings)
    batch = _placed(BATCH, built.batch_sharding_fn(BATCH))
    return jax.jit(built.step).lower(state, batch).compile().as_text()


def _probe_config():
    return SASGConfig(compressor=CompressorConfig(name="topk_ef", k_ratio=0.05),
                      selection=SelectionConfig(enabled=True, max_delay=4, probe_fraction=0.5))


@pytest.fixture(scope="module")
def one_cpu():
    return make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])


@pytest.fixture(scope="module")
def flat(one_cpu):
    built = _step(one_cpu, sasg_config(k_ratio=0.05, max_delay=4))
    return built, _compiled_text(built)


@pytest.mark.parametrize("variant", ["flat", "probe", "plain"])
def test_scopes_reach_the_compiled_step(one_cpu, flat, variant):
    if variant == "flat":
        built, text = flat
    elif variant == "probe":
        built = _step(one_cpu, _probe_config())
        text = _compiled_text(built)
    else:
        built = _step(one_cpu, sgd_config(), params_bytes=10 ** 14)
        text = _compiled_text(built)
    assert built.strategy.name == ("plain" if variant == "plain" else "flat")
    names = [on for _, _, on in _instructions(text)]
    want = ("step.grad", "step.apply") if variant == "plain" else SCOPES + EXCHANGE
    for scope in want:
        assert any(f"/{scope}/" in on for on in names), scope
    if variant == "plain":
        assert not any("step.rule_grads" in on or "step.exchange" in on for on in names)
    # every matrix product is a gradient pass's (the compiler's own
    # rewrites may leave a product with no op_name at all)
    products = [on for _, op, on in _instructions(text)
                if op in ("dot", "convolution") and on]
    assert products
    assert all("/step.grad/" in on or "/step.rule_grads/" in on for on in products)


def test_scopes_change_nothing_but_metadata(one_cpu, flat, monkeypatch):
    _, text = flat
    monkeypatch.setattr(obs, "scope", lambda name: contextlib.nullcontext())
    unscoped = _compiled_text(_step(one_cpu, sasg_config(k_ratio=0.05, max_delay=4)))
    assert "step.grad" not in unscoped
    assert _strip(unscoped) == _strip(text)


@pytest.fixture(scope="module")
def described_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no compiler logs in /tmp
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's executables cannot be read back from the persistent
    # cache, so keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"))
    jax.config.update("jax_enable_compilation_cache", was)


def test_kernel_and_products_scoped_on_tpu(described_chip, monkeypatch):
    from repro.kernels.topk_ef import ops

    monkeypatch.setattr(ops, "_use_interpret", lambda: False)
    text = _compiled_text(_step(described_chip, sasg_config(k_ratio=0.05, max_delay=4)))
    kernels = [on for n, op, on in _instructions(text)
               if op == "custom-call" and n.startswith("topk_ef")]
    assert kernels
    assert all("/step.exchange/encode/" in on for on in kernels)
    products = [on for _, op, on in _instructions(text)
                if op in ("dot", "convolution") and on]
    assert products
    assert all("/step.grad/" in on or "/step.rule_grads/" in on for on in products)


# ---------------------------------------------------------------------------
# host spans
# ---------------------------------------------------------------------------

class _Rows:
    """Batches by step; collects garbage while fetching ``gc_at``."""

    def __init__(self, vocab, gc_at=None):
        self.vocab, self.gc_at = vocab, gc_at

    def batch_at(self, step):
        if step == self.gc_at:
            gc.collect()
        rows = (np.arange(2 * 65).reshape(2, 65) * (step + 3)) % self.vocab
        return {"tokens": jnp.asarray(rows[:, :-1], jnp.int32),
                "labels": jnp.asarray(rows[:, 1:], jnp.int32)}


@pytest.fixture(scope="module")
def trainer_run(flat):
    built, _ = flat
    vocab = get_config("mamba2_370m").reduced().vocab_size
    failed = []

    def fault(step):
        if step == 2 and not failed:
            failed.append(step)
            raise RuntimeError("planted node failure")

    tr = Trainer(built, _Rows(vocab, gc_at=1), TrainerConfig(total_steps=3, log_every=10 ** 9),
                 fault_hook=fault, log_fn=lambda s: None)
    tr.run(jax.random.PRNGKey(0))
    return tr, list(tr.spans.records)


def test_trainer_spans_nest_in_order(trainer_run):
    tr, spans = trainer_run
    main = [s for s in spans if s.name != "host.gc"]
    steps = [s for s in main if s.name == "train.step"]
    # steps 0, 1, then 2 fails, the run recovers to step 0 and runs 0-2
    assert [s.step for s in steps] == [0, 1, 2, 0, 1, 2]
    start = [s for s in main if s.name == "train.start"]
    assert [(s.step, s.parent) for s in start] == [(None, None)]
    assert start[0].t1_ns <= steps[0].t0_ns
    recover = [s for s in main if s.name == "train.recover"]
    assert [(s.step, s.parent) for s in recover] == [(2, None)]
    assert recover[0].t0_ns >= steps[2].t1_ns
    for st in steps:
        kids = [s for s in main if s.parent == "train.step" and st.t0_ns <= s.t0_ns <= st.t1_ns]
        assert all(k.step == st.step and k.t1_ns <= st.t1_ns for k in kids)
        names = [k.name for k in sorted(kids, key=lambda k: k.t0_ns)]
        if st is steps[2]:  # the planted failure comes before the fetch
            assert names == []
        else:
            assert names == ["train.fetch", "train.dispatch", "train.metrics_sync",
                             "train.checkpoint"]
    assert all(a.t0_ns <= b.t0_ns for a, b in zip(steps, steps[1:]))
    # the collection forced while fetching step 1 is a span inside the fetch
    assert any(s.name == "host.gc" and s.parent == "train.fetch" and s.step == 1
               for s in spans)
    # the gc callback is removed when the run ends
    assert tr.spans._on_gc not in gc.callbacks


def test_history_carries_the_rule(trainer_run):
    tr, _ = trainer_run
    for h in tr.history:
        assert {"rule_lhs", "rule_rhs"} <= set(h)
        assert np.isfinite(h["rule_lhs"]) and h["rule_lhs"] >= 0
    # the window holds the first update from step 1 on
    assert tr.history[0]["rule_rhs"] == 0.0
    assert tr.history[1]["rule_rhs"] > 0.0


def test_span_buffer_is_bounded():
    spans = obs.Spans()
    for i in range(obs.MAX_SPANS + 10):
        with spans.span("t.bounded", step_num=i):
            pass
    assert len(spans.records) == obs.MAX_SPANS
    assert spans.records[0].step == 10
    assert spans.records[-1].step == obs.MAX_SPANS + 9


def test_spans_reach_a_profiler_trace(flat, tmp_path):
    from jax.profiler import ProfileData

    built, _ = flat
    vocab = get_config("mamba2_370m").reduced().vocab_size
    tr = Trainer(built, _Rows(vocab), TrainerConfig(total_steps=1, log_every=10 ** 9),
                 log_fn=lambda s: None)
    jax.profiler.start_trace(str(tmp_path))
    try:
        tr.run(jax.random.PRNGKey(0))
    finally:
        jax.profiler.stop_trace()
    files = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    names = {e.name for p in ProfileData.from_file(files[-1]).planes
             for line in p.lines for e in line.events}
    assert {"train.start", "train.step", "train.fetch", "train.dispatch",
            "train.metrics_sync", "train.checkpoint"} <= names
