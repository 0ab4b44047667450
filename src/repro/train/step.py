"""Training-step builder: model x SASG exchange x optimizer x mesh strategy.

The step has two nested domains (DESIGN.md §2):

  outer (auto/SPMD): parameter update, optimizer, window push, counters —
      everything replicated over worker axes and FSDP/TP sharded over the
      auto axes.
  inner (shard_map over strategy.worker_axes): per-worker gradients,
      selection rule, error feedback + compression, and the sparse
      all-gather exchange.

``plain`` strategy (no shard_map) is standard auto-SPMD data-parallel SGD —
used both as the non-SASG baseline and the fallback where worker replication
cannot fit (DESIGN.md §6).
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import obs
from repro.comm import bits as bits_lib
from repro.comm.transport import (
    ActivationLayout as TransportActivationLayout,
    StageInfo,
    supports_stage_payload,
)
from repro.core import metrics as CM
from repro.core.sasg import SASGConfig, build_exchange, update_global_state
from repro.core.types import (
    CommCounters,
    add_worker_axis,
    strip_worker_axis,
    tree_flatten_with_paths,
    tree_size,
    tree_sq_norm,
)
from repro.dist.pipeline import (
    build_pipelined_vag,
    build_stage_combine,
    resolve_microbatches,
)
from repro.dist.sharding import (
    ef_specs,
    param_specs,
    stage_only_spec,
    strip_stage_spec,
)
from repro.dist.strategy import Strategy
from repro.models.model import Model
from repro.optim import GradientTransformation, apply_updates


class TrainState(NamedTuple):
    params: Any
    opt_state: Any
    wstate: Any            # worker-stacked SASG state; () for plain
    gstate: Any
    counters: CommCounters
    rng: jax.Array


class BuiltStep(NamedTuple):
    step: Callable                     # pure: (state, batch) -> (state, metrics)
    init: Callable                     # (key) -> TrainState (sharded)
    jit_step: Callable                 # jitted/donating version of `step`
    state_shardings: Any
    batch_sharding_fn: Callable        # batch -> shardings tree
    exchange: Any
    strategy: Strategy
    bits_paper: float
    bits_wire: float
    param_specs: Any


# Knob: when True, worker-state shardings constrain only the worker dim and
# XLA propagates auto-axis shardings (workaround lever for partitioner bugs).
SIMPLE_WSTATE_SPECS = False


def pipeline_gather_bits(transport, params_shape, pdef, strategy, selection) -> float:
    """Static stage-axis GRADIENT-exchange wire bits per step per device.

    Honest about which path the built transport takes: on the payload-gather
    path it is one k-sized payload all-gather ((S-1)/S tiled) plus the tiny
    prepare-grad psum per grad computation; on the dense fallback it is the
    d-sized trunk all-gather + non-trunk psum per grad computation
    (``dist.pipeline.build_stage_combine``). Consumed by the train-step
    metrics (``pipe_gather_bits_step``) and the HLO audit's analytic pipe
    model, so both stay in sync with ``CM.PipelineCommModel``.
    """
    S = strategy.pipeline_stages
    # pipelined grad computations per step: fresh, plus the stale-params
    # auxiliary grad when selection is on (two probe grads when probing)
    n_combines = (
        1 if not selection.enabled
        else (3 if selection.probe_fraction < 1.0 else 2)
    )
    paths, leaves, _ = tree_flatten_with_paths(params_shape)
    trunk_pfx = ("/".join(str(k) for k in pdef.trunk_path),)

    def _under(pth, prefixes):
        return any(pth == p or pth.startswith(p + "/") for p in prefixes)

    def _dense_bits(prefixes, invert=False):
        return float(sum(
            leaf.size * jnp.dtype(leaf.dtype).itemsize * 8
            for pth, leaf in zip(paths, leaves)
            if _under(pth, prefixes) != invert
        ))

    if transport.stage is not None:
        trunk_wire = bits_lib.bucket_wire_bits(
            transport.bits_report(params_shape), trunk_pfx
        )
        prep_pfx = tuple("/".join(str(k) for k in p) for p in pdef.prepare_paths)
        return (
            (S - 1) / S * trunk_wire
            + n_combines * 2 * (S - 1) / S * _dense_bits(prep_pfx)
        )
    return n_combines * (
        (S - 1) / S * _dense_bits(trunk_pfx)
        + 2 * (S - 1) / S * _dense_bits(trunk_pfx, invert=True)
    )


def _worker_index(worker_axes):
    idx = jnp.zeros((), jnp.int32)
    for a in worker_axes:
        idx = idx * jax.lax.axis_size(a) + jax.lax.axis_index(a)
    return idx


def _rep(tree):
    return jax.tree.map(lambda _x: P(), tree)


def _worker_stacked(tree, wa):
    return jax.tree.map(lambda x: P(wa, *([None] * (np.ndim(x) - 1))), tree)


def build_train_step(
    model: Model,
    sasg_cfg: SASGConfig,
    mesh,
    strategy: Strategy,
    lr_schedule: Callable,
    optimizer: Optional[GradientTransformation] = None,
    donate: bool = True,
) -> BuiltStep:
    fold_lr = sasg_cfg.fold_lr and strategy.uses_shard_map
    M = strategy.num_workers
    waxes = strategy.worker_axes
    wa = (waxes if len(waxes) > 1 else (waxes[0] if waxes else None))

    # Pipeline composition: a stage axis only engages inside the worker
    # shard_map region, and needs the model's homogeneous trunk (PipelineDef)
    # to divide over the stages. choose_strategy applies soft fallbacks when
    # it is told the trunk depth; an incompatible hand-built Strategy is a
    # config error and fails eagerly here.
    stage = strategy.stage_axis if (
        strategy.pipelined and strategy.uses_shard_map
    ) else None
    pdef = model.pipeline
    if stage is not None:
        if pdef is None:
            raise ValueError(
                f"strategy requests pipeline_stages={strategy.pipeline_stages} "
                f"but model {model.config.name!r} has no PipelineDef "
                "(no homogeneous stage-stackable trunk)"
            )
        if pdef.n_layers % strategy.pipeline_stages != 0:
            raise ValueError(
                f"trunk depth {pdef.n_layers} does not divide over "
                f"{strategy.pipeline_stages} pipeline stages; pass "
                "trunk_layers to choose_strategy for the soft fallback"
            )
    trunk_paths = (tuple(str(k) for k in pdef.trunk_path),) if stage else ()

    params_shape = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    pspecs = param_specs(
        params_shape, mesh, strategy.fsdp_axis, strategy.tp_axis,
        stage_axis=stage, trunk_paths=trunk_paths,
    )

    def _stage_only(spec):
        """The manual-stage part of a param spec (trunk stacked dim)."""
        return stage_only_spec(spec, stage)

    def _no_stage(spec):
        """A param spec with the manual stage axis stripped (auto axes only)."""
        return strip_stage_spec(spec, stage)

    # Payload-gather hot path: when the compressor supports stage-local
    # encoding (block-local per_shard topk_ef) and the model's prepare/finish
    # param reads are disjoint, the trunk gradient is NEVER stage-gathered —
    # gradients stay stage-sliced, the transport compresses the local slice,
    # and only the k-sized payload crosses the stage axis. Everything else
    # (per_tensor/flat layouts, randk/qsgd/dense compressors, tied-embedding
    # models) takes the dense stage-combine fallback.
    payload_mode = (
        stage is not None
        and pdef.prepare_paths is not None
        and supports_stage_payload(sasg_cfg.compressor)
    )
    stage_info = None
    if payload_mode:
        _prefixes = tuple("/".join(p) for p in (trunk_paths or ()))
        _tpaths, _tleaves, _ = tree_flatten_with_paths(params_shape)
        trunk_dims = {
            pth: leaf.shape[0]
            for pth, leaf in zip(_tpaths, _tleaves)
            if any(pth == p or pth.startswith(p + "/") for p in _prefixes)
        }
        stage_info = StageInfo(
            axis=stage, num_stages=strategy.pipeline_stages,
            trunk_prefixes=_prefixes, trunk_dims=trunk_dims,
        )

    vag = jax.value_and_grad(model.loss_fn)
    # Inside the worker region, pipelined strategies swap value_and_grad for
    # the stage-pipelined version. On the fallback path the per-stage
    # gradient combine (trunk all-gather + stage-0-masked psum) is NOT fused
    # into the vag: it is threaded into the exchange as the transport's
    # stage composition (repro.comm.Transport.gather), so the exchange
    # always operates on — and densifies against — the FULL gradient tree.
    # On the payload path the vag itself is stage-local (stop-gradient loss
    # mask, dist.pipeline.build_pipelined_loss) and no dense combine exists.
    worker_vag = (
        build_pipelined_vag(
            pdef, stage, strategy.microbatches,
            combine=False, stage_local=payload_mode,
            act_layout=sasg_cfg.act_layout, engine=sasg_cfg.pipeline_engine,
        )
        if stage is not None else vag
    )
    stage_combine = (
        build_stage_combine(pdef, stage)
        if stage is not None and not payload_mode else None
    )

    # Manual axes of the worker region: the worker axes, the stage axis when
    # pipelining, and every axis of size 1. A size-1 axis partitions nothing,
    # so taking it as manual changes no numbers; it matters on TPU, whose
    # compiler cannot auto-partition a Pallas kernel (the fused top-k/EF
    # compressor) inside a region that still has auto axes.
    manual = set(waxes) | ({stage} if stage is not None else set()) | {
        a for a, n in zip(mesh.axis_names, mesh.devices.shape) if n == 1
    }

    if strategy.uses_shard_map:
        # inner_dp stays an AUTO axis: the in-pod gradient mean over it is the
        # automatic backward psum of the batch sharding — no manual reduce.
        axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        # the exchange's leaf specs never carry the manual stage axis: on
        # the fallback path the exchange sees the FULL gradient tree (trunk
        # gathered over stages first), and on the payload path the
        # stage-local slice must use the SAME TP-only blocked geometry as
        # the flat run (support-exactness) — either way, a stage entry in
        # the specs would diverge payload sizing from the non-pipelined run
        exchange = build_exchange(
            sasg_cfg,
            worker_axes=waxes,
            reduce_axes=(),
            num_workers=M,
            leaf_specs=jax.tree.map(
                _no_stage, pspecs, is_leaf=lambda x: isinstance(x, P)
            ),
            axis_sizes=axis_sizes,
            grad_combine=stage_combine,
            stage=stage_info,
        )
        bits_paper = exchange.bits_per_upload_paper(params_shape)
        bits_wire = exchange.bits_per_upload_wire(params_shape)
    else:
        exchange = None
        bits_paper = bits_wire = 32.0 * tree_size(params_shape)

    # Static stage-axis GRADIENT-exchange wire bits per step (per device),
    # honest about which path is taken. Ring (activation) traffic is modeled
    # separately inside the step (it depends on the batch shape).
    gather_bits_step = 0.0
    if stage is not None and strategy.uses_shard_map:
        gather_bits_step = pipeline_gather_bits(
            exchange.transport, params_shape, pdef, strategy,
            sasg_cfg.selection,
        )

    # ------------------------------------------------------------------
    # init + shardings
    # ------------------------------------------------------------------
    def init_all(key):
        params = model.init(key)
        opt_state = optimizer.init(params) if optimizer is not None else ()
        if strategy.uses_shard_map:
            ws = exchange.init_worker(params)
            wstate = jax.tree.map(
                lambda x: jnp.broadcast_to(
                    jnp.asarray(x)[None], (M,) + jnp.asarray(x).shape
                ),
                ws,
            )
            gstate = exchange.init_global()
        else:
            wstate, gstate = (), ()
        return TrainState(params, opt_state, wstate, gstate,
                          CommCounters.zeros(), key)

    state_shape = jax.eval_shape(init_all, jax.random.PRNGKey(0))

    def _opt_specs(os_shape):
        """Optimizer moments mirror param specs (keys mu/m/v); rest replicated."""
        pstruct = jax.tree.structure(params_shape)

        def rec(t):
            if isinstance(t, dict):
                return {
                    k: (pspecs if (k in ("mu", "m", "v")
                                   and jax.tree.structure(v) == pstruct) else rec(v))
                    for k, v in t.items()
                }
            if isinstance(t, (tuple, list)):
                return type(t)(rec(v) for v in t)
            return jax.tree.map(lambda _x: P(), t)

        return rec(os_shape)

    def _wstate_specs(ws_shape):
        """Worker dim over worker axes; stale_params additionally reuse param
        specs on their trailing dims (they ARE param-shaped, stage sharding
        included — they must mirror the params the pipelined forward slices).
        comp_state (EF buffers): stage-SHARDED on the payload-gather path
        (each stage owns its trunk slice's residuals, dist.sharding.ef_specs)
        and stage-replicated auto-axis specs on the dense-combine fallback.
        Either way the checkpointed logical array keeps the FULL trunk shape,
        so restore across stage counts is pure resharding."""
        base = _worker_stacked(ws_shape, wa)
        if not strategy.uses_shard_map or SIMPLE_WSTATE_SPECS:
            return base
        ef_pspecs = ef_specs(pspecs, stage, payload_mode)
        try:
            if jax.tree.structure(ws_shape.stale_params) == jax.tree.structure(params_shape):
                stale = jax.tree.map(
                    lambda x, ps: P(wa, *tuple(ps)), ws_shape.stale_params, pspecs
                )
                base = base._replace(stale_params=stale)
            if jax.tree.structure(ws_shape.comp_state) == jax.tree.structure(params_shape):
                err = jax.tree.map(
                    lambda x, ps: P(wa, *tuple(ps)),
                    ws_shape.comp_state, ef_pspecs,
                )
                base = base._replace(comp_state=err)
        except (AttributeError, ValueError):
            pass
        return base

    state_pspec = TrainState(
        params=pspecs,
        opt_state=_opt_specs(state_shape.opt_state),
        wstate=_wstate_specs(state_shape.wstate) if strategy.uses_shard_map else (),
        gstate=_rep(state_shape.gstate),
        counters=_rep(state_shape.counters),
        rng=P(),
    )
    to_sharding = lambda specs: jax.tree.map(
        lambda s: NamedSharding(mesh, s), specs, is_leaf=lambda x: isinstance(x, P)
    )
    state_shardings = to_sharding(state_pspec)

    def batch_sharding_fn(batch):
        ba = tuple(strategy.batch_axes)
        bspec = ba if len(ba) > 1 else (ba[0] if ba else None)
        return jax.tree.map(
            lambda x: NamedSharding(
                mesh, P(bspec, *([None] * (np.ndim(x) - 1)))
            ),
            batch,
        )

    # ------------------------------------------------------------------
    # the step
    # ------------------------------------------------------------------
    if strategy.uses_shard_map:

        def worker_fn(params, batch, wstate, gstate, lr, key, fs=None):
            wstate = strip_worker_axis(wstate)
            if strategy.inner_dp and strategy.inner_dp not in manual:
                batch = jax.tree.map(
                    lambda x: jax.lax.with_sharding_constraint(
                        x, P(strategy.inner_dp, *([None] * (x.ndim - 1)))
                    ),
                    batch,
                )
            key = jax.random.fold_in(key, _worker_index(waxes))
            # fs: replicated (M,) bool straggler mask (train.faults) — each
            # worker picks its own flag; None traces exactly the unfaulted
            # program (no gates added)
            force_skip = fs[_worker_index(waxes)] if fs is not None else None
            update, new_wstate, info = exchange.run(
                params, batch, wstate, gstate, lr, key, worker_vag,
                force_skip=force_skip,
            )
            # pin the densified update to the parameter sharding over the
            # AUTO axes (otherwise XLA replicates the fp32 update tree —
            # 32 GB/device on llama3-8b; EXPERIMENTS.md §Perf iteration 1)
            def _strip_manual(spec):
                out = []
                for entry in tuple(spec):
                    names = entry if isinstance(entry, tuple) else (entry,)
                    if entry is not None and any(n in manual for n in names):
                        out.append(None)
                    else:
                        out.append(entry)
                return P(*out)

            update = jax.tree.map(
                lambda u, s: jax.lax.with_sharding_constraint(u, _strip_manual(s)),
                update, pspecs,
            )
            return update, add_worker_axis(new_wstate), add_worker_axis(info)

        def _params_region_specs(params):
            """shard_map specs for the params input: replicated over worker
            axes; trunk leaves stage-sliced when pipelining (each stage gets
            its contiguous block of stacked layers)."""
            if stage is None:
                return _rep(params)
            return jax.tree.map(
                _stage_only, pspecs, is_leaf=lambda x: isinstance(x, P)
            )

        def _wstate_region_specs(ws):
            """shard_map specs for the worker state: worker dim over worker
            axes; stale_params additionally stage-sliced on the trunk so they
            mirror the params tree the pipelined grad_fn consumes. On the
            payload-gather path the EF buffers (comp_state) are stage-sliced
            the same way: encode sees the residuals of exactly the trunk
            slice it compresses."""
            base = _worker_stacked(ws, wa)
            if stage is None:
                return base
            try:
                if jax.tree.structure(ws.stale_params) == jax.tree.structure(params_shape):
                    stale = jax.tree.map(
                        lambda x, ps: P(wa, *tuple(_stage_only(ps))),
                        ws.stale_params, pspecs,
                    )
                    base = base._replace(stale_params=stale)
                if payload_mode and (
                    jax.tree.structure(ws.comp_state)
                    == jax.tree.structure(params_shape)
                ):
                    err = jax.tree.map(
                        lambda x, ps: P(wa, *tuple(_stage_only(ps))),
                        ws.comp_state, pspecs,
                    )
                    base = base._replace(comp_state=err)
            except (AttributeError, ValueError):
                pass
            return base

        def step(state: TrainState, batch, force_skip=None):
            lr = lr_schedule(state.gstate.step)
            key = jax.random.fold_in(state.rng, state.gstate.step)

            in_specs = (
                _params_region_specs(state.params),
                _worker_stacked(batch, wa),
                _wstate_region_specs(state.wstate),
                _rep(state.gstate),
                P(),
                P(),
            )
            if force_skip is not None:
                in_specs = in_specs + (P(),)  # replicated (M,) bool mask
            # outputs: update (params-structured, replicated), worker state
            # (same structure as input, worker-stacked), info (5 scalars with
            # a singleton worker dim)
            from repro.core.sasg import ExchangeInfo

            out_specs = (
                _rep(state.params),
                _wstate_region_specs(state.wstate),
                ExchangeInfo(*([P(wa)] * len(ExchangeInfo._fields))),
            )
            sm = jax.shard_map(
                worker_fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                axis_names=manual, check_vma=False,
            )
            args = (state.params, batch, state.wstate, state.gstate, lr, key)
            if force_skip is not None:
                args = args + (jnp.asarray(force_skip, bool),)
            update, wstate, info = sm(*args)

            with obs.scope("step.apply"):
                if fold_lr:
                    delta, opt_state = update, state.opt_state
                else:
                    delta, opt_state = optimizer.update(update, state.opt_state, state.params)
                new_params = apply_updates(state.params, delta)
                gstate = update_global_state(state.gstate, tree_sq_norm(delta))
                num_sent = info.num_sent[0]
                counters = CM.accumulate(state.counters, num_sent, bits_paper, bits_wire)
                mets = {
                    "loss": jnp.mean(info.loss),
                    "num_sent": num_sent,
                    "lr": lr,
                    "rounds_total": counters.rounds,
                    "bits_paper_total": counters.bits_paper,
                    "bits_wire_total": counters.bits_wire,
                    # the selection rule's two sides, worker mean (0 when off)
                    "rule_lhs": jnp.mean(info.rule_lhs),
                    "rule_rhs": jnp.mean(info.rule_rhs),
                }
            if stage is not None:
                # static per-stage ring traffic (CM.PipelineCommModel), every
                # step, independent of the send/skip decisions. Engine-aware:
                # the 1F1B ring moves ActivationLayout wire parts (compressed
                # hop + broadcast payload bits); GPipe moves dense microbatch
                # activations per tick.
                wbatch = jax.tree.map(
                    lambda x: jax.ShapeDtypeStruct(
                        (x.shape[0] // M,) + x.shape[1:], x.dtype
                    ),
                    batch,
                )
                pshape = jax.tree.map(
                    lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state.params
                )
                h = jax.eval_shape(pdef.prepare, pshape, wbatch)
                nm = resolve_microbatches(
                    h.shape[0], strategy.microbatches or strategy.pipeline_stages
                )
                act_elems = int(np.prod(h.shape)) // nm
                layout = sasg_cfg.act_layout or TransportActivationLayout()
                pipe = CM.PipelineCommModel(
                    stages=strategy.pipeline_stages, n_micro=nm,
                    act_elems=act_elems,
                    bits_per_elem=h.dtype.itemsize * 8,
                    gather_bits=gather_bits_step,
                    engine=sasg_cfg.pipeline_engine,
                    hop_payload_bits=layout.payload_bits(act_elems),
                    bcast_payload_bits=layout.payload_bits(nm * act_elems),
                )
                mets["pipe_stages"] = jnp.float32(strategy.pipeline_stages)
                mets["pipe_ring_bits_step"] = jnp.float32(pipe.ring_bits_per_step())
                mets["pipe_gather_bits_step"] = jnp.float32(pipe.gather_bits)
                mets["pipe_bits_step"] = jnp.float32(pipe.bits_per_step())
                mets["pipe_bits_total"] = (
                    jnp.float32(pipe.bits_per_step()) * gstate.step.astype(jnp.float32)
                )
            return (
                TrainState(new_params, opt_state, wstate, gstate, counters, state.rng),
                mets,
            )

    else:

        def step(state: TrainState, batch, force_skip=None):
            # plain SPMD has no selection rule: a straggler mask is meaningless
            # (every worker contributes to the dense psum) and is ignored
            count = state.counters.rounds.astype(jnp.int32)
            lr = lr_schedule(count)
            with obs.scope("step.grad"):
                loss, grads = vag(state.params, batch)
            with obs.scope("step.apply"):
                if optimizer is not None:
                    delta, opt_state = optimizer.update(grads, state.opt_state, state.params)
                else:
                    delta = jax.tree.map(lambda g: lr * g.astype(jnp.float32), grads)
                    opt_state = state.opt_state
                new_params = apply_updates(state.params, delta)
                counters = CM.accumulate(state.counters, jnp.float32(1.0), bits_paper, bits_wire)
                mets = {
                    "loss": loss,
                    "num_sent": jnp.float32(1.0),
                    "lr": lr,
                    "rounds_total": counters.rounds,
                    "bits_paper_total": counters.bits_paper,
                    "bits_wire_total": counters.bits_wire,
                }
            return (
                TrainState(new_params, opt_state, (), (), counters, state.rng),
                mets,
            )

    def jit_step(state, batch, force_skip=None):
        # jax.jit caches wrappers on (fun, options): the no-mask call builds
        # the SAME jitted program as before this arg existed, and the masked
        # call gets its own cached 3-arg wrapper (used by the straggler
        # fault path; mask is a traced (M,) bool so flipping workers between
        # steps does NOT retrace)
        if force_skip is None:
            fn = jax.jit(
                step,
                in_shardings=(state_shardings, batch_sharding_fn(batch)),
                out_shardings=(state_shardings, None),
                donate_argnums=(0,) if donate else (),
            )
            return fn(state, batch)
        fn = jax.jit(
            step,
            in_shardings=(
                state_shardings,
                batch_sharding_fn(batch),
                NamedSharding(mesh, P()),
            ),
            out_shardings=(state_shardings, None),
            donate_argnums=(0,) if donate else (),
        )
        return fn(state, batch, jnp.asarray(force_skip, bool))

    def init(key):
        # partitionable threefry makes sharded-output RNG value-stable, so
        # the state is born sharded (no replicated transient)
        return jax.jit(init_all, out_shardings=state_shardings)(key)

    return BuiltStep(
        step=step,
        init=init,
        jit_step=jit_step,
        state_shardings=state_shardings,
        batch_sharding_fn=batch_sharding_fn,
        exchange=exchange,
        strategy=strategy,
        bits_paper=bits_paper,
        bits_wire=bits_wire,
        param_specs=pspecs,
    )
