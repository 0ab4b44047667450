"""SASG: the paper's algorithm as a composable gradient-exchange transform.

One engine expresses all four paper algorithms (Section 5.1) plus the extra
baselines, by composing two orthogonal switches:

                     selection OFF            selection ON
  identity           distributed SGD          LASG
  topk_ef            Sparse (top-k + EF)      SASG   <- the paper
  (randk/qsgd/...)   extra baselines          adaptive variants (beyond paper)

The exchange runs inside a partial-auto ``jax.shard_map``: worker axes
(pod/data) are manual, the model axis stays auto so TP sharding composes
transparently. Each worker:

  1. computes its fresh local gradient (and, if selection is on, the
     auxiliary gradient at its stale parameters **on the same minibatch** —
     the paper's variance-cancelling trick, eq. 6/7);
  2. decides send-vs-skip with the LASG rule (worker-local, zero comms);
  3. folds the learning rate and error feedback: g = lr * grad + e  (eq. 8);
  4. compresses (top-k -> fixed-k values+indices payload);
  5. contributes either the fresh payload or its cached stale payload to the
     worker-axis exchange (all-gather + local densify for sparse; psum for
     dense). Re-sending the cached payload is wire-identical to the paper's
     server-side reuse: the "server memory" is distributed across workers,
     and each worker's cache is exactly the sparse contribution the paper's
     server would have stored (DESIGN.md §2).

The returned ``update`` equals eq. (8)'s (1/M) [sum fresh T_k(g) + sum stale
T_k(g)] — identically replicated across workers, ready for `params - update`
(paper mode, fold_lr=True) or for a downstream optimizer (fold_lr=False,
beyond-paper composition e.g. with Adam, cf. CADA).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp

from repro import obs

# submodule imports (not the repro.comm package __init__) so that importing
# repro.comm first does not cycle through repro.core -> sasg -> repro.comm
from repro.comm.collectives import pmean_tree, psum_scalar
from repro.comm.transport import ActivationLayout, Transport, build_transport

from .compressors import CompressorConfig, CompressorDef
from .selection import (
    SelectionConfig,
    SelectionState,
    advance_tau,
    push_window,
    should_send,
)
from .types import Tree, tree_cast, tree_scale, tree_sq_norm, tree_where


@dataclass(frozen=True)
class SASGConfig:
    compressor: CompressorConfig = field(default_factory=CompressorConfig)
    selection: SelectionConfig = field(default_factory=SelectionConfig)
    mode: str = "flat"                    # "flat" | "hierarchical" (pod = worker)
    fold_lr: bool = True                  # paper folds gamma into the compressed g
    stale_params_dtype: str = "float32"   # bf16 = beyond-paper memory saving
    name: str = "sasg"
    # pipeline-parallel knobs (no effect without a stage axis):
    pipeline_engine: str = "1f1b"         # "1f1b" | "gpipe" (reference)
    act_layout: Optional[ActivationLayout] = None  # 1F1B ring wire format
    # overlap: dispatch per-bucket collectives as gradients complete and
    # commit EF state double-buffered AFTER the collectives
    # (Transport.exchange_overlapped) — bit-identical to the sync exchange
    overlap: bool = False


# -- presets: the paper's four algorithms -----------------------------------

def sgd_config(**kw) -> SASGConfig:
    return SASGConfig(
        compressor=CompressorConfig(name="identity"),
        selection=SelectionConfig(enabled=False),
        name="sgd", **kw,
    )


def sparse_config(k_ratio: float = 0.01, **kw) -> SASGConfig:
    return SASGConfig(
        compressor=CompressorConfig(name="topk_ef", k_ratio=k_ratio),
        selection=SelectionConfig(enabled=False),
        name="sparse", **kw,
    )


def lasg_config(max_delay: int = 10, **kw) -> SASGConfig:
    return SASGConfig(
        compressor=CompressorConfig(name="identity"),
        selection=SelectionConfig(enabled=True, max_delay=max_delay),
        name="lasg", **kw,
    )


def sasg_config(k_ratio: float = 0.01, max_delay: int = 10, **kw) -> SASGConfig:
    return SASGConfig(
        compressor=CompressorConfig(name="topk_ef", k_ratio=k_ratio),
        selection=SelectionConfig(enabled=True, max_delay=max_delay),
        name="sasg", **kw,
    )


PRESETS = {
    "sgd": sgd_config,
    "sparse": sparse_config,
    "lasg": lasg_config,
    "sasg": sasg_config,
}


class WorkerState(NamedTuple):
    """Per-worker (device-varying over worker axes) SASG state."""

    comp_state: Tree        # compressor state (EF error buffers)
    stale_cache: Tree       # last-sent payload (the distributed "server memory")
    stale_params: Tree      # w^{t - tau_m}; () when selection is off
    tau: jax.Array          # () int32


class GlobalState(NamedTuple):
    """Replicated SASG state."""

    window: jax.Array       # (D,) ||w^{t+1-d} - w^{t-d}||^2
    step: jax.Array         # () int32


class ExchangeInfo(NamedTuple):
    loss: jax.Array          # () f32   — this worker's fresh minibatch loss
    send: jax.Array          # () bool  — this worker uploaded
    num_sent: jax.Array      # () f32   — |M^t| across all workers
    rule_lhs: jax.Array      # selection diagnostics (0 when selection off)
    rule_rhs: jax.Array


class SASGExchange(NamedTuple):
    """Built exchange: functions to be called from the training step."""

    config: SASGConfig
    transport: Transport
    compressor: CompressorDef
    num_workers: int
    worker_axes: tuple
    reduce_axes: tuple
    init_worker: Callable[[Tree], WorkerState]
    init_global: Callable[[], GlobalState]
    # run(params, batch, wstate, gstate, lr, key, grad_fn) -> (update, wstate, info)
    run: Callable[..., tuple]
    bits_per_upload_paper: Callable[[Tree], float]
    bits_per_upload_wire: Callable[[Tree], float]


def build_exchange(
    cfg: SASGConfig,
    worker_axes: Sequence[str],
    reduce_axes: Sequence[str] = (),
    num_workers: int = 1,
    leaf_specs=None,
    axis_sizes=None,
    grad_combine=None,
    stage=None,
) -> SASGExchange:
    """Build the SASG exchange over a ``repro.comm`` Transport.

    ``grad_combine`` (optional) is the per-stage gradient combine under
    pipeline parallelism (``dist.pipeline.build_stage_combine``); the
    transport applies it so the exchange always sees the FULL gradient tree,
    and densifies against that tree — never against the (possibly
    stage-sliced) params tree.

    ``stage`` (optional, a ``comm.transport.StageInfo``, mutually exclusive
    with ``grad_combine``) selects the payload-level gather path instead:
    gradients stay stage-sliced, ``encode`` compresses the stage-LOCAL trunk
    slice, and only the k-sized payload is gathered over the stage axis
    (``Transport.gather_payload``); the selection rule runs on the
    transport's stage-psum'd ``diff_sq_norm``.
    """
    assert grad_combine is None or stage is None, (
        "grad_combine (dense fallback) and stage (payload gather) are "
        "mutually exclusive stage compositions"
    )
    transport = build_transport(
        cfg.compressor, worker_axes, num_workers,
        leaf_specs=leaf_specs, axis_sizes=axis_sizes, grad_combine=grad_combine,
        stage=stage, act_layout=cfg.act_layout,
    )
    compressor = transport.compressor
    sel = cfg.selection
    worker_axes = tuple(worker_axes)
    reduce_axes = tuple(reduce_axes)

    def init_worker(params: Tree) -> WorkerState:
        comp_state = transport.init_state(params)
        stale_cache = transport.zero_payload(params)
        if sel.enabled:
            stale_params = tree_cast(params, jnp.dtype(cfg.stale_params_dtype))
        else:
            stale_params = ()
        return WorkerState(comp_state, stale_cache, stale_params, jnp.ones((), jnp.int32))

    def init_global() -> GlobalState:
        return GlobalState(
            window=jnp.zeros((max(sel.max_delay, 1),), jnp.float32),
            step=jnp.zeros((), jnp.int32),
        )

    def _reduce(tree: Tree) -> Tree:
        # d-sized reduction -> owned by the repro.comm seam (audited there)
        return pmean_tree(tree, reduce_axes)

    def run(
        params: Tree,
        batch: Tree,
        wstate: WorkerState,
        gstate: GlobalState,
        lr: jax.Array,
        key: jax.Array,
        grad_fn: Callable[[Tree, Tree], tuple],
        force_skip: Optional[jax.Array] = None,
    ):
        """One SASG exchange. Called inside shard_map (manual worker axes).

        ``grad_fn(params, batch) -> (loss, grads)`` (i.e. value_and_grad).

        Under pipeline parallelism ``grad_fn`` returns per-stage gradient
        slices; ``transport.gather`` combines them into the full tree
        (identity otherwise)."""
        with obs.scope("step.grad"):
            loss, g_fresh = grad_fn(params, batch)
            g_fresh = _reduce(transport.gather(g_fresh))
            if reduce_axes:
                loss = pmean_tree(loss, reduce_axes)

        if sel.enabled:
            with obs.scope("step.rule_grads"):
                stale_p = jax.tree.map(
                    lambda s, p: s.astype(p.dtype), wstate.stale_params, params
                )
                if sel.probe_fraction < 1.0:
                    # rule (6) on a probe sub-batch: both sides re-evaluated on
                    # the same probe data (the variance-cancelling pairing is
                    # preserved); costs 2*p extra grads instead of 1x.
                    def probe(x):
                        n = max(1, int(round(sel.probe_fraction * x.shape[0])))
                        return x[:n]

                    pbatch = jax.tree.map(probe, batch)
                    g_rule_fresh = _reduce(transport.gather(grad_fn(params, pbatch)[1]))
                    g_stale = _reduce(transport.gather(grad_fn(stale_p, pbatch)[1]))
                else:
                    g_rule_fresh = g_fresh
                    g_stale = _reduce(transport.gather(grad_fn(stale_p, batch)[1]))
            with obs.scope("step.exchange"), obs.scope("rule"):
                # alpha_d defaults to alpha_scale/lr (paper grid); lr is traced,
                # so compute rhs directly here.
                if sel.alphas is not None:
                    a = jnp.asarray(sel.alphas, jnp.float32)
                else:
                    a = sel.alpha_scale / jnp.maximum(lr, 1e-12)
                    a = jnp.broadcast_to(a, (sel.max_delay,)).astype(jnp.float32)
                sstate = SelectionState(tau=wstate.tau, window=gstate.window)
                # payload-gather path: trunk grads are stage-local slices, so
                # the rule's ||.||^2 must psum the trunk part over the stage
                # axis (transport.diff_sq_norm) for all stages to agree on
                # send/skip
                dsn = transport.diff_sq_norm if transport.stage is not None else None
                send = should_send(
                    sel, g_rule_fresh, g_stale, sstate, a, num_workers, force_skip,
                    diff_sq_norm=dsn,
                )
                if dsn is not None:
                    lhs = dsn(g_rule_fresh, g_stale)
                else:
                    lhs = tree_sq_norm(jax.tree.map(jnp.subtract, g_rule_fresh, g_stale))
                rhs = jnp.sum(a * gstate.window) / float(num_workers) ** 2
        else:
            send = jnp.ones((), bool)
            lhs = jnp.zeros(())
            rhs = jnp.zeros(())

        with obs.scope("step.exchange"):
            with obs.scope("rule"):
                # Always upload on the very first step (empty caches).
                send = send | (gstate.step == 0)

            # Paper eq. (8): g_m^t = gamma * grad + e_m^t (error folded inside
            # the compressor; gamma folded here when fold_lr). The transport
            # owns the wire layout, the worker-axis collectives, and
            # densification — the densify template is the FULL gradient tree
            # ``g``, never the params tree (whose trunk is stage-sliced under
            # pipelining).
            with obs.scope("encode"):
                g = tree_scale(g_fresh, lr) if cfg.fold_lr else g_fresh
                payload_fresh, comp_state_cand = transport.encode(wstate.comp_state, g, key)
                # payload-gather path: the k-sized trunk payload slices
                # all-gather over the stage axis HERE (identity otherwise) —
                # the stale cache then stores the full gathered payload, so
                # skip-step replays are collective-free over stages just like
                # in the flat run
                payload_fresh = transport.gather_payload(payload_fresh)

            if cfg.overlap:
                # per-bucket select -> dispatch as each gradient bucket is
                # ready, EF commit emitted AFTER the collectives
                # (double-buffered candidate/old state pair) — bit-identical
                # per-leaf ops to the sync path below. The traced ``send`` is
                # passed even when the rule is off (it is then the
                # constant-True first-step mask) so both paths emit the SAME
                # where-gates: dropping them would change the program around
                # the step's psums and XLA's all-reduce regrouping can shift
                # their summation order by an ulp (send=None remains a
                # transport-level API for callers whose sync path has no
                # gates at all).
                with obs.scope("collective"):
                    update, payload, comp_state_new = transport.exchange_overlapped(
                        payload_fresh, wstate.stale_cache, comp_state_cand,
                        wstate.comp_state, send, g,
                    )
            else:
                with obs.scope("commit"):
                    payload = tree_where(send, payload_fresh, wstate.stale_cache)
                    comp_state_new = tree_where(send, comp_state_cand, wstate.comp_state)
                with obs.scope("collective"):
                    update = transport.densify(transport.exchange(payload), g)

            with obs.scope("commit"):
                if sel.enabled:
                    stale_params_new = tree_where(
                        send,
                        tree_cast(params, jnp.dtype(cfg.stale_params_dtype)),
                        wstate.stale_params,
                    )
                else:
                    stale_params_new = ()

                new_wstate = WorkerState(
                    comp_state=comp_state_new,
                    stale_cache=payload,
                    stale_params=stale_params_new,
                    tau=advance_tau(SelectionState(wstate.tau, gstate.window), send),
                )
                # send is identical within a reduce group (g_fresh was pmean'd
                # over reduce_axes), so summing over worker axes alone counts
                # |M^t|.
                num_sent = psum_scalar(send.astype(jnp.float32), worker_axes)
        info = ExchangeInfo(
            loss=loss, send=send, num_sent=num_sent, rule_lhs=lhs, rule_rhs=rhs
        )
        return update, new_wstate, info

    return SASGExchange(
        config=cfg,
        transport=transport,
        compressor=compressor,
        num_workers=num_workers,
        worker_axes=worker_axes,
        reduce_axes=reduce_axes,
        init_worker=init_worker,
        init_global=init_global,
        run=run,
        bits_per_upload_paper=transport.bits_paper,
        bits_per_upload_wire=transport.bits_wire,
    )


def update_global_state(
    gstate: GlobalState, applied_delta_sq_norm: jax.Array
) -> GlobalState:
    """Push ||w^{t+1} - w^t||^2 into the window and advance the step."""
    sstate = SelectionState(tau=jnp.zeros((), jnp.int32), window=gstate.window)
    return GlobalState(
        window=push_window(sstate, applied_delta_sq_norm),
        step=gstate.step + 1,
    )
