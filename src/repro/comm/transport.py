"""The ``Transport`` seam: payload layout x compression x collectives.

The paper's bit savings come from what crosses the wire, so everything that
decides *wire shape* lives here, behind one interface:

    encode(state, g, key)   -> (payload, candidate_state)
    exchange(payload)       -> mean contribution (dense tree or flat vectors)
    densify(contrib, like)  -> full-shape fp32 update tree
    gather(g)               -> stage-combined full gradient tree
    bits_paper / bits_wire / bits_report   (centralized, repro.comm.bits)

Compressors (``repro.core.compressors``) only map values: they receive a
tree already laid out by the transport and return payload leaves + candidate
error-feedback state. The transport owns:

- **layout** (``per_shard | per_tensor | flat``): whether leaves are
  compressed on their shard-aligned blocked view, as per-leaf flat vectors,
  or as one concatenated global vector (the paper-exact T_k);
- **densification templates**: ``densify`` reshapes against the caller's
  full *gradient* tree, never against the raw params tree — under pipeline
  parallelism the in-region params have a stage-SLICED trunk, which is
  exactly why the old per-compressor densify paths could not compose with
  pipelining (the deleted ``train/step.py`` guard);
- **stage composition**: on the default hot path (block-local per_shard
  topk_ef) the transport is handed a ``StageInfo`` and compresses the
  stage-LOCAL trunk slice, then ``gather_payload`` all-gathers only the
  k-sized (values, indices) payload over the stage axis — the d-sized trunk
  gather never happens, and ``diff_sq_norm`` gives the selection rule a
  stage-psum'd norm so all stages agree on send/skip. Compressors whose
  support depends on cross-slice state fall back to the dense per-stage
  gradient combine (``dist.pipeline.build_stage_combine``), threaded in as
  ``grad_combine`` and applied by ``gather``;
- **bit accounting**: per-bucket paper/wire bits, wire-dtype aware,
  reporting the per-layer k-ratio schedule (``bits_report``).
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Callable, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.core.compressors import CompressorConfig, CompressorDef, build_compressor
from repro.core.topk import BlockPayload, _scatter_last
from repro.core.types import (
    Tree,
    ceil_div,
    tree_cast,
    tree_flatten_concat,
    tree_flatten_with_paths,
    tree_unflatten_concat,
    tree_zeros_like,
)

from . import bits as bits_lib
from . import collectives


@dataclass(frozen=True)
class ActivationLayout:
    """Wire layout for stage-boundary activations on the pipeline ring.

    The gradient exchange owns its payload layout via the compressor configs;
    this is the analogous knob for the 1F1B activation ring (forward carries,
    backward cotangent carries, and the finished-output broadcast). Owned by
    the transport layer so ``encode``/``decode`` and the bit accounting
    (``payload_bits`` == ``bits.activation_payload_bits``) cannot drift apart.

    - default (fp32, ``k_ratio=0``): identity — ``encode`` returns the array
      unchanged and the ring is bit-identical to the uncompressed schedule.
    - ``wire_dtype="bfloat16"``: cast-on-the-wire; decode casts back to the
      compute dtype.
    - ``k_ratio > 0``: blocked top-k over the flattened activation (blocks of
      ``block_size``, ``kb = ceil(block_size * k_ratio)`` kept per block),
      values at ``wire_dtype`` + block-local u8/u16 indices — the same
      payload shape family as the gradient compressors, so the bit counters
      share one formula. Lossy: backward runs against the decoded forward
      activations, so the 1F1B engine still computes a consistent (exact
      gradient of the compressed forward) update.
    """

    wire_dtype: str = "float32"
    k_ratio: float = 0.0
    block_size: int = 256

    @property
    def is_identity(self) -> bool:
        return self.k_ratio <= 0.0 and jnp.dtype(self.wire_dtype) == jnp.float32

    def _kb(self) -> int:
        return min(max(1, math.ceil(self.block_size * self.k_ratio)),
                   self.block_size)

    def _index_dtype(self):
        if self.block_size <= 256:
            return jnp.uint8
        if self.block_size <= 65536:
            return jnp.uint16
        return jnp.int32

    def payload_bits(self, elems: int) -> float:
        """Wire bits of one encoded activation of ``elems`` elements."""
        return bits_lib.activation_payload_bits(
            self.wire_dtype, self.k_ratio, self.block_size, elems
        )

    def encode(self, x: jax.Array) -> tuple:
        """Activation -> tuple of wire arrays (the ring moves these parts)."""
        if self.k_ratio <= 0.0:
            return (x.astype(self.wire_dtype),)
        flat = x.reshape(-1)
        nb = ceil_div(flat.size, self.block_size)
        pad = nb * self.block_size - flat.size
        if pad:
            flat = jnp.pad(flat, (0, pad))
        blocks = flat.reshape(nb, self.block_size)
        # encode runs on device-LOCAL blocks inside the pipeline's manual
        # shard_map region, so lax.top_k's sort-partitioner caveat (the
        # reason topk.blocked_topk unrolls masked argmax) doesn't apply —
        # and one sort pass is far cheaper than kb argmax sweeps. Ties
        # resolve identically (descending |x|, first index wins).
        _, idx = jax.lax.top_k(jnp.abs(blocks), self._kb())
        vals = jnp.take_along_axis(blocks, idx, axis=-1)
        return (
            vals.astype(self.wire_dtype),
            idx.astype(self._index_dtype()),
        )

    def decode(self, parts: tuple, shape: tuple,
               dtype=jnp.float32) -> jax.Array:
        """Wire parts -> dense activation of ``shape`` (static)."""
        if self.k_ratio <= 0.0:
            return parts[0].astype(dtype)
        vals, idxs = parts
        dense = _scatter_last(
            vals.astype(jnp.float32), idxs.astype(jnp.int32), self.block_size
        )
        n = 1
        for d in shape:
            n *= d
        return dense.reshape(-1)[:n].reshape(shape).astype(dtype)


class StageInfo(NamedTuple):
    """Pipeline-stage context for the payload-level gather path.

    ``trunk_prefixes`` are "/"-joined params-tree path prefixes of the
    stage-sharded trunk leaves; ``trunk_dims`` maps each trunk leaf's full
    path to its FULL (unsliced) leading-dim size so the compressor can pick
    the as-if-full per-block k on the stage-local slice.
    """

    axis: str
    num_stages: int
    trunk_prefixes: tuple
    trunk_dims: dict


def supports_stage_payload(cfg: CompressorConfig) -> bool:
    """True iff the compressor can encode a stage-local trunk slice whose
    gathered payload is bit-identical to compressing the full leaf: the
    block-local per_shard top-k is support-exact (blocks never straddle the
    stage-slice boundary); every other layout/compressor sees cross-slice
    state (global or per-leaf top-k support, per-leaf norms, full-leaf
    randomness) and must use the dense stage-combine fallback."""
    return cfg.name == "topk_ef" and cfg.resolved_layout() == "per_shard"


def _is_trunk_path(path: str, prefixes) -> bool:
    return any(path == p or path.startswith(p + "/") for p in prefixes)


class Transport:
    """One built wire transport for a (compressor, mesh role) pair."""

    def __init__(
        self,
        cfg: CompressorConfig,
        worker_axes: Sequence[str],
        num_workers: int,
        leaf_specs=None,
        axis_sizes: Optional[dict] = None,
        grad_combine: Optional[Callable[[Tree], Tree]] = None,
        stage: Optional[StageInfo] = None,
        act_layout: Optional[ActivationLayout] = None,
    ):
        self.cfg = cfg
        self.worker_axes = tuple(worker_axes)
        self.num_workers = num_workers
        self.leaf_specs = leaf_specs
        self.axis_sizes = axis_sizes or {}
        self.grad_combine = grad_combine
        self.stage = stage
        self.act_layout = act_layout or ActivationLayout()
        if stage is not None and not supports_stage_payload(cfg):
            raise ValueError(
                f"compressor {cfg.name!r} (layout {cfg.resolved_layout()!r}) "
                "cannot take the payload-level stage gather path; use the "
                "dense grad_combine fallback instead"
            )
        self.compressor: CompressorDef = build_compressor(
            cfg, leaf_specs=leaf_specs, axis_sizes=axis_sizes,
            stage_dims=stage.trunk_dims if stage is not None else None,
        )
        self.kind = self.compressor.kind      # "sparse" | "dense"
        # the REALIZED layout: compressors without a blocked impl (randk)
        # realize per_shard configs as per_tensor flat vectors
        self.layout = self.compressor.layout

    # -- layout -------------------------------------------------------------

    def _lay_out(self, tree: Tree) -> Tree:
        """Apply the wire layout to a full-shape tree (flat = one global
        pseudo-leaf; other layouts keep the tree structure and let the
        compressor view each leaf)."""
        if self.layout == "flat":
            return {"__global__": tree_flatten_concat(tree)}
        return tree

    # -- stage composition ---------------------------------------------------

    def gather(self, g: Tree) -> Tree:
        """Combine per-stage gradient slices into the full tree the exchange
        operates on (identity when no pipeline stage axis is threaded in).

        On the payload path (``stage`` set, ``grad_combine`` None) this stays
        the identity: gradients remain stage-sliced and only the k-sized
        payload crosses the stage axis (``gather_payload``)."""
        if self.grad_combine is None:
            return g
        return self.grad_combine(g)

    def gather_payload(self, payload: Tree) -> Tree:
        """All-gather the k-sized trunk payload slices over the stage axis.

        The payload-level replacement for the d-sized trunk gather: trunk
        BlockPayload leaves (compressed from the stage-local slice) are
        dim-0 tiled-gathered into the full-stack payload; non-trunk payloads
        were computed from replicated grads and are already bit-identical
        across stages, so they pass through with zero collectives. Identity
        when no stage is threaded in."""
        if self.stage is None:
            return payload
        axis = self.stage.axis
        prefixes = self.stage.trunk_prefixes
        paths, leaves, treedef = tree_flatten_with_paths(
            payload, is_leaf=collectives._is_payload
        )
        out = [
            collectives.gather_block_payload(p, axis)
            if isinstance(p, BlockPayload) and _is_trunk_path(path, prefixes)
            else p
            for path, p in zip(paths, leaves)
        ]
        return jax.tree.unflatten(treedef, out)

    def diff_sq_norm(self, a: Tree, b: Tree) -> jax.Array:
        """Stage-aware ||a - b||^2 for the SASG/LASG selection rule.

        Trunk leaves are stage-local slices, so their squared-norm
        contribution is psum'd over the stage axis (a scalar — O(1) wire);
        non-trunk leaves are replicated and summed locally. All stages
        compute the same value, so the send decision agrees bitwise."""
        paths, la, _ = tree_flatten_with_paths(a)
        lb = jax.tree.leaves(b)
        trunk = jnp.zeros((), jnp.float32)
        local = jnp.zeros((), jnp.float32)
        for path, xa, xb in zip(paths, la, lb):
            d = xa.astype(jnp.float32) - xb.astype(jnp.float32)
            sq = jnp.sum(jnp.square(d))
            if self.stage is not None and _is_trunk_path(path, self.stage.trunk_prefixes):
                trunk = trunk + sq
            else:
                local = local + sq
        if self.stage is not None:
            trunk = collectives.psum_scalar(trunk, (self.stage.axis,))
        return local + trunk

    # -- encode / exchange / densify ----------------------------------------

    def init_state(self, params: Tree) -> Tree:
        """Compressor state (error-feedback buffers) for the wire layout."""
        return self.compressor.init(self._lay_out(params))

    def zero_payload(self, params: Tree) -> Tree:
        """Payload-shaped zeros: the structure and dtypes of compressing a
        zero tree, every value and index 0. Only the shapes are traced, so
        no compressor (and no Pallas kernel) runs here: the state init is
        auto-partitioned over the whole mesh, where a Mosaic kernel cannot
        be."""
        zeros = tree_zeros_like(params, dtype=jnp.float32)
        shapes = jax.eval_shape(
            lambda z: self.encode(self.init_state(z), z, jax.random.PRNGKey(0))[0],
            zeros,
        )
        return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)

    def encode(self, state: Tree, g: Tree, key) -> tuple:
        """Lay out the (full-shape) quantity tree and compress it.

        Returns (payload, candidate_state); the caller commits or discards
        the candidate state with the send/skip decision.
        """
        payload, cand = self.compressor.compress(state, self._lay_out(g), key)
        return payload, cand

    def exchange(self, payload: Tree) -> Tree:
        """Worker-axis collective: psum-mean for dense payloads, fixed-k
        all-gather + local scatter-add mean for sparse ones."""
        return collectives.exchange(
            payload, self.kind, self.worker_axes, self.num_workers
        )

    def exchange_overlapped(
        self, fresh: Tree, stale: Tree, cand_state: Tree, old_state: Tree,
        send, like: Tree,
    ) -> tuple:
        """Per-bucket select -> dispatch with a double-buffered EF commit.

        The synchronous path selects the WHOLE payload tree (fresh vs the
        stale cache), commits the EF state, then hands one monolithic tree to
        the worker collective — every bucket's collective therefore depends
        on every bucket's select in the emitted dataflow. Here each payload
        bucket is selected and dispatched to its worker collective
        independently, so XLA's latency-hiding scheduler may launch a
        bucket's all-gather as soon as ITS gradient leaf (and the scalar send
        bit) is ready, overlapping the remaining buckets' backward compute.
        The EF state is double-buffered: the candidate buffer from ``encode``
        is held alongside the old one until all bucket dispatches are
        emitted, then committed with the same send bit — the commit is moved
        AFTER the collectives in the dataflow, but selects between the same
        two buffers, so the committed state (and the update) is bit-identical
        to the synchronous path (tests/test_overlap_exchange.py).

        ``send=None`` means selection is statically off (always-send): the
        per-bucket where-gates vanish entirely and each bucket's collective
        depends only on its own gradient leaf. The flat layout has a single
        global bucket, so per-bucket == whole-tree there.

        Dense-kind payloads (qsgd / signsgd / terngrad / identity) keep the
        monolithic dispatch: their exchange is a summing psum, and splitting
        it per bucket lets XLA's all-reduce combiner regroup the reductions
        into a different elementwise summation order (ulp-level drift vs the
        sync path). Sparse kinds are all-gathers — order-free — so only they
        gain (and stay bit-exact under) per-bucket dispatch.

        Returns ``(update, payload_committed, comp_state_committed)``.
        """
        from repro.core.types import tree_where

        monolithic = self.layout == "flat" or self.kind == "dense"
        if send is None:
            sel_payload, new_state = fresh, cand_state
        elif monolithic:
            sel_payload = tree_where(send, fresh, stale)
            new_state = tree_where(send, cand_state, old_state)
        else:
            fpaths, fleaves, ftdef = tree_flatten_with_paths(
                fresh, is_leaf=collectives._is_payload
            )
            _, sleaves, _ = tree_flatten_with_paths(
                stale, is_leaf=collectives._is_payload
            )
            sel_payload = jax.tree.unflatten(ftdef, [
                tree_where(send, pf, ps) for pf, ps in zip(fleaves, sleaves)
            ])
            new_state = tree_where(send, cand_state, old_state)
        if monolithic or send is None:
            contrib = self.exchange(sel_payload)
        else:
            spaths, sleaves2, stdef = tree_flatten_with_paths(
                sel_payload, is_leaf=collectives._is_payload
            )
            contrib = jax.tree.unflatten(stdef, [
                collectives.exchange(
                    p, self.kind, self.worker_axes, self.num_workers
                )
                for p in sleaves2
            ])
        return self.densify(contrib, like), sel_payload, new_state

    def densify(self, contrib: Tree, like: Tree) -> Tree:
        """Reshape the exchanged mean contribution against ``like`` — the
        full gradient tree (NOT the possibly stage-sliced params tree).
        Sparse layouts come back fp32; dense contributions pass through."""
        if self.kind == "dense":
            return contrib
        if self.layout == "flat":
            update = tree_unflatten_concat(contrib["__global__"], like)
            return tree_cast(update, jnp.float32)
        if self.layout == "per_shard":
            # BlockPayload densify already restored leaf shapes
            return tree_cast(contrib, jnp.float32)
        # per_tensor: flat vectors per leaf
        return collectives.reshape_like(contrib, tree_cast(like, jnp.float32))

    # -- bit accounting ------------------------------------------------------

    def bits_report(self, template: Tree) -> bits_lib.BitsReport:
        return bits_lib.account(
            self.cfg, template, leaf_specs=self.leaf_specs,
            axis_sizes=self.axis_sizes,
        )

    def bits_paper(self, template: Tree) -> float:
        return self.bits_report(template).paper

    def bits_wire(self, template: Tree) -> float:
        return self.bits_report(template).wire


def build_transport(
    cfg: CompressorConfig,
    worker_axes: Sequence[str],
    num_workers: int,
    leaf_specs=None,
    axis_sizes: Optional[dict] = None,
    grad_combine: Optional[Callable[[Tree], Tree]] = None,
    stage: Optional[StageInfo] = None,
    act_layout: Optional["ActivationLayout"] = None,
) -> Transport:
    return Transport(
        cfg, worker_axes, num_workers,
        leaf_specs=leaf_specs, axis_sizes=axis_sizes, grad_combine=grad_combine,
        stage=stage, act_layout=act_layout,
    )
