"""Communication accounting — paper Table 1/2/3 semantics.

Two views are maintained and reported side by side (DESIGN.md §2):

- *algorithmic* (paper convention): rounds = uploads that actually carry
  fresh information (|M^t| per step); bits = 32 per transmitted element
  (k for sparse, d for dense). This is what Tables 1-2 count and what an
  async PS transport would pay.
- *wire* (TPU bulk-synchronous reality): sparse payloads also carry 32-bit
  indices; skipped workers still occupy their fixed-k all-gather slot. The
  dry-run/roofline reports physical collective bytes; this module reconciles
  the two.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax

from .types import CommCounters


@dataclass(frozen=True)
class CommModel:
    """Static per-iteration cost model (paper Table 1)."""

    d: int          # model dimension
    k: int          # sparsification level
    M: int          # number of workers

    def bits_per_iter(self, method: str, num_sent: float | None = None) -> float:
        m = num_sent if num_sent is not None else self.M
        return {
            "sgd": 32.0 * self.d * self.M,
            "sparse": 32.0 * self.k * self.M,
            "lasg": 32.0 * self.d * m,
            "sasg": 32.0 * self.k * m,
        }[method]

    def total_bits(self, method: str, T: int, sum_rounds: float | None = None) -> float:
        if method in ("sgd", "sparse"):
            return self.bits_per_iter(method) * T
        assert sum_rounds is not None, "adaptive methods need the realized sum |M^t|"
        per_upload = 32.0 * (self.k if method == "sasg" else self.d)
        return per_upload * sum_rounds


def accumulate(
    counters: CommCounters,
    num_sent: jax.Array,
    bits_paper_per_upload: float,
    bits_wire_per_upload: float,
) -> CommCounters:
    """Fold one step's uploads into the running counters (jit-safe)."""
    return CommCounters(
        rounds=counters.rounds + num_sent,
        bits_paper=counters.bits_paper + num_sent * bits_paper_per_upload,
        bits_wire=counters.bits_wire + num_sent * bits_wire_per_upload,
    )


@dataclass(frozen=True)
class PipelineCommModel:
    """Static per-step pipeline (stage-axis) traffic accounting.

    Orthogonal to the SASG upload counters above: the activation ring runs
    every step, regardless of the send/skip decisions. Two engines
    (``dist/pipeline.py``):

    - ``"gpipe"``: one dense fp32 microbatch activation per stage per tick
      over ``n_micro + stages - 1`` ticks, plus the final output-replicating
      psum (``n_micro`` activation hops per stage).
    - ``"1f1b"`` (the default): forward carries AND backward cotangent
      carries, ``n_micro + stages - 2`` hops each per stage, all in the
      ``ActivationLayout`` wire format (``hop_payload_bits`` — the dense
      wire-dtype cast or the blocked top-k payload,
      ``bits.activation_payload_bits``); the finished-output broadcast is a
      stage-axis all-reduce of the encoded ``n_micro``-activation block, so
      each stage pays the ring all-reduce factor ``2(S-1)/S`` of
      ``bcast_payload_bits``.

    ``gather_bits`` additionally accounts the stage-axis GRADIENT-exchange
    traffic per step — the k-sized payload all-gather on the payload-gather
    hot path (plus the tiny prepare-grad psum), or the d-sized dense stage
    combine on the fallback path. Surfaced by the train step as
    ``pipe_ring_bits_step`` / ``pipe_gather_bits_step`` (and their sum
    ``pipe_bits_step``) and by ``benchmarks/run.py --stages``; the HLO audit
    gates the compiled ring wire bytes against this model.
    """

    stages: int
    n_micro: int
    act_elems: int              # elements in ONE microbatch activation
    bits_per_elem: int = 32     # dense ring payload width (GPipe engine)
    gather_bits: float = 0.0    # stage-axis gradient-exchange bits per step
    engine: str = "gpipe"       # "gpipe" | "1f1b"
    hop_payload_bits: float | None = None    # encoded per-hop bits (1f1b);
    #                                          None -> dense act_elems * bpe
    bcast_payload_bits: float | None = None  # encoded output-broadcast bits

    @property
    def ticks(self) -> int:
        if self.engine == "1f1b":
            return self.n_micro + 2 * (self.stages - 1)
        return self.n_micro + self.stages - 1

    def _dense_act_bits(self) -> float:
        return float(self.act_elems) * self.bits_per_elem

    def _hop_bits(self) -> float:
        if self.hop_payload_bits is not None:
            return float(self.hop_payload_bits)
        return self._dense_act_bits()

    def bits_per_stage_per_step(self) -> float:
        """ppermute traffic one stage emits per training step."""
        if self.engine == "1f1b":
            shifts = 2 * max(self.n_micro + self.stages - 2, 0)
            return shifts * self._hop_bits()
        return float(self.ticks) * self._dense_act_bits()

    def ring_bits_per_step(self) -> float:
        """Activation-ring traffic per step, summed over stages: the
        per-tick carries plus the finished-output broadcast."""
        if self.engine == "1f1b":
            bcast = (
                float(self.bcast_payload_bits)
                if self.bcast_payload_bits is not None
                else self.n_micro * self._dense_act_bits()
            )
            ar = 2.0 * (self.stages - 1) / max(self.stages, 1)
            return self.stages * (self.bits_per_stage_per_step() + ar * bcast)
        return self.stages * (
            self.bits_per_stage_per_step()
            + self.n_micro * self._dense_act_bits()
        )

    def bits_per_step(self) -> float:
        """Total stage-axis traffic per step: activation ring + gradient
        exchange (payload gather or dense combine)."""
        return self.ring_bits_per_step() + self.gather_bits


@dataclass(frozen=True)
class LinkModel:
    """Analytic transport-time model (paper Table 3 / Fig 5-6 setting).

    The paper measures GLOO point-to-point uploads at 1 Gbps per worker, with
    the server receiving sequentially. ``sequential_uplink=True`` reproduces
    that accounting; False models a fully parallel fabric (TPU ICI/DCI).
    """

    bandwidth_bps: float = 1e9
    latency_s: float = 1e-4
    sequential_uplink: bool = True

    def upload_time(self, bits_per_upload: float, num_uploads: float) -> float:
        per = bits_per_upload / self.bandwidth_bps + self.latency_s
        if self.sequential_uplink:
            return per * num_uploads
        return per

