"""Fault-tolerant training loop.

- periodic async checkpointing (atomic commit, keep-last-N GC) with
  surfaced save failures: the writer retries with backoff and a checkpoint
  that still cannot be written is declared LOST (logged + recorded in
  ``events``) instead of silently pretending success — a lost checkpoint
  never rolls back training, it only widens the replay window of the next
  recovery;
- automatic restore-and-continue on step failure, falling back through
  checkpoint candidates newest-first until one passes ``verify`` (a corrupt
  latest checkpoint costs replay distance, not the run);
- deterministic replay: recovery reseeks the data source to the restored
  step (``repro.data.ReplayableStream``), so the batch sequence an
  interrupted run consumes is identical to an uninterrupted one — zero
  skipped, zero duplicated. Non-seekable iterators keep the legacy lossy
  behavior with a one-time warning;
- straggler hook: a per-step worker mask is forwarded into the SASG
  selection rule as force_skip (the algorithm's own M_c path doubles as the
  mitigation mechanism — DESIGN.md §5);
- subclass hooks (``_pre_step`` / ``_fetch_batch`` / ``_force_skip``) are
  the extension surface used by ``train.elastic.ElasticTrainer`` for in-run
  membership resizes and fault injection.
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Callable, Iterator, Optional

import jax

from repro import obs

from . import checkpoint as CKPT
from .step import BuiltStep, TrainState


@dataclass
class TrainerConfig:
    total_steps: int = 100
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 50
    ckpt_keep: int = 3
    ckpt_async: bool = True
    log_every: int = 10
    max_restarts: int = 3
    record_batches: bool = False  # log (step, fingerprint) per applied batch


class Trainer:
    def __init__(
        self,
        built: BuiltStep,
        data: Iterator[dict],
        cfg: TrainerConfig,
        fault_hook: Optional[Callable[[int], None]] = None,
        log_fn: Callable[[str], None] = print,
    ):
        self.built = built
        self.data = data
        self.cfg = cfg
        self.fault_hook = fault_hook
        self.log = log_fn
        self._save_handle: Optional[CKPT.SaveHandle] = None
        self._ckpt_fail_attempts = 0  # armed by fault injection (save_fail)
        self._init_key = None
        self._warned_unseekable = False
        self.history: list[dict] = []
        self.events: list[dict] = []      # resizes, recoveries, lost ckpts
        self.batch_log: list[tuple] = []  # (step, fingerprint) when recording
        self.spans = obs.Spans()          # host spans of the loop (repro.obs)

    # -- checkpointing -----------------------------------------------------

    def _ckpt_meta(self) -> dict:
        # the restore path needs the worker count to decide whether SASG
        # worker state can be carried or must be re-initialized (elastic)
        return {"num_workers": self.built.strategy.num_workers}

    def _join_save(self):
        """Block on the in-flight async save; surface (never swallow) its
        failure. A lost checkpoint is an event, not a training error."""
        if self._save_handle is None:
            return
        handle, self._save_handle = self._save_handle, None
        try:
            handle.join()
        except CKPT.CheckpointSaveError as e:
            self.log(f"[trainer] checkpoint LOST: {e}")
            self.events.append(
                {"kind": "ckpt_lost", "step": handle.step, "error": str(e.cause)}
            )

    def _maybe_ckpt(self, state: TrainState, step: int, force=False):
        c = self.cfg
        if not c.ckpt_dir:
            return
        if force or (step > 0 and step % c.ckpt_every == 0):
            self._join_save()  # backpressure: one in flight
            fail_attempts, self._ckpt_fail_attempts = self._ckpt_fail_attempts, 0
            try:
                handle = CKPT.save(
                    state, c.ckpt_dir, step, blocking=not c.ckpt_async,
                    meta=self._ckpt_meta(), fail_attempts=fail_attempts,
                )
            except CKPT.CheckpointSaveError as e:  # blocking save exhausted retries
                self.log(f"[trainer] checkpoint LOST: {e}")
                self.events.append(
                    {"kind": "ckpt_lost", "step": step, "error": str(e.cause)}
                )
            else:
                if c.ckpt_async:
                    self._save_handle = handle
            CKPT.gc_old(c.ckpt_dir, c.ckpt_keep)

    def _restore_latest(self, template: TrainState) -> tuple[TrainState, int]:
        """Newest *verified* checkpoint, falling back through older
        candidates when verification fails (corrupt/truncated files)."""
        c = self.cfg
        if not c.ckpt_dir:
            return template, 0
        for step in CKPT.candidate_steps(c.ckpt_dir):
            if not CKPT.verify(c.ckpt_dir, step):
                self.log(
                    f"[trainer] checkpoint step_{step} failed verification; "
                    "trying an older one"
                )
                continue
            state = CKPT.restore(
                template, c.ckpt_dir, step, shardings=self.built.state_shardings
            )
            saved_m = CKPT.manifest_meta(c.ckpt_dir, step).get("num_workers")
            m = self.built.strategy.num_workers
            if (
                self.built.strategy.uses_shard_map
                and saved_m is not None
                and saved_m != m
            ):
                # elastic restart: the checkpoint's worker set is gone, so
                # per-worker state restores as template debris — re-init it
                # from the RESTORED params (same cold start the in-run
                # resize uses, DESIGN.md §5)
                from .elastic import fresh_worker_state

                state = state._replace(
                    wstate=fresh_worker_state(self.built, state.params)
                )
                self.log(
                    f"[trainer] worker count changed {saved_m} -> {m}; "
                    "re-initialized SASG worker state from restored params"
                )
            self.log(f"[trainer] restored checkpoint at step {step}")
            return state, step
        return template, 0

    # -- subclass hooks (ElasticTrainer) -----------------------------------

    def _pre_step(self, state: TrainState, step: int) -> TrainState:
        """Before the batch fetch; may raise (node failure) or swap
        ``self.built`` + remap ``state`` (membership resize)."""
        if self.fault_hook is not None:
            self.fault_hook(step)  # legacy hook; may raise
        return state

    def _fetch_batch(self, step: int) -> dict:
        """The batch for training step ``step``. Replayable sources are
        indexed directly (pure in ``step``); plain iterators are consumed."""
        if hasattr(self.data, "batch_at"):
            return self.data.batch_at(step)
        return next(self.data)

    def _force_skip(self, step: int):
        """(M,) bool straggler mask for this step, or None (no stragglers)."""
        return None

    def _seek(self, step: int, initial: bool = False):
        if hasattr(self.data, "seek"):
            self.data.seek(step)
        elif initial and step == 0:
            pass  # a fresh iterator at a fresh start: nothing to rewind
        elif not self._warned_unseekable:
            self._warned_unseekable = True
            self.log(
                "[trainer] WARNING: data source is not seekable; batches "
                "between the restored checkpoint and the failure are lost "
                "(use repro.data.ReplayableStream for exact replay)"
            )

    def _recover(self) -> tuple[TrainState, int]:
        # satellite fix: the restore template must use the caller's init key
        # — a fresh-start recovery with PRNGKey(0) would silently change the
        # run's initialization
        template = self.built.init(self._init_key)
        state, step = self._restore_latest(template)
        self._seek(step)
        return state, step

    # -- main loop ----------------------------------------------------------

    def run(self, init_key=None, state: Optional[TrainState] = None) -> TrainState:
        with self.spans.gc_spans():
            return self._run(init_key, state)

    def _run(self, init_key, state: Optional[TrainState]) -> TrainState:
        c = self.cfg
        with self.spans.span("train.start"):
            self._init_key = init_key if init_key is not None else jax.random.PRNGKey(0)
            if state is None:
                state = self.built.init(self._init_key)
            state, start = self._restore_latest(state)
            self._seek(start, initial=True)

        step = start
        restarts = 0
        while step < c.total_steps:
            try:
                with self.spans.span("train.step", step_num=step):
                    state = self._pre_step(state, step)
                    with self.spans.span("train.fetch"):
                        batch = self._fetch_batch(step)
                    fs = self._force_skip(step)
                    with self.spans.span("train.dispatch"):
                        if fs is None:
                            state, mets = self.built.jit_step(state, batch)
                        else:
                            state, mets = self.built.jit_step(state, batch, fs)
                    with self.spans.span("train.metrics_sync"):
                        self._record_metrics(step, mets)
                    if c.record_batches:
                        from repro.data.replay import batch_fingerprint

                        self.batch_log.append((step, batch_fingerprint(batch)))
                    step += 1
                    with self.spans.span("train.checkpoint"):
                        self._maybe_ckpt(state, step)
            except KeyboardInterrupt:
                raise
            except Exception as e:  # node failure / data failure: recover
                restarts += 1
                if restarts > c.max_restarts:
                    raise
                with self.spans.span("train.recover", step_num=step):
                    t0 = time.monotonic()
                    self.log(
                        f"[trainer] step {step} failed ({type(e).__name__}: {e}); "
                        f"recovering ({restarts}/{c.max_restarts})"
                    )
                    self._join_save()  # commit (or mourn) the in-flight save first
                    state, new_step = self._recover()
                    self.events.append(
                        {
                            "kind": "recovery",
                            "failed_step": step,
                            "restored_step": new_step,
                            "steps_lost": step - new_step,
                            "error": type(e).__name__,
                            "latency_s": time.monotonic() - t0,
                        }
                    )
                    step = new_step
        self._maybe_ckpt(state, step, force=True)
        self._join_save()
        return state

    def _record_metrics(self, step: int, mets: dict) -> None:
        """The step's metrics on the host (where the host waits for the
        device), logged every ``log_every`` steps and at the last."""
        c = self.cfg
        if step % c.log_every == 0 or step == c.total_steps - 1:
            loss = float(mets["loss"])
            sent = float(mets["num_sent"])
            self.log(
                f"[trainer] step {step:5d} loss {loss:8.4f} "
                f"sent {sent:4.0f}/{max(self.built.strategy.num_workers,1)} "
                f"rounds {float(mets['rounds_total']):9.0f} "
                f"bits(paper) {float(mets['bits_paper_total']):.3e}"
            )
        self.history.append({k: float(v) for k, v in mets.items()})
