"""Plain reference of the SASG training step (paper arXiv:2112.04088,
eq. 6-8) with the preset's block top-k and error feedback.

Per step t and worker m (rows ``m*R/M .. (m+1)*R/M`` of the batch):

  g_m      = grad of the worker's mean loss at the current parameters
  send_m   = t == 0  or  ||g_m - grad(stale_m)||^2 > (alpha/lr) sum(window) / M^2
             or  tau_m >= D                         (same rows for both grads)
  c_m      = lr g_m + e_m;  T(c_m) = the kb largest |c| in each block
  on send: cache_m = T(c_m), e_m = c_m - T(c_m), stale_m = params, tau_m = 1
  else:    tau_m += 1       (the cached contribution is used again)
  update   = mean_m cache_m;  params <- params - update (stored in the
             parameters' dtype);  window <- push ||update||^2

Blocks: each leaf is viewed as (*lead, nbc, bc) with bc the largest divisor
of its last dimension (all dims for a vector) not above ``block``;
kb = ceil(k / nblocks) with k = round(k_ratio * size). Ties go to the lower
index. Everything is float32; the gradients come from ``row_grad``.
"""
from __future__ import annotations

import math
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np


def _largest_divisor_leq(n: int, cap: int) -> int:
    for b in range(min(n, cap), 0, -1):
        if n % b == 0:
            return b
    return 1


def block_view(shape: tuple, block: int) -> tuple:
    nd = len(shape)
    cut = max(nd - 1, 1)
    if cut >= nd:
        cut = nd - 1
    c = int(np.prod(shape[cut:]))
    bc = _largest_divisor_leq(c, block)
    return tuple(shape[:cut]) + (c // bc, bc)


def block_k(shape: tuple, blocked: tuple, k_ratio: float) -> int:
    size = int(np.prod(shape))
    k = max(1, int(round(k_ratio * size)))
    nblocks = size // blocked[-1]
    return min(max(1, -(-k // nblocks)), blocked[-1])


def topk_ef(c: jax.Array, k_ratio: float, block: int):
    """(sparse T(c) as a dense array, residual c - T(c)) for one leaf."""
    blocked = block_view(c.shape, block)
    kb = block_k(c.shape, blocked, k_ratio)
    x = c.reshape(blocked)
    _, idx = jax.lax.top_k(jnp.abs(x), kb)
    keep = jnp.zeros(blocked, bool)
    keep = jnp.put_along_axis(keep, idx, True, axis=-1, inplace=False)
    sparse = jnp.where(keep, x, 0.0).reshape(c.shape)
    return sparse, c - sparse


def sq_norm(tree) -> jax.Array:
    return sum(jnp.sum(jnp.square(x.astype(jnp.float32))) for x in jax.tree.leaves(tree))


class Reference:
    """SASG steps of the plain reference.

    ``row_grad(params, tokens, labels) -> (loss, grads)`` for one row; the
    step's gradient is the mean over a worker's rows."""

    def __init__(self, row_grad: Callable, n_workers: int, lr: float,
                 k_ratio: float, block: int, max_delay: int, alpha_scale: float,
                 devices=None):
        self.M, self.lr, self.k_ratio, self.block = n_workers, lr, k_ratio, block
        self.devices = list(devices or [])
        self.D, self.alpha = max_delay, alpha_scale / lr
        self._row_grad = jax.jit(row_grad)
        self._add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b), donate_argnums=(0,))
        self._compress = jax.jit(self._compress_impl)
        self._diff = jax.jit(lambda a, b: sq_norm(jax.tree.map(jnp.subtract, a, b)))
        self._apply = jax.jit(lambda p, u: jax.tree.map(
            lambda x, y: (x.astype(jnp.float32) - y).astype(x.dtype), p, u))
        self._mean = jax.jit(lambda ts: jax.tree.map(lambda *xs: sum(xs) / len(xs), *ts))
        self._norms = jax.jit(lambda t: jax.tree.map(
            lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))), t))

    def _compress_impl(self, g, e):
        pairs = jax.tree.map(
            lambda gi, ei: topk_ef(self.lr * gi + ei, self.k_ratio, self.block), g, e)
        is_pair = lambda x: isinstance(x, tuple)
        sparse = jax.tree.map(lambda p: p[0], pairs, is_leaf=is_pair)
        resid = jax.tree.map(lambda p: p[1], pairs, is_leaf=is_pair)
        return sparse, resid

    def grad(self, params, tokens, labels):
        """Mean loss and mean gradient over rows (host loop, one compile)."""
        acc, loss = None, 0.0
        for r in range(tokens.shape[0]):
            l, g = self._row_grad(params, tokens[r], labels[r])
            acc = g if acc is None else self._add(acc, g)
            loss = loss + l
        n = tokens.shape[0]
        return loss / n, jax.tree.map(lambda x: x / n, acc)

    def _on(self, m: int, tree):
        """Worker m's copy of a tree: on its own device when the reference
        was given devices, so the workers' gradients run side by side."""
        if not self.devices:
            return tree
        return jax.device_put(tree, self.devices[m % len(self.devices)])

    def run(self, params, batches, steps: int, forced=None) -> dict:
        """Run ``steps`` steps from ``params`` on ``batches[t]`` (dicts of
        tokens/labels, global rows).

        ``forced`` (a count of uploading workers per step, 0 or M) makes
        the steps before the last upload as given, so that the reference
        follows another run's decisions; those steps compute only what an
        upload needs. The last step always evaluates the exact rule; with
        ``forced`` it still takes the forced decision.

        Returns the readings the comparison uses: each step's loss (None
        where no gradient was taken), uploads and the exact rule's
        (lhs, rhs, uploads) where evaluated, the squared norm of every
        applied update, the first update's and residual's leaf norms, the
        first gradient's leaf norms, and the parameters after step 1,
        before the last step and after it."""
        M = self.M
        e = [None] * M
        cache = [None] * M
        stale = [self._on(m, params) for m in range(M)]
        tau = [1] * M
        window = np.zeros((max(self.D, 1),), np.float64)
        out = {"loss": [], "sent": [], "rule": {}, "window": []}
        for t in range(steps):
            tok, lab = np.asarray(batches[t]["tokens"]), np.asarray(batches[t]["labels"])
            rows = tok.shape[0] // M
            rhs = self.alpha * float(window.sum()) / M ** 2
            last = t == steps - 1
            want = None if forced is None else int(forced[t])
            if want not in (None, 0, M):
                raise ValueError(f"step {t}: {want} of {M} workers uploading is not followable")
            local = [self._on(m, params) for m in range(M)]
            sl = [slice(m * rows, (m + 1) * rows) for m in range(M)]
            need_grad = t == 0 or last or want != 0
            fresh = ([self.grad(local[m], tok[sl[m]], lab[sl[m]]) for m in range(M)]
                     if need_grad else None)
            if t == 0:
                out["grad_norms"] = jax.device_get(self._norms(fresh[0][1]))
            rule = None
            if t > 0 and (last or forced is None):
                lhs = [float(self._diff(fresh[m][1],
                                        self.grad(stale[m], tok[sl[m]], lab[sl[m]])[1]))
                       for m in range(M)]
                rule = [t == 0 or lhs[m] > rhs or tau[m] >= self.D for m in range(M)]
                out["rule"][t] = {"lhs": lhs, "rhs": rhs, "sent": int(sum(rule))}
            sends = []
            for m in range(M):
                if want is not None:
                    send = want == M
                elif rule is not None:
                    send = rule[m]
                else:
                    send = t == 0 or tau[m] >= self.D
                if send:
                    if e[m] is None:
                        e[m] = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32),
                                            fresh[m][1])
                    cache[m], e[m] = self._compress(fresh[m][1], e[m])
                    stale[m] = local[m]
                    tau[m] = 1
                else:
                    tau[m] += 1
                sends.append(send)
            losses = [float(f[0]) for f in fresh] if fresh is not None else None
            del fresh
            update = cache[0] if M == 1 else self._mean([self._on(0, c) for c in cache])
            if t == 0:
                out["update_norms"] = jax.device_get(self._norms(update))
                out["ef_norms"] = [jax.device_get(self._norms(x)) for x in e]
            w = float(sq_norm(update))
            window = np.concatenate([[w], window[:-1]])
            if last:
                out["params_before_last"] = params
            params = self._apply(self._on(0, params), update)
            if t == 0:
                out["params1"] = params
            out["loss"].append(None if losses is None else float(np.mean(losses)))
            out["sent"].append(int(sum(sends)))
            out["window"].append(w)
        out["params_last"] = params
        return out


def leaf_gap(prog: dict, ref: dict, keep=None) -> tuple:
    """Per-leaf |prog - ref| / max(ref, median ref leaf) over leaves of two
    {path: norm} maps (restricted to ``keep`` when given). Returns (the
    worst leaf's gap, its path, the median leaf's gap)."""
    paths = [p for p in ref if keep is None or p in keep]
    med = float(np.median([ref[p] for p in paths]))
    gaps = {}
    for p in paths:
        g = abs(float(prog[p]) - float(ref[p])) / max(float(ref[p]), med, 1e-30)
        gaps[p] = g if math.isfinite(float(prog[p])) and math.isfinite(g) else math.inf
    where = max(gaps, key=gaps.get)
    return gaps[where], where, float(np.median(list(gaps.values())))
