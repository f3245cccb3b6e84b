"""Synthetic data generation + host-side pipeline.

LASSO side: sparse-ground-truth regression problems shaped like the paper's
datasets (abalone / covtype / susy, Table II) so every benchmark runs offline.
LM side: deterministic token streams with sharded host feeding and
double-buffered prefetch.
"""
from __future__ import annotations

import dataclasses
import queue
import threading
import zlib
from typing import Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.problem import LassoProblem


# ---------------------------------------------------------------------------
# LASSO problems (paper Table II stand-ins)
# ---------------------------------------------------------------------------

#: name -> (d features, n samples, lambda) at the published sizes of the
#: paper's datasets (Table II); callers cut n with ``scale``.
PAPER_DATASETS = {
    "abalone": dict(d=8, n=4177, lam=0.1),
    "covtype": dict(d=54, n=581_012, lam=0.01),
    "susy": dict(d=18, n=5_000_000, lam=0.01),
}


def make_lasso_data(key: jax.Array, d: int, n: int, sparsity: float = 0.25,
                    noise: float = 0.01, lam_frac: float = 0.1,
                    dtype=jnp.float32) -> LassoProblem:
    """X (d, n) with unit-variance columns, y = X^T w* + noise, w* sparse.

    lambda is set to lam_frac * lambda_max, where lambda_max = ||X y / n||_inf
    is the smallest lambda with all-zero solution — guaranteeing a nontrivial
    sparse optimum for any data scaling.
    """
    kx, kw, kn, km = jax.random.split(key, 4)
    X = jax.random.normal(kx, (d, n), dtype) / np.sqrt(d)
    w_star = jax.random.normal(kw, (d,), dtype)
    mask = jax.random.bernoulli(km, sparsity, (d,))
    w_star = jnp.where(mask, w_star, 0.0)
    y = X.T @ w_star + noise * jax.random.normal(kn, (n,), dtype)
    lam = float(lam_frac * jnp.max(jnp.abs(X @ y / n)))
    return LassoProblem(X=X, y=y, lam=lam), w_star


def make_dataset_like(name: str, key: Optional[jax.Array] = None,
                      scale: float = 1.0):
    """A synthetic problem with the shape/lambda of a paper dataset, cut to
    ``scale`` of its published sample count."""
    spec = PAPER_DATASETS[name]
    if key is None:
        # stable digest, NOT hash(): str hashing is salted per process, which
        # made every test run solve a different problem instance
        key = jax.random.PRNGKey(zlib.adler32(name.encode()) & 0x7FFFFFFF)
    n = max(int(spec["n"] * scale), 64)
    # Synthetic stand-in: a data-dependent lambda (fraction of lambda_max)
    # plays the role of the paper's per-dataset tuned lambda.
    return make_lasso_data(key, spec["d"], n)


# ---------------------------------------------------------------------------
# LM token pipeline
# ---------------------------------------------------------------------------

def make_token_batch(key: jax.Array, batch: int, seq: int, vocab: int):
    """One (tokens, labels) next-token-prediction batch."""
    toks = jax.random.randint(key, (batch, seq + 1), 0, vocab, dtype=jnp.int32)
    return toks[:, :-1], toks[:, 1:]


class TokenStream:
    """Deterministic, restartable token stream with background prefetch.

    Sharding-aware: given a NamedSharding for the batch, device_put happens on
    the prefetch thread so the training step never blocks on H2D. ``state``
    (the step counter) is checkpointable, making the pipeline restart exactly
    where it left off after a failure.
    """

    def __init__(self, batch: int, seq: int, vocab: int, seed: int = 0,
                 sharding=None, prefetch: int = 2, start_step: int = 0):
        self.batch, self.seq, self.vocab = batch, seq, vocab
        self.seed = seed
        self.sharding = sharding
        self.step = start_step
        self._q: queue.Queue = queue.Queue(maxsize=prefetch)
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._worker, daemon=True)
        self._thread.start()

    def _make(self, step: int):
        rng = np.random.default_rng(np.uint64(self.seed * 1_000_003 + step))
        toks = rng.integers(0, self.vocab, (self.batch, self.seq + 1),
                            dtype=np.int32)
        batch = dict(tokens=toks[:, :-1], labels=toks[:, 1:])
        if self.sharding is not None:
            batch = {k: jax.device_put(v, self.sharding) for k, v in batch.items()}
        return batch

    def _worker(self):
        step = self.step
        while not self._stop.is_set():
            try:
                self._q.put(self._make(step), timeout=0.1)
                step += 1
            except queue.Full:
                continue

    def __iter__(self) -> Iterator[dict]:
        return self

    def __next__(self) -> dict:
        item = self._q.get()
        self.step += 1
        return item

    def state(self) -> dict:
        return dict(step=self.step, seed=self.seed)

    def close(self):
        self._stop.set()
        while not self._q.empty():
            self._q.get_nowait()
        self._thread.join(timeout=1.0)
