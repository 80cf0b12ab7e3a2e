"""Data pipelines: synthetic tabular generators (GBDT) and a
bounded-prefetch loader for straggler isolation."""

from __future__ import annotations

import queue
import threading

import numpy as np


def synthetic_tabular(n: int, d: int, seed: int = 0, task: str = "binary",
                      n_classes: int = 2, sparsity: float = 0.0):
    """Synthetic vertical-federated tabular data with a nonlinear target."""
    rng = np.random.default_rng(seed)
    X = rng.normal(0, 1, (n, d)).astype(np.float32)
    if sparsity:
        X[rng.random(X.shape) < sparsity] = 0.0
    w = rng.normal(0, 1, d)
    s = X @ w + 0.5 * (X[:, 0] * X[:, min(1, d - 1)]) \
        + 0.3 * rng.normal(0, 1, n)
    if task == "binary":
        y = (s > np.median(s)).astype(np.float64)
    else:
        qs = np.quantile(s, np.linspace(0, 1, n_classes + 1)[1:-1])
        y = np.digitize(s, qs).astype(np.float64)
    return X, y


class PrefetchLoader:
    """Bounded background prefetch; a slow source can never queue more than
    ``depth`` batches behind (skip-slow-shard straggler isolation).  With
    ``n_steps`` set the producer stops after that many batches -- the
    finite mode ``RowBlocks`` uses for one lookahead pass over a chunked
    source."""

    def __init__(self, fn, depth: int = 2, n_steps: int | None = None):
        self.fn = fn
        self.q: queue.Queue = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self._step = 0
        self._n_steps = n_steps
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def _run(self):
        while not self._stop.is_set():
            if self._n_steps is not None and self._step >= self._n_steps:
                return
            batch = self.fn(self._step)
            self._step += 1
            while not self._stop.is_set():
                try:
                    self.q.put(batch, timeout=0.5)
                    break
                except queue.Full:
                    continue

    def __call__(self, step: int):
        return self.q.get()

    def stop(self):
        self._stop.set()


class RowBlocks:
    """Chunked row source: the out-of-core data path's one abstraction.

    Wraps a pure ``fn(block_idx) -> (rows, n_features) float32`` and yields
    ``(start_row, X_block)`` in order; every consumer (streaming binning,
    chunked encrypt, block-wise histograms) sees the same fixed-size blocks
    so nothing upstream ever holds the full matrix.  ``from_array`` adapts
    an in-memory matrix (zero-copy views) for parity testing against the
    monolithic path.  Iteration optionally runs the source through a
    finite ``PrefetchLoader`` so block b+1 is generated/read while block b
    is being consumed.
    """

    def __init__(self, fn, n_rows: int, n_features: int, block: int,
                 prefetch: int = 0):
        if block <= 0:
            raise ValueError("block must be positive")
        self.fn = fn
        self.n_rows = int(n_rows)
        self.n_features = int(n_features)
        self.block = int(block)
        self.prefetch = int(prefetch)

    @property
    def n_blocks(self) -> int:
        return -(-self.n_rows // self.block)

    def block_rows(self, b: int) -> tuple:
        start = b * self.block
        return start, min(start + self.block, self.n_rows)

    @classmethod
    def from_array(cls, X: np.ndarray, block: int,
                   prefetch: int = 0) -> "RowBlocks":
        X = np.asarray(X)
        def fn(b):
            return X[b * block: (b + 1) * block]
        return cls(fn, X.shape[0], X.shape[1], block, prefetch=prefetch)

    def select_columns(self, lo: int, hi: int) -> "RowBlocks":
        """Column-slice view sharing this source's fn — how one generated
        stream splits into per-party feature ranges (vertical split)."""
        fn = self.fn
        def cut(b):
            return fn(b)[:, lo:hi]
        return RowBlocks(cut, self.n_rows, hi - lo, self.block,
                         prefetch=self.prefetch)

    def __iter__(self):
        if self.prefetch > 0 and self.n_blocks > 1:
            loader = PrefetchLoader(self.fn, depth=self.prefetch,
                                    n_steps=self.n_blocks)
            try:
                for b in range(self.n_blocks):
                    yield b * self.block, loader(b)
            finally:
                loader.stop()
        else:
            for b in range(self.n_blocks):
                yield b * self.block, self.fn(b)


_GEN_CHUNK = 8192   # synthetic row-generation granularity: fixed so the
                    # dataset is a pure function of (n, d, seed) no matter
                    # what block size the consumer picks


def synthetic_tabular_stream(n: int, d: int, block: int, seed: int = 0,
                             task: str = "binary", n_classes: int = 2,
                             sparsity: float = 0.0):
    """Out-of-core twin of ``synthetic_tabular``: returns ``(blocks, y)``
    where ``blocks`` is a ``RowBlocks`` whose fn regenerates its rows from
    seeded micro-chunks on every pass -- X is never materialized.  Rows
    are drawn in fixed ``_GEN_CHUNK``-sized chunks keyed by chunk index,
    so two streams over the same (n, d, seed) yield identical data even
    with different block sizes.  The label needs the global
    median/quantiles of the score, so one cheap O(n) float64 score vector
    is collected up front (the only full-length array this generator
    keeps)."""
    rng = np.random.default_rng((seed, 10007))
    w = rng.normal(0, 1, d)

    def chunk(ci):
        crng = np.random.default_rng((seed, ci))
        r = min(_GEN_CHUNK, n - ci * _GEN_CHUNK)
        Xc = crng.normal(0, 1, (r, d)).astype(np.float32)
        if sparsity:
            Xc[crng.random(Xc.shape) < sparsity] = 0.0
        return Xc

    def gen(b):
        lo = b * block
        hi = min(lo + block, n)
        parts = []
        for ci in range(lo // _GEN_CHUNK, (hi - 1) // _GEN_CHUNK + 1):
            Xc = chunk(ci)
            cs = ci * _GEN_CHUNK
            parts.append(Xc[max(lo - cs, 0): hi - cs])
        return parts[0] if len(parts) == 1 else np.concatenate(parts)

    blocks = RowBlocks(gen, n, d, block, prefetch=2)
    s = np.empty(n, np.float64)
    n_chunks = -(-n // _GEN_CHUNK)
    for ci in range(n_chunks):
        Xc = chunk(ci)
        erng = np.random.default_rng((seed, 20011, ci))
        start = ci * _GEN_CHUNK
        s[start:start + len(Xc)] = (
            Xc @ w + 0.5 * (Xc[:, 0] * Xc[:, min(1, d - 1)])
            + 0.3 * erng.normal(0, 1, len(Xc)))
    if task == "binary":
        y = (s > np.median(s)).astype(np.float64)
    else:
        qs = np.quantile(s, np.linspace(0, 1, n_classes + 1)[1:-1])
        y = np.digitize(s, qs).astype(np.float64)
    return blocks, y
