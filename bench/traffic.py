"""Training traffic: token batches made from the run's seed.

A traffic mix is a JSON file under ``bench/traffic/`` that this one
generator reads:

    {"batch": 4, "per_worker": 2, "seq": 1024, "regime": "fresh", "h": null}

``batch`` is the global batch (rows), ``per_worker`` the rows each worker
takes of it (so the mix fixes the workers' count: a run with another
count is refused), ``seq`` the tokens per row.  The
``fresh`` regime draws a new batch for every step; ``fixed`` reuses step
0's batch (the paper's full-batch regime).  ``h`` is the heterogeneity
dial in [0, 1]: null gives every row the same stream, a number gives
worker m's rows (``m*B/W:(m+1)*B/W``) the token-noise level at that point
of the 0.01 -> 0.4 ramp.

The token stream follows ``repro.data.TokenStream`` and the dial
``repro.netsim.hetero``, kept here so that a later change to the
program's data pipeline does not move this yardstick.  Batches are
deterministic in ``(seed, step, worker)``.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import numpy as np

NOISE = 0.1
NOISE_LO, NOISE_HI = 0.01, 0.4


@dataclasses.dataclass(frozen=True)
class Traffic:
    batch: int
    seq: int
    regime: str = "fresh"
    h: Optional[float] = None
    per_worker: Optional[int] = None

    def __post_init__(self):
        if self.regime not in ("fresh", "fixed"):
            raise ValueError(f"regime must be 'fresh' or 'fixed', got "
                             f"{self.regime!r}")
        if self.h is not None and not 0.0 <= self.h <= 1.0:
            raise ValueError(f"h must be in [0, 1], got {self.h}")

    @classmethod
    def from_dict(cls, d: Dict) -> "Traffic":
        return cls(batch=int(d["batch"]), seq=int(d["seq"]),
                   regime=d.get("regime", "fresh"), h=d.get("h"),
                   per_worker=d.get("per_worker"))

    @property
    def tokens_per_step(self) -> int:
        return self.batch * self.seq


def stream_batch(vocab: int, seed: int, step: int, worker: int, rows: int,
                 length: int, noise: float = NOISE) -> np.ndarray:
    """``rows`` sequences of ``length`` ids: x_{t+1} = (a*x_t + drift_w)
    mod V, replaced by a uniform id with probability ``noise``.

    The draws are made in bulk, and the recurrence is solved in closed
    form from the last reset: k steps after id r the chain reads
    (a^k r + drift (a^{k-1} + ... + 1)) mod V, exact in int64 since every
    factor is below V.  The same process as ``repro.data.TokenStream``,
    a few hundred times cheaper on the host, drawn in another order."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, step, worker]))
    a = 6364136223846793005 % vocab
    drift = 1 + 97 * worker
    first = rng.integers(0, vocab, size=(rows, 1))
    resets = rng.integers(0, vocab, size=(rows, length - 1))
    use = rng.random((rows, length - 1)) < noise
    start = np.concatenate([first, resets], axis=1)          # (rows, length)
    reset = np.concatenate([np.ones((rows, 1), bool), use], axis=1)
    pos = np.arange(length)
    last = np.maximum.accumulate(np.where(reset, pos, 0), axis=1)
    k = pos - last                                            # steps since
    power, geo = np.ones(length, np.int64), np.zeros(length, np.int64)
    for i in range(1, length):
        power[i] = power[i - 1] * a % vocab
        geo[i] = (geo[i - 1] * a + 1) % vocab
    base = np.take_along_axis(start, last, axis=1)
    return ((power[k] * base + drift * geo[k]) % vocab).astype(np.int32)


def noise_levels(workers: int, h: float) -> list:
    """Per-worker noise at dial position ``h`` (h = 0: all at the ramp's
    midpoint; h = 1: the full ramp)."""
    center = 0.5 * (NOISE_LO + NOISE_HI)
    return [(1.0 - h) * center
            + h * (NOISE_LO + (NOISE_HI - NOISE_LO) * m / max(workers - 1, 1))
            for m in range(workers)]


def make_batch(traffic: Traffic, vocab: int, seed: int, step: int,
               workers: int) -> Dict[str, np.ndarray]:
    """The global batch of ``step``: int32 ``tokens`` and ``targets``,
    each (batch, seq)."""
    if traffic.per_worker is not None and \
            traffic.per_worker * workers != traffic.batch:
        raise ValueError(f"{workers} workers of {traffic.per_worker} rows "
                         f"each do not make the batch of {traffic.batch}")
    step = 0 if traffic.regime == "fixed" else step
    if traffic.h is None:
        toks = stream_batch(vocab, seed, step, 0, traffic.batch,
                            traffic.seq + 1)
    else:
        if traffic.batch % workers:
            raise ValueError(f"batch {traffic.batch} does not split over "
                             f"{workers} workers")
        per = traffic.batch // workers
        levels = noise_levels(workers, traffic.h)
        toks = np.concatenate([
            stream_batch(vocab, seed, step, m, per, traffic.seq + 1,
                         noise=levels[m]) for m in range(workers)])
    return {"tokens": toks[:, :-1], "targets": toks[:, 1:]}
