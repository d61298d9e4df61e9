"""The one generator behind every traffic mix.

A mix is a JSON file of parameters under ``bench/traffic/``; its ``kind``
names the driver that runs it, ``bench/drivers/<kind>.py``.  The kinds
here:

``solve``  one-shot solves back to back over a pool of measure pairs
           drawn once from the mix's ``pool_seed``, in an order drawn from
           the seed (``bench/drivers/solve.py``; the configuration fixes
           the shapes).
``waves``  closed waves of ``wave_size`` requests handed to the server at
           once.  Without ``pool`` every request is a new problem, and
           every wave holds the configuration's ``sizes`` in equal shares
           (the first sizes one more where they do not divide), in an order
           drawn from the seed.  With ``problem_seed`` set, every draw of
           the stream (problems, sizes, order) comes from it and not from
           the run's seed, so every seed sends the same waves: the engine's
           slot batches follow the order, and with them the work.  With
           ``pool`` set, the pool's problems take the sizes in equal shares,
           and each request is one of them under Zipf popularity ``zipf_s``
           (which problem is most popular is drawn from the seed); a
           problem's first request is sent as made, a later one
           byte-identical with probability ``identical_share`` and otherwise
           under a random rotation.

Everything is drawn from ``(seed, stream)``: the same pair gives the same
work, in the same order.  Stream 0 is the measured window; warm-up draws
from stream 1, so it never touches the window's problems.
"""
from __future__ import annotations

import dataclasses

import numpy as np

WINDOW, WARMUP = 0, 1


def rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([seed, stream]))


def key_words(seed: int, stream: int) -> int:
    """A 31-bit word for ``jax.random.PRNGKey`` from a seed of any size
    (``PRNGKey`` keeps only the low 32 bits of a larger int)."""
    return int(np.random.SeedSequence([seed, stream, 7]).generate_state(1)[0]
               >> 1)


def random_rotation(r: np.random.Generator, dim: int) -> np.ndarray:
    """A rotation matrix (det +1) drawn uniformly (QR of a Gaussian)."""
    q, u = np.linalg.qr(r.normal(size=(dim, dim)))
    q = q * np.sign(np.diag(u))[None, :]
    if np.linalg.det(q) < 0:
        q[:, 0] = -q[:, 0]
    return q


@dataclasses.dataclass(frozen=True)
class Request:
    #: identifies the problem's data within its (seed, stream)
    problem: int
    size: int
    #: "first" (as made), "same" (byte-identical repeat) or "rotated"
    variant: str
    #: (dim, dim) rotation applied to both clouds, for "rotated"
    rotation: np.ndarray | None = None


class Waves:
    """The waves of a ``waves`` mix, one list of `Request` per call."""

    def __init__(self, traffic: dict, sizes, dim: int, seed: int,
                 stream: int = WINDOW):
        self.wave_size = int(traffic["wave_size"])
        self.sizes = [int(s) for s in sizes]
        self.dim = dim
        if traffic.get("problem_seed") is not None:
            seed = int(traffic["problem_seed"])
        self.r = rng(seed, stream)
        self.pool = traffic.get("pool")
        self.identical_share = float(traffic.get("identical_share", 1.0))
        self.next_problem = 0
        if self.pool:
            self.pool_sizes = self.r.permutation(self.shares(self.pool))
            ranks = np.arange(1, self.pool + 1, dtype=np.float64)
            weights = ranks ** -float(traffic["zipf_s"])
            self.popularity = self.r.permutation(weights / weights.sum())
            self.sent = np.zeros(self.pool, bool)

    def shares(self, n: int) -> list:
        """``n`` sizes, the configuration's in equal shares."""
        return [self.sizes[i % len(self.sizes)] for i in range(n)]

    def request(self) -> Request:
        p = int(self.r.choice(self.pool, p=self.popularity))
        size = int(self.pool_sizes[p])
        if not self.sent[p]:
            self.sent[p] = True
            return Request(p, size, "first")
        if self.r.random() < self.identical_share:
            return Request(p, size, "same")
        return Request(p, size, "rotated", random_rotation(self.r, self.dim))

    def wave(self) -> list:
        if self.pool:
            return [self.request() for _ in range(self.wave_size)]
        first = self.next_problem
        self.next_problem += self.wave_size
        sizes = self.r.permutation(self.shares(self.wave_size))
        return [Request(first + i, int(n), "first")
                for i, n in enumerate(sizes)]
