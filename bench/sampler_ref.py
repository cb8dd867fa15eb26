"""Plain numpy reference of the workload sampler's draw.

It recomputes, from the stream's seeds alone, what every batch of the
program's `OpStream` must hold: key indexes, op kinds, value sizes and
unit-rate exponential gaps.  The random bits follow JAX's threefry-2x32
with partitionable counters (`jax.random.split`, `jax.random.uniform`),
written out here in uint32 numpy so that no program or JAX code is
reused.  `dtype` sets the precision of the uniforms, the CDFs and the
gaps: float32 is what the program states; a lower one is the control.
"""

from __future__ import annotations

import math

import ml_dtypes
import numpy as np

_ROT = ((13, 15, 26, 6), (17, 29, 16, 24))


def _rotl(x: np.ndarray, r: int) -> np.ndarray:
    return (x << np.uint32(r)) | (x >> np.uint32(32 - r))


def threefry2x32(k1: int, k2: int, x1: np.ndarray, x2: np.ndarray):
    """Threefry-2x32 with 20 rounds (Salmon et al., SC 2011)."""
    ks = [np.uint32(k1), np.uint32(k2),
          np.uint32(k1) ^ np.uint32(k2) ^ np.uint32(0x1BD11BDA)]
    x = [x1.astype(np.uint32) + ks[0], x2.astype(np.uint32) + ks[1]]
    with np.errstate(over="ignore"):
        for i in range(5):
            for r in _ROT[i % 2]:
                x[0] = x[0] + x[1]
                x[1] = _rotl(x[1], r) ^ x[0]
            x[0] = x[0] + ks[(i + 1) % 3]
            x[1] = x[1] + ks[(i + 2) % 3] + np.uint32(i + 1)
    return x[0], x[1]


def split(key: tuple[int, int], n: int) -> list[tuple[int, int]]:
    b1, b2 = threefry2x32(key[0], key[1], np.zeros(n, np.uint32),
                          np.arange(n, dtype=np.uint32))
    return [(int(a), int(b)) for a, b in zip(b1, b2)]


def uniform(key: tuple[int, int], n: int) -> np.ndarray:
    """Float32 uniforms in [0, 1) from 23 random mantissa bits."""
    b1, b2 = threefry2x32(key[0], key[1], np.zeros(n, np.uint32),
                          np.arange(n, dtype=np.uint32))
    bits = ((b1 ^ b2) >> np.uint32(9)) | np.uint32(0x3F800000)
    return bits.view(np.float32) - np.float32(1.0)


def zipf_cdf(n: int, theta: float) -> np.ndarray:
    w = np.arange(1, n + 1, dtype=np.float64) ** (-theta)
    c = np.cumsum(w)
    return c / c[-1]


def scramble_multiplier(n: int) -> int:
    """The odd constant that spreads zipfian ranks over the keyspace: the
    first value from 2654435761 mod n upward that is coprime to n."""
    a = 2654435761 % n
    while a < 2 or math.gcd(a, n) != 1:
        a = (a + 1) % n or 3
    return a


def _in(dtype, u: np.ndarray) -> np.ndarray:
    """Float32 uniforms carried into `dtype` by cutting mantissa bits, as a
    uniform drawn in that precision would have them: never rounded up to 1."""
    if np.dtype(dtype) == np.float32:
        return u
    nmant = ml_dtypes.finfo(dtype).nmant
    keep = np.uint32(~((1 << (23 - nmant)) - 1) & 0xFFFFFFFF)
    return (u.view(np.uint32) & keep).view(np.float32).astype(dtype)


class SamplerReference:
    """Batches of one stream: `batch(i)` gives batch i as drawn from the
    random key `(0, seed)`, with zipfian ranks scrambled into key indexes
    by the offset that `placement_seed` sets."""

    def __init__(self, seed: int, placement_seed: int, num_keys: int,
                 theta: float, mix: list[float], value_size: int,
                 batch: int, dtype=np.float32):
        self.n, self.batch_size, self.value_size = num_keys, batch, value_size
        self.dtype = dtype
        self.cdf = zipf_cdf(num_keys, theta).astype(dtype)
        m = np.asarray(mix, np.float64)
        self.mix_cdf = np.cumsum(m / m.sum()).astype(dtype)
        self.mult = scramble_multiplier(num_keys) if num_keys > 1 else 1
        self.offset = (placement_seed * 40503 + 12345) % num_keys
        self._keys = [(0, seed & 0xFFFFFFFF)]

    def _subkey(self, i: int) -> tuple[int, int]:
        while len(self._keys) <= i + 1:
            nxt, _ = split(self._keys[-1], 2)
            self._keys.append(nxt)
        return split(self._keys[i], 2)[1]

    def batch(self, i: int):
        k1, k2, _k3, k4 = split(self._subkey(i), 4)
        b = self.batch_size
        u = _in(self.dtype, uniform(k1, b))
        ranks = np.clip(np.searchsorted(self.cdf, u, side="left"), 0,
                        self.n - 1).astype(np.int64)
        keys = (ranks * self.mult + self.offset) % self.n
        ops = np.searchsorted(self.mix_cdf,
                              _in(self.dtype, uniform(k2, b)), side="left")
        vsz = np.full(b, self.value_size, np.int64)
        u4 = _in(self.dtype, uniform(k4, b))
        gaps = -np.log1p(-u4.astype(np.float64)).astype(self.dtype)
        return keys, ops, vsz, gaps
