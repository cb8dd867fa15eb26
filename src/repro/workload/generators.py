"""YCSB-style workload generators, vectorized in JAX.

The hot inner loops — Zipfian CDF inversion, op-mix choice, value sizing,
Poisson inter-arrival sampling — run as one jitted program that fills a
whole batch of ops at a time; the per-op Python path is an array index
into pre-sampled numpy buffers.  Key distributions:

- `uniform`: every key equally likely;
- `zipfian`: rank r drawn with P(r) ∝ 1/r^theta (YCSB theta=0.99), with a
  bijective multiplicative scramble so hot ranks spread over the keyspace
  (and therefore over range partitions) instead of piling on node 0;
- `latest`: zipfian over recency — hot keys are the most recently written,
  skewing toward the tail of the keyspace.
"""

from __future__ import annotations

import enum
import math
import time
from dataclasses import dataclass
from functools import partial
from typing import Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.hostprof import BATCH_SPAN, SAMPLER_WAIT


class OpKind(enum.IntEnum):
    READ = 0
    WRITE = 1
    RMW = 2         # read-modify-write: strong read, then conditional put
    COND = 3        # conditional put at the last-read version
    TXN = 4         # multi-key transaction (adapter picks the partner keys)


@dataclass(frozen=True)
class Op:
    kind: OpKind
    key_index: int
    value_size: int


@dataclass
class WorkloadSpec:
    """One workload = key distribution + op mix + value sizing."""
    num_keys: int = 10_000
    key_dist: str = "zipfian"          # uniform | zipfian | latest
    zipf_theta: float = 0.99
    scramble: bool = True
    # op mix (normalized at build time)
    read_frac: float = 0.80
    write_frac: float = 0.15
    rmw_frac: float = 0.03
    cond_frac: float = 0.02
    txn_frac: float = 0.0              # multi-key transactions (PR 4)
    # fraction of TXN ops that deliberately span ranges (the adapter
    # resolves partner keys against the live range table, so "cross"
    # means a real 2PC and "local" the single-cohort fast path)
    txn_cross_frac: float = 0.5
    # value sizes (bytes)
    value_size: int = 4096
    value_size_dist: str = "fixed"     # fixed | uniform
    value_size_min: int = 256

    def mix(self) -> np.ndarray:
        m = np.array([self.read_frac, self.write_frac, self.rmw_frac,
                      self.cond_frac, self.txn_frac], dtype=np.float64)
        s = m.sum()
        if s <= 0:
            raise ValueError("op mix must have positive mass")
        return m / s


def _coprime_multiplier(n: int) -> int:
    """Odd multiplicative-hash constant coprime to n (bijective mod n)."""
    a = 2654435761 % n
    while a < 2 or math.gcd(a, n) != 1:
        a = (a + 1) % n or 3
    return a


def _zipf_cdf(n: int, theta: float) -> jnp.ndarray:
    # one-time precompute in f64 on the host; inversion happens in JAX
    ranks = np.arange(1, n + 1, dtype=np.float64)
    w = ranks ** (-theta)
    c = np.cumsum(w)
    return jnp.asarray(c / c[-1], jnp.float32)


@partial(jax.jit, static_argnames=("num_keys", "vfix", "vmin", "vmax",
                                   "batch"))
def _sample_batch(key, cdf: Optional[jnp.ndarray], mix_cdf: jnp.ndarray,
                  num_keys: int, vfix: int, vmin: int, vmax: int,
                  batch: int):
    """One fused sampling step: (key ranks, op kinds, value sizes, gaps).
    Each draw sits under a named scope, which names its device ops in a
    profiler trace."""
    k1, k2, k3, k4 = jax.random.split(key, 4)
    with jax.named_scope("zipf_search"):
        u = jax.random.uniform(k1, (batch,))
        if cdf is None:                       # uniform keys
            ranks = jnp.floor(u * num_keys).astype(jnp.int32)
        else:                                 # zipfian CDF inversion
            ranks = jnp.searchsorted(cdf, u).astype(jnp.int32)
        ranks = jnp.clip(ranks, 0, num_keys - 1)
    with jax.named_scope("op_mix"):
        ops = jnp.searchsorted(mix_cdf, jax.random.uniform(k2, (batch,)))
    with jax.named_scope("value_size"):
        if vmax > vmin:
            vsz = jax.random.randint(k3, (batch,), vmin, vmax + 1)
        else:
            vsz = jnp.full((batch,), vfix, jnp.int32)
    with jax.named_scope("gaps"):
        # unit-rate exponential gaps; the driver scales by 1/rate
        gaps = -jnp.log1p(-jax.random.uniform(k4, (batch,)))
    return ranks, ops.astype(jnp.int32), vsz.astype(jnp.int32), \
        gaps.astype(jnp.float32)


class OpStream:
    """Iterator of `Op`s backed by JAX batch sampling.

    `next_op()` costs an array read; a new jitted batch is drawn every
    `batch` ops.  Streams with the same (spec, seed) are identical, which
    makes every benchmark bit-reproducible.

    A driver that draws from the stream sets `sim` to its simulator; while
    that simulator carries a host profile, each refill is timed as
    `sampler_wait` and opens the profile's per-batch trace annotation.
    `first_refill_s` is the wall time of the first refill: tracing,
    compiling or loading the sampler, and its first run.
    """

    def __init__(self, spec: WorkloadSpec, seed: int = 0, batch: int = 8192):
        if spec.num_keys < 1:
            raise ValueError("num_keys must be >= 1")
        if spec.key_dist not in ("uniform", "zipfian", "latest"):
            raise ValueError(f"unknown key_dist {spec.key_dist!r}")
        self.spec = spec
        self.batch = batch
        self._key = jax.random.PRNGKey(seed)
        self._cdf = None
        if spec.key_dist in ("zipfian", "latest"):
            self._cdf = _zipf_cdf(spec.num_keys, spec.zipf_theta)
        self._mix_cdf = jnp.asarray(np.cumsum(spec.mix()), jnp.float32)
        self._mult = _coprime_multiplier(spec.num_keys) \
            if (spec.scramble and spec.key_dist == "zipfian"
                and spec.num_keys > 1) else 1
        self._offset = (seed * 40503 + 12345) % spec.num_keys
        if spec.value_size_dist == "uniform":
            self._vmin, self._vmax = spec.value_size_min, spec.value_size
        else:
            self._vmin = self._vmax = spec.value_size
        self._i = self.batch          # force refill on first use
        self._keys = self._ops = self._vsz = self._gaps = None
        self.sampled = 0
        self.sim = None
        self.first_refill_s: Optional[float] = None
        # `latest` support: the most recently inserted key index; drivers
        # bump this on successful writes
        self.insert_horizon = spec.num_keys

    def _refill(self) -> None:
        hp = self.sim.hostprof if self.sim is not None else None
        t0 = time.perf_counter() if self.first_refill_s is None else None
        if hp is None:
            keys, ops, vsz, gaps = self._draw()
        else:
            with jax.profiler.TraceAnnotation(BATCH_SPAN, **hp.batch_meta()):
                hp.enter(SAMPLER_WAIT)
                keys, ops, vsz, gaps = self._draw()
                hp.leave()
        if self._mult > 1:
            # bijective scramble rank -> key in int64 on the host (the
            # product overflows int32 for large keyspaces under jit)
            keys = ((keys.astype(np.int64) * self._mult + self._offset)
                    % self.spec.num_keys).astype(np.int32)
        self._keys, self._ops, self._vsz, self._gaps = keys, ops, vsz, gaps
        self._i = 0
        self.sampled += self.batch
        if t0 is not None:
            self.first_refill_s = time.perf_counter() - t0

    def _draw(self) -> tuple:
        """One batch from the sampler, as numpy arrays on the host."""
        self._key, sub = jax.random.split(self._key)
        out = _sample_batch(
            sub, self._cdf, self._mix_cdf, self.spec.num_keys,
            self.spec.value_size, self._vmin, self._vmax, self.batch)
        return tuple(np.asarray(x) for x in out)

    def _key_index(self, rank: int) -> int:
        if self.spec.key_dist == "latest":
            # rank 0 = newest key; clip to the current horizon
            return max(0, min(self.insert_horizon, self.spec.num_keys) - 1
                       - rank)
        return int(rank)

    def next_op(self) -> Op:
        if self._i >= self.batch:
            self._refill()
        i = self._i
        self._i += 1
        return Op(kind=OpKind(int(self._ops[i])),
                  key_index=self._key_index(int(self._keys[i])),
                  value_size=int(self._vsz[i]))

    def next_gap(self, rate: float) -> float:
        """Next Poisson inter-arrival time at `rate` ops/s."""
        if self._i >= self.batch:
            self._refill()
        g = float(self._gaps[self._i]) / rate
        # gaps ride along with ops in the same buffer; consuming a gap does
        # not consume the op at the same slot (open-loop drivers call
        # next_gap then next_op, which advances the cursor once)
        return g

    def __iter__(self) -> Iterator[Op]:
        while True:
            yield self.next_op()
