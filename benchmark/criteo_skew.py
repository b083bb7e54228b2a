"""`criteo-skew`: the one generator of Criteo-shaped records for every mix.

Field f of a record draws a rank from a Zipf-like law truncated to that
field's public cardinality N_f, P(rank = r) proportional to the integral of
x^-s over [r, r+1) — the bounded Pareto law discretised, which has a closed
inverse so a draw costs one power — and the rank is scrambled by a fixed odd
multiplier per field into the int32 a raw Criteo value is. Distinct ranks
stay distinct (an odd multiplier is a bijection mod 2^32); the program's own
`fs.hashed` then buckets the value. A field with 3 values puts a whole batch
on 3 rows; a field with 10M values has a hot head and a long tail of ids
seen once.

Dense features are log-normal counts, labels Bernoulli(label_rate).
Everything comes from the seed. NumPy only: the job driver's process calls
this and must stay off JAX.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

NUM_DENSE = 13
NUM_CAT = 26


def field_multiplier(f: int) -> int:
    """Fixed odd 32-bit multiplier of field f."""
    return ((0x9E3779B1 + 2 * f * 0x632BE5AB) & 0xFFFFFFFF) | 1


def field_offset(f: int) -> int:
    return (0x7F4A7C15 * (f + 1)) & 0xFFFFFFFF


def zipf_ranks(u: np.ndarray, cardinality: int, s: float) -> np.ndarray:
    """Ranks in [0, cardinality) from uniforms in [0, 1): the inverse CDF of
    the bounded Pareto law on [1, cardinality + 1), floored."""
    if cardinality <= 1:
        return np.zeros(u.shape, np.int64)
    a = 1.0 - s
    span = 1.0 - float(cardinality + 1) ** a
    x = np.power(1.0 - u * span, 1.0 / a)
    return np.clip(x.astype(np.int64) - 1, 0, cardinality - 1)


def _field_column(seed: int, f: int, n: int, cardinality: int, s: float):
    rng = np.random.Generator(np.random.PCG64(np.random.SeedSequence([seed, 1, f])))
    ranks = zipf_ranks(rng.random(n), cardinality, s).astype(np.uint32)
    # uint32 arithmetic wraps: the multiplier is a bijection mod 2^32
    raw = ranks * np.uint32(field_multiplier(f)) + np.uint32(field_offset(f))
    return raw.view(np.int32)


def generate(seed: int, n: int, cardinalities, zipf_s: float = 1.1,
             label_rate: float = 0.256, dense_mu: float = 1.0,
             dense_sigma: float = 1.5, threads: int = 8) -> dict:
    """n records: {"dense": (n, 13) float32 counts, "cat": (n, 26) int32 raw
    values, "labels": (n,) int32}. Field columns are drawn in parallel
    (NumPy releases the GIL inside its kernels); the result depends on the
    seed alone, not on the thread count."""
    if len(cardinalities) != NUM_CAT:
        raise ValueError(f"need {NUM_CAT} cardinalities, got {len(cardinalities)}")
    by_field = np.empty((NUM_CAT, n), np.int32)     # a field's column contiguous

    def fill(f):
        by_field[f] = _field_column(seed, f, n, int(cardinalities[f]), zipf_s)

    def dense_and_labels():
        rng = np.random.Generator(np.random.PCG64(np.random.SeedSequence([seed, 2])))
        normal = rng.standard_normal((n, NUM_DENSE), dtype=np.float32)
        counts = np.floor(np.exp(normal * np.float32(dense_sigma) + np.float32(dense_mu)))
        return counts, (rng.random(n) < label_rate).astype(np.int32)

    cat = np.empty((n, NUM_CAT), np.int32)
    block = 16384           # a transpose in cache-sized blocks, not one strided pass

    def transpose(start):
        cat[start:start + block] = by_field[:, start:start + block].T

    with ThreadPoolExecutor(max_workers=max(1, threads)) as pool:
        rest = pool.submit(dense_and_labels)
        list(pool.map(fill, range(NUM_CAT)))
        list(pool.map(transpose, range(0, n, block)))
        dense, labels = rest.result()
    return {"dense": dense, "cat": cat, "labels": labels}


def from_traffic(seed: int, n: int, cardinalities, traffic: dict) -> dict:
    return generate(
        seed, n, cardinalities, zipf_s=float(traffic["zipf_s"]),
        label_rate=float(traffic["label_rate"]),
        dense_mu=float(traffic["dense_lognormal_mu"]),
        dense_sigma=float(traffic["dense_lognormal_sigma"]))
