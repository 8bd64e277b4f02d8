"""Hand-scheduled collectives: ring all-reduce with compute overlap.

XLA schedules most collectives well, but the classic distributed-
optimization trick — overlapping the gradient all-reduce with trailing
backward compute — sometimes needs to be *structural*: a ring
reduce-scatter/all-gather built from ``jax.lax.ppermute`` inside
``shard_map`` exposes per-chunk boundaries that compute can interleave
with (each of the 2(n-1) steps moves 1/n of the tensor, so the first
gradient chunks are ready for the optimizer while later chunks are still
on the wire).

These are used by the training stack as an OPTIONAL substitute for the
pod-axis gradient all-reduce (combined with int8 compression the wire
format is chunk-quantized), and they double as executable documentation
of the wire cost model the roofline uses: ring all-reduce moves
2 (n-1)/n x bytes per chip.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P


def _ring_reduce_scatter(x: jax.Array, axis_name: str) -> jax.Array:
    """Per-shard reduce-scatter over a ring. x: [n*chunk, ...] local copy
    (unreduced); returns this device's reduced chunk [chunk, ...]."""
    n = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    chunks = x.reshape((n, -1) + x.shape[1:])

    # Step i: send the partial for chunk (idx - i), receive the partial
    # for chunk (idx - i - 1), add our own slice of it. After n-1 steps
    # device idx holds the complete sum for chunk (idx + 1) % n.
    acc = chunks[idx]
    for i in range(n - 1):  # n is small (ring over pods/data groups)
        acc = jax.lax.ppermute(
            acc, axis_name, perm=[(d, (d + 1) % n) for d in range(n)]
        )
        acc = acc + chunks[(idx - i - 1) % n]
    return acc


def _ring_all_gather(chunk: jax.Array, axis_name: str) -> jax.Array:
    n = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    pieces = [chunk]
    cur = chunk
    for _ in range(n - 1):
        cur = jax.lax.ppermute(
            cur, axis_name, perm=[(d, (d + 1) % n) for d in range(n)]
        )
        pieces.append(cur)
    # piece j arrived from device (idx - j) % n, and after the ring
    # reduce-scatter device d holds reduced chunk (d + 1) % n — so piece j
    # is chunk (idx - j + 1) % n.
    stacked = jnp.stack(pieces)  # [n, chunk, ...]
    order = (idx + 1 - jnp.arange(n)) % n
    canonical = jnp.zeros_like(stacked)
    canonical = canonical.at[order].set(stacked)
    return canonical.reshape((-1,) + chunk.shape[1:])


def ring_all_reduce(
    x: jax.Array,
    mesh: Mesh,
    axis_name: str,
    chunk_fn: Optional[Callable[[jax.Array], jax.Array]] = None,
) -> jax.Array:
    """All-reduce x (replicated per device along `axis_name`) via a ring.

    ``chunk_fn`` is applied to each reduced chunk as it lands — the
    overlap hook (e.g. int8 decompress + optimizer update per chunk).
    Requires leading dim divisible by the axis size.
    """
    n = mesh.shape[axis_name]
    if x.shape[0] % n != 0:
        raise ValueError(f"leading dim {x.shape[0]} not divisible by {n}")

    def body(local):
        reduced = _ring_reduce_scatter(local, axis_name)
        if chunk_fn is not None:
            reduced = chunk_fn(reduced)
        return _ring_all_gather(reduced, axis_name)

    spec = P(*([None] * x.ndim))
    return jax.shard_map(
        body, mesh=mesh, in_specs=spec, out_specs=spec, check_vma=False
    )(x)


def wire_bytes_ring_all_reduce(nbytes: int, n: int) -> float:
    """Analytic wire bytes per chip for a ring all-reduce of `nbytes`."""
    return 2.0 * nbytes * (n - 1) / n
