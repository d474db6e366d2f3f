"""jit'd wrapper for the route-rank kernel.

``route_rank(shard, num_shards)`` -> (rank_within_shard, per-shard
counts), dispatching between the Pallas TPU kernel and the XLA
reference (identical integer results).  This is the routing primitive of
the fused device-resident request path (:meth:`repro.core.shard.
ShardedOnlineStore.query` with ``device_routing=True``): shard ids come
from the on-device Feistel permutation, ranks place each row in its
shard's padded grid, counts drive the overflow check and the skew
histograms — one program, no host round-trip.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels import note_cutover, note_dispatch, vmem_row_budget
from repro.kernels.route.ref import route_rank_ref
from repro.kernels.route.route import ROUTE_LANE, route_rank_pallas

__all__ = ["route_rank"]

# The route kernel holds the whole batch resident: the (rows, 128) id
# tile, the output tile, the mask, and the two log-step prefix passes
# (running sum, rolled copy, iota guard) — 8 live i32 arrays.  Unlike the
# fold kernel it does not stream tiles, so residency IS the cap (2^19
# rows, compiled for a v5e); serving batches sit orders of magnitude
# below it.
_ROUTE_PALLAS_MAX_ROWS = ROUTE_LANE * vmem_row_budget(8)


def route_rank(
    shard: jnp.ndarray,  # (N,) int32 shard ids in [0, num_shards)
    *,
    num_shards: int,
    impl: str = "auto",
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(rank (N,) int32, counts (S,) int32): rank of each row within its
    shard in batch order, and rows per shard."""
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "xla"
        if impl == "pallas" and shard.shape[0] > _ROUTE_PALLAS_MAX_ROWS:
            # the batch outgrows the kernel's VMEM residency: counted, so
            # the size cut-over is never a silent fallback
            impl = "xla"
            note_cutover("route_rank")
    note_dispatch("route_rank", impl)
    return _route_rank(
        shard, num_shards=num_shards, impl=impl, interpret=interpret
    )


@functools.partial(
    jax.jit, static_argnames=("num_shards", "impl", "interpret")
)
def _route_rank(
    shard: jnp.ndarray,
    *,
    num_shards: int,
    impl: str,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    n = shard.shape[0]
    if impl == "xla":
        return route_rank_ref(shard, num_shards)
    # lane-major 2-D tiling; padding gets the inert id S (claimed by no
    # grid step, so pad lanes rank as 0 and count into no shard)
    rows = -(-n // ROUTE_LANE)
    rows += (-rows) % 8
    m = rows * ROUTE_LANE
    padded = jnp.full((m,), num_shards, jnp.int32).at[:n].set(
        jnp.asarray(shard, jnp.int32)
    )
    rank2d = route_rank_pallas(
        padded.reshape(rows, ROUTE_LANE),
        num_shards=num_shards,
        interpret=interpret,
    )
    rank = rank2d.reshape(m)[:n]
    counts = jnp.sum(
        (
            jnp.asarray(shard, jnp.int32)[:, None]
            == jnp.arange(num_shards, dtype=jnp.int32)[None, :]
        ).astype(jnp.int32),
        axis=0,
    )
    return rank, counts
