"""Ring-route rank kernel (Pallas TPU).

The serial dependency of device-side routing is the *rank within shard*:
row i's slot in its shard's padded grid is the number of earlier batch
rows owning the same shard — a segmented prefix count over the batch.
On TPU that is one VMEM-resident pass per shard:

* the shard-id batch lives as a (rows, 128) int32 tile (lane-major
  flattening of the 1-D batch, padded with an inert id);
* grid step ``s`` masks the tile to shard ``s`` and computes the
  flat-order exclusive prefix count in two log-step passes of shifted
  adds (``pltpu.roll`` + an iota guard): along lanes within each row, then
  along rows over the per-row totals — no cumsum (Mosaic has no lowering
  for it), no gather, no sort;
* each step merges its ranks into the output tile, so after S steps every
  row holds its rank.  S grid steps pipeline; the tile stays resident.

Integer adds only, so the kernel is bit-identical to
:func:`repro.kernels.route.ref.route_rank_ref` (asserted in interpret
mode on CPU — the repo's standing kernel-parity pattern).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["route_rank_pallas", "ROUTE_LANE"]

ROUTE_LANE = 128  # f32/i32 native lane width — tile rows are (8, 128)


def _prefix_sum(x: jnp.ndarray, axis: int) -> jnp.ndarray:
    """Inclusive prefix sum along ``axis`` by log-step shifted adds:
    ``x += x shifted by d`` for d = 1, 2, 4, ... (exact for integers)."""
    n = x.shape[axis]
    idx = jax.lax.broadcasted_iota(jnp.int32, x.shape, axis)
    d = 1
    while d < n:
        x = x + jnp.where(idx >= d, pltpu.roll(x, d, axis), 0)
        d *= 2
    return x


def _route_rank_kernel(shard_ref, rank_ref):
    s = pl.program_id(0)
    mask = (shard_ref[...] == s).astype(jnp.int32)  # (rows, LANE)
    # flat-order exclusive prefix count: earlier lanes of this row plus
    # all lanes of earlier rows
    within = _prefix_sum(mask, 1) - mask
    row_tot = jnp.broadcast_to(
        jnp.sum(mask, axis=1, keepdims=True), mask.shape
    )
    prior = _prefix_sum(row_tot, 0) - row_tot
    rank_s = within + prior

    @pl.when(s == 0)
    def _init():
        rank_ref[...] = jnp.where(mask == 1, rank_s, 0)

    @pl.when(s > 0)
    def _merge():
        rank_ref[...] = jnp.where(mask == 1, rank_s, rank_ref[...])


@functools.partial(
    jax.jit, static_argnames=("num_shards", "interpret")
)
def route_rank_pallas(
    shard2d: jnp.ndarray,  # (rows, ROUTE_LANE) int32, padded with >= S
    *,
    num_shards: int,
    interpret: bool = False,
) -> jnp.ndarray:
    """Rank-within-shard per element of the (rows, LANE) shard-id tile."""
    rows, lane = shard2d.shape
    return pl.pallas_call(
        _route_rank_kernel,
        grid=(num_shards,),
        in_specs=[
            pl.BlockSpec(
                (rows, lane), lambda s: (0, 0), memory_space=pltpu.VMEM
            )
        ],
        out_specs=pl.BlockSpec(
            (rows, lane), lambda s: (0, 0), memory_space=pltpu.VMEM
        ),
        out_shape=jax.ShapeDtypeStruct((rows, lane), jnp.int32),
        interpret=interpret,
    )(shard2d.astype(jnp.int32))
