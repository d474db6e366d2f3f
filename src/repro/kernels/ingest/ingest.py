"""Pallas fused ingest: ring scatter + bucket pre-agg merge, one batch pass.

The split XLA path makes two passes over the batch payloads: one scatter
into the ring, then a segmented reduction + scatter into the bucket
states.  This kernel walks the (key, ts)-sorted batch ONCE over a
``grid=(N,)`` of rows: each step writes its row into the resident ring
blocks of its key AND folds it into a VMEM accumulator for its (key,
bucket) segment, flushing the accumulator into the resident bucket blocks
when the segment ends.

State is in the stored key-minor layout of :mod:`repro.core.storage`
(small axes first, then (K, slot)), so every block is a group of
``KEY_GROUP`` = 8 keys (one sublane tile) by the whole slot axis:
``(8, C)`` ring timestamps, ``(F, 8, C)`` ring lanes, ``(F, NUM_STATS, 8,
NB)`` bucket stats, ``(F, 8, NB)`` bitmaps, ``(8, NB)`` bucket ids.  Those
blocks meet Mosaic's tiling rule (last two block dims divisible by 8 and
128, or equal to the array's) and alias the HBM state in place: XLA never
relays the state out for the kernel.

Residency model: every state array is an aliased input/output pair whose
block index is the row's key group (``PrefetchScalarGridSpec`` with
scalar-prefetched index maps).  Rows are key-sorted, so each group's
blocks are visited in one consecutive run: seeded from the aliased input
on the run's first row, mutated in VMEM across the run (cells selected by
sublane/lane iota masks), and written back when the block index moves on.
Pad rows (sentinel key == K) are index-mapped to a neighbouring real key
(fill in ops.py) so they never fault a block switch, and every state write
is gated on the row's validity.  The cursor advance is a (K,) scatter-add
outside the kernel.

Bit-exactness with the oracle: the per-segment fold runs in batch row
order (``((ident ⊕ r1) ⊕ r2) …``) and merges into the stored state once
per segment — the same association as the oracle's segment reduction —
and min/max/OR lanes are order-free, so results match the split path
bit-for-bit (tier-1 asserts it).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.aggregates import (
    LANES,
    NUM_STATS,
    lane_combine,
    lane_identity,
    row_bitmap,
)

__all__ = ["fused_ingest_pallas", "KEY_GROUP"]

KEY_GROUP = 8  # keys per state block: one sublane tile of the (K, slot) axes


def _fused_ingest_kernel(
    # scalar prefetch (SMEM), computed by the ops.py prologue
    ckey_ref,    # (N,) block key per row (pads filled from a neighbour)
    gstart_ref,  # (N,) 1 on the first row of each key-group run
    sstart_ref,  # (N,) 1 on the first row of each (key, bucket) run
    flush_ref,   # (N,) 1 on the last row of a run holding >= 1 valid row
    valid_ref,   # (N,) 1 for real rows, 0 for sentinel pads
    slot_r_ref,  # (N,) ring slot (cursor0[key] + valid rank) % C
    ts_ref,      # (N,) row timestamps
    cbid_ref,    # (N,) absolute bucket id (pads filled)
    slot_b_ref,  # (N,) bucket slot = cbid % NB
    vals_ref,    # (N*F,) f32 row payloads, row-major
    vals2_ref,   # (N*F,) f32 pre-rounded v*v (see fused_ingest_pallas)
    rbm_ref,     # (N*F,) int32 row bitmap contributions (aggregates)
    # state blocks (aliased in/out pairs)
    rts_in, rvals_in, bst_in, bbm_in, bid_in,
    rts_out, rvals_out, bst_out, bbm_out, bid_out,
    # scratch
    acc_stats,   # (F * NUM_STATS, NB) f32 running segment fold, one
                 # lane-broadcast row per (lane, stat)
    acc_bm,      # (F, NB) int32 running segment bitmap OR per lane
):
    i = pl.program_id(0)
    f = rvals_out.shape[0]
    g, cap = rts_out.shape
    nb = bid_out.shape[1]
    r = ckey_ref[i] % g  # the key's sublane within its group

    # first row of a key group: the group's blocks just streamed in — seed
    # the output (resident) copies from the aliased inputs so unwritten
    # cells round-trip unchanged
    @pl.when(gstart_ref[i] == 1)
    def _init_blocks():
        rts_out[...] = rts_in[...]
        rvals_out[...] = rvals_in[...]
        bst_out[...] = bst_in[...]
        bbm_out[...] = bbm_in[...]
        bid_out[...] = bid_in[...]

    @pl.when(sstart_ref[i] == 1)
    def _reset_segment():
        for j, lane in enumerate(LANES):
            for l in range(f):
                acc_stats[pl.ds(l * NUM_STATS + j, 1), :] = jnp.full(
                    (1, nb), lane_identity(lane), jnp.float32
                )
        acc_bm[...] = jnp.zeros_like(acc_bm)

    @pl.when(valid_ref[i] == 1)
    def _ingest_row():
        # ring scatter: ts + payload at (this key's sublane, ring slot)
        at = (
            (jax.lax.broadcasted_iota(jnp.int32, (g, cap), 0) == r)
            & (jax.lax.broadcasted_iota(jnp.int32, (g, cap), 1)
               == slot_r_ref[i])
        )
        rts_out[...] = jnp.where(at, ts_ref[i], rts_out[...])
        for l in range(f):
            v = vals_ref[i * f + l]
            rvals_out[l] = jnp.where(at, v, rvals_out[l])
            # bucket pre-agg: fold the lifted row (v, 1, v, v, v*v) into
            # the segment accumulator.  The sumsq increment is the
            # PRE-ROUNDED v*v loaded as its own operand — computing v*v
            # here would let the backend contract the mul into the add
            # (fma), skipping the rounding the oracle's materialized lift
            # takes and breaking bit-exactness by 1 ulp.
            lifted = (v, 1.0, v, v, vals2_ref[i * f + l])
            for j, lane in enumerate(LANES):
                row = pl.ds(l * NUM_STATS + j, 1)
                acc_stats[row, :] = lane_combine(
                    lane, acc_stats[row, :], lifted[j]
                )
            acc_bm[pl.ds(l, 1), :] = (
                acc_bm[pl.ds(l, 1), :] | rbm_ref[i * f + l]
            )

    @pl.when(flush_ref[i] == 1)
    def _flush_segment():
        b = cbid_ref[i]
        bid = bid_out[...]
        at = (
            (jax.lax.broadcasted_iota(jnp.int32, (g, nb), 0) == r)
            & (jax.lax.broadcasted_iota(jnp.int32, (g, nb), 1)
               == slot_b_ref[i])
        )
        # a slot still holding an older bucket id restarts from identity
        stale = at & (bid != b) & (bid != -1)
        for l in range(f):
            for j, lane in enumerate(LANES):
                cur = bst_out[l, j]
                base = jnp.where(stale, lane_identity(lane), cur)
                acc = acc_stats[pl.ds(l * NUM_STATS + j, 1), :]  # (1, NB)
                bst_out[l, j] = jnp.where(
                    at, lane_combine(lane, base, acc), cur
                )
            cur = bbm_out[l]
            base = jnp.where(stale, 0, cur)
            bbm_out[l] = jnp.where(at, base | acc_bm[pl.ds(l, 1), :], cur)
        bid_out[...] = jnp.where(at, b, bid)


def fused_ingest_pallas(
    ring_ts: jnp.ndarray,    # (K, C) int32
    ring_vals: jnp.ndarray,  # (F, K, C) f32
    bstats: jnp.ndarray,     # (F, NUM_STATS, K, NB) f32
    bbitmap: jnp.ndarray,    # (F, K, NB) int32
    bbucket: jnp.ndarray,    # (K, NB) int32
    ts: jnp.ndarray,         # (N,) int32
    vals: jnp.ndarray,       # (N, F) f32
    plan: Tuple[jnp.ndarray, ...],  # the ops.py prologue arrays
    *,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, ...]:
    """One fused pass; returns the five updated state arrays (ring
    ts/vals, bucket stats/bitmap/ids)."""
    K, cap = ring_ts.shape
    f = ring_vals.shape[0]
    nb = bbucket.shape[1]
    n = ts.shape[0]
    G = KEY_GROUP
    (ckey, gstart, sstart, flush, valid, slot_r, cbid, slot_b) = plan

    state = (ring_ts, ring_vals, bstats, bbitmap, bbucket)
    pad = -K % G
    if pad:
        # key counts that are not a multiple of the group (small test
        # stores) are padded for the call; deployment key counts are
        # multiples of 8 and take the in-place path
        state = tuple(
            jnp.pad(x, [(0, 0)] * (x.ndim - 2) + [(0, pad), (0, 0)])
            for x in state
        )

    def by_group(rank):
        def index_map(i, ckey, *_):
            return (0,) * (rank - 2) + (ckey[i] // G, 0)

        return index_map

    state_specs = [
        pl.BlockSpec((G, cap), by_group(2)),                  # ring_ts
        pl.BlockSpec((f, G, cap), by_group(3)),               # ring_vals
        pl.BlockSpec((f, NUM_STATS, G, nb), by_group(4)),     # bstats
        pl.BlockSpec((f, G, nb), by_group(3)),                # bbitmap
        pl.BlockSpec((G, nb), by_group(2)),                   # bbucket
    ]
    # vals2 is the sumsq increment, rounded HERE (outside the kernel) so
    # the kernel's accumulator add sees a materialized operand rather
    # than an adjacent multiply it could fma-contract (see the kernel).
    # The bitmap lift is the library's own (aggregates.row_bitmap).
    rows = (
        vals.reshape(n * f),
        (vals * vals).reshape(n * f),
        row_bitmap(vals).reshape(n * f),
    )
    prefetch = plan[:6] + (jnp.asarray(ts, jnp.int32),) + plan[6:] + rows
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(n,),
        in_specs=state_specs,
        out_specs=state_specs,
        scratch_shapes=[
            pltpu.VMEM((f * NUM_STATS, nb), jnp.float32),
            pltpu.VMEM((f, nb), jnp.int32),
        ],
    )
    # operand order: the prefetch scalars, then the 5 state arrays —
    # input_output_aliases indices count the prefetch operands
    outs = pl.pallas_call(
        _fused_ingest_kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype) for x in state],
        input_output_aliases={len(prefetch) + j: j for j in range(5)},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(*prefetch, *state)
    if pad:
        outs = [x[..., :K, :] for x in outs]
    return tuple(outs)
