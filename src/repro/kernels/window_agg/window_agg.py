"""Pre-aggregated multi-window query kernel (Pallas TPU).

FeatInsight's hot path: a request row arrives; every feature of the view
needs (sum, count, min, max, sumsq) over several RANGE windows of the
request key's history.  The skiplist walk of the CPU system becomes, on
TPU:

* the query's per-key ring rows and bucket-aggregate rows are selected by
  a **scalar-prefetched index map** — q_key is prefetched into SMEM before
  the grid step so the DMA engine fetches exactly the key's 8-key group
  of the stored key-minor state ((8, C) ring timestamps, (L, 8, C) ring
  lanes, (L, 5, 8, NB) bucket stats, (8, NB) bucket ids) into VMEM, and
  the kernel reads the key's sublane of each (no gather op in the kernel
  body, no host round-trip);
* all windows and all lanes are evaluated from that single VMEM-resident
  tile in one grid step — the "parallelize window operations on the same
  table" optimization of the paper, expressed as vector ops over the
  (C, L) tile;
* middle buckets are selected by *membership* (b_lo < id < b_q) rather
  than enumeration, so the bucket ring needs no modular walk.

Grid: (Q,) — one query per step; Q queries pipeline their DMAs.
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["window_stats_pallas", "fold_levels_pallas"]

_TS_EMPTY = -2147483648  # python literal: kernels must not capture arrays
_KEY_GROUP = 8  # keys per state block: one sublane tile of (K, slot)
_POS_INF = 3.0e38
_NEG_INF = -3.0e38


def _window_agg_kernel(
    qkey_ref, qts_ref,              # scalar prefetch (SMEM)
    ts_ref, lanes_ref, bstats_ref, bbucket_ref, qlanes_ref,
    out_ref,
    *,
    windows: Sequence[int],
    bucket_size: int,
):
    i = pl.program_id(0)
    ts_q = qts_ref[i]
    B = jnp.int32(bucket_size)
    r = pl.ds(qkey_ref[i] % _KEY_GROUP, 1)  # the key's sublane
    L = lanes_ref.shape[0]

    ts = ts_ref[r, :][0]                                    # (C,)
    g = jnp.stack([lanes_ref[l, r, :][0] for l in range(L)], -1)  # (C, L)
    bstats = jnp.stack(
        [
            jnp.stack([bstats_ref[l, j, r, :][0] for j in range(5)], -1)
            for l in range(L)
        ],
        1,
    )                                                       # (NB, L, 5)
    bids = bbucket_ref[r, :][0]                             # (NB,)
    ql = qlanes_ref[0, 0]                                   # (L,)

    valid = ts != _TS_EMPTY
    bucket_row = ts // B
    not_future = ts <= ts_q

    for wi, T in enumerate(windows):
        T = jnp.int32(T)
        lo = ts_q - T + 1
        b_q = ts_q // B
        b_lo = (ts_q - T) // B
        in_lo = ts >= lo
        head = valid & not_future & in_lo & (bucket_row == b_lo) & (b_lo != b_q)
        tail = valid & not_future & in_lo & (bucket_row == b_q)
        raw = (head | tail)[:, None]  # (C, 1)
        rawf = raw.astype(jnp.float32)

        s_sum = jnp.sum(g * rawf, axis=0) + ql
        s_cnt = jnp.sum(jnp.broadcast_to(rawf, g.shape), axis=0) + 1.0
        s_min = jnp.minimum(
            jnp.min(jnp.where(raw, g, _POS_INF), axis=0), ql
        )
        s_max = jnp.maximum(
            jnp.max(jnp.where(raw, g, _NEG_INF), axis=0), ql
        )
        s_sq = jnp.sum(g * g * rawf, axis=0) + ql * ql

        mid = ((bids > b_lo) & (bids < b_q))[:, None]  # (NB, 1)
        midf = mid.astype(jnp.float32)
        m_sum = jnp.sum(bstats[..., 0] * midf, axis=0)
        m_cnt = jnp.sum(bstats[..., 1] * midf, axis=0)
        m_min = jnp.min(jnp.where(mid, bstats[..., 2], _POS_INF), axis=0)
        m_max = jnp.max(jnp.where(mid, bstats[..., 3], _NEG_INF), axis=0)
        m_sq = jnp.sum(bstats[..., 4] * midf, axis=0)

        out_ref[0, wi] = jnp.stack(
            [
                s_sum + m_sum,
                s_cnt + m_cnt,
                jnp.minimum(s_min, m_min),
                jnp.maximum(s_max, m_max),
                s_sq + m_sq,
            ],
            axis=-1,
        ).astype(out_ref.dtype)


def window_stats_pallas(
    ring_ts: jnp.ndarray,      # (K, C) int32
    ring_lanes: jnp.ndarray,   # (L, K, C) f32 (stored layout)
    bagg_stats: jnp.ndarray,   # (L, 5, K, NB) f32 (stored layout)
    bagg_bucket: jnp.ndarray,  # (K, NB) int32
    q_key: jnp.ndarray,        # (Q,) int32
    q_ts: jnp.ndarray,         # (Q,) int32
    q_lanes: jnp.ndarray,      # (Q, L) f32
    *,
    windows: Sequence[int],
    bucket_size: int,
    interpret: bool = False,
) -> jnp.ndarray:
    """Returns (Q, NW, L, 5)."""
    K, C = ring_ts.shape
    L = ring_lanes.shape[0]
    NB = bagg_bucket.shape[1]
    Q = q_key.shape[0]
    NW = len(windows)
    G = _KEY_GROUP
    pad = -K % G
    if pad:  # whole key groups per block (small test stores)
        ring_ts, ring_lanes, bagg_stats, bagg_bucket = (
            jnp.pad(x, [(0, 0)] * (x.ndim - 2) + [(0, pad), (0, 0)])
            for x in (ring_ts, ring_lanes, bagg_stats, bagg_bucket)
        )

    kernel = functools.partial(
        _window_agg_kernel, windows=tuple(windows), bucket_size=bucket_size
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(Q,),
        in_specs=[
            pl.BlockSpec((G, C), lambda i, qk, qt: (qk[i] // G, 0)),
            pl.BlockSpec((L, G, C), lambda i, qk, qt: (0, qk[i] // G, 0)),
            pl.BlockSpec(
                (L, 5, G, NB), lambda i, qk, qt: (0, 0, qk[i] // G, 0)
            ),
            pl.BlockSpec((G, NB), lambda i, qk, qt: (qk[i] // G, 0)),
            pl.BlockSpec((1, 1, L), lambda i, qk, qt: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, NW, L, 5), lambda i, qk, qt: (i, 0, 0, 0)
        ),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Q, NW, L, 5), jnp.float32),
        interpret=interpret,
    )(q_key, q_ts, ring_ts, ring_lanes, bagg_stats, bagg_bucket,
      q_lanes.reshape(Q, 1, L))


# ---------------------------------------------------------------------------
# Segmented-combine fold levels (offline scan hot loop)
# ---------------------------------------------------------------------------

_FOLD_LANE = 128  # TPU lane width; rows are stored flat as (R, 128) tiles


def _fold_ident(op: str, dtype):
    if op == "min":
        return jnp.asarray(_POS_INF, dtype)
    if op == "max":
        return jnp.asarray(_NEG_INF, dtype)
    return jnp.zeros((), dtype)


def _fold_combine(op: str):
    return {"min": jnp.minimum, "max": jnp.maximum, "or": jnp.bitwise_or}[op]


def _flat_shift(a: jnp.ndarray, d: int, fill) -> jnp.ndarray:
    """Shift a flat row-major (R, LANE) array right by ``d`` positions,
    filling with ``fill`` — static pads/slices/concats only (Mosaic-
    friendly; a gather here is what blew up the old XLA formulation)."""
    rows, lanes = a.shape
    rshift, lshift = divmod(d, lanes)
    if rshift:
        a = jnp.concatenate(
            [jnp.full((rshift, lanes), fill, a.dtype), a[: rows - rshift]],
            axis=0,
        )
    if lshift:
        carry = jnp.concatenate(
            [jnp.full((1, lanes), fill, a.dtype), a[:-1]], axis=0
        )
        a = jnp.concatenate(
            [carry[:, lanes - lshift:], a[:, : lanes - lshift]], axis=1
        )
    return a


def _fold_levels_kernel(
    x_ref, seg_ref, out_ref, cur_ref, src_ref, rsem, wsem,
    *, op: str, levels: int, tile_rows: int,
):
    """Grid-tiled doubling levels of the segmented combine.

    The row axis is tiled over the grid: grid step ``t`` owns flat rows
    ``[t*TR, (t+1)*TR)``.  Only the active tile is VMEM-resident — the
    (TR, 128) x/seg input blocks stream HBM→VMEM through the BlockSpec
    pipeline (double-buffered across steps), while the (levels, R, 128)
    output stays in HBM (``memory_space=ANY``) and is written one
    (TR, 128) tile per level by an explicit DMA.

    The inter-tile boundary combine rides the sequential TPU grid: level
    ``k`` of every earlier tile is already in the HBM output when step
    ``t`` runs, so the shifted source for distance ``2^k`` is fetched
    back from ``out[k]`` by a second DMA.  Three static cases per level
    (the shift distance is a python constant):

    * ``2^k < 128`` — a lane shift whose carry row is the last row of
      tile ``t-1``: one 1-row DMA;
    * ``128 <= 2^k < TR*128`` — an exact row shift by ``2^k/128`` rows
      straddling tiles ``t-1``/``t``: DMA the straddle rows, concat with
      the resident tile;
    * ``2^k >= TR*128`` — the source is exactly tile ``t - 2^k/(TR*128)``
      (both powers of two): DMA the whole tile.

    Every fetch is guarded by ``pl.when`` on the source tile existing;
    elements whose true source precedes the array (idx - 2^k < 0) are
    masked to the identity by the segment guard (seg >= 0 always), so
    skipped DMAs can never leak scratch garbage into a live value.
    """
    t = pl.program_id(0)
    TR = tile_rows
    ident = _fold_ident(op, x_ref.dtype)
    f = _fold_combine(op)
    seg = seg_ref[...]
    row = jax.lax.broadcasted_iota(jnp.int32, (TR, _FOLD_LANE), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (TR, _FOLD_LANE), 1)
    idx = (t * TR + row) * _FOLD_LANE + lane
    cur = x_ref[...]
    for k in range(levels):
        # publish level k of this tile; later steps read it back from HBM
        cur_ref[...] = cur
        put = pltpu.make_async_copy(
            cur_ref, out_ref.at[k, pl.ds(t * TR, TR)], wsem
        )
        put.start()
        put.wait()
        if k == levels - 1:
            break
        half = 1 << k
        if half < _FOLD_LANE:
            # lane shift; carry row = out[k] row t*TR - 1 (tile t-1)
            @pl.when(t > 0)
            def _fetch_carry():
                get = pltpu.make_async_copy(
                    out_ref.at[k, pl.ds(t * TR - 1, 1)],
                    src_ref.at[pl.ds(0, 1)],
                    rsem,
                )
                get.start()
                get.wait()

            prev = jnp.concatenate([src_ref[0:1], cur[:-1]], axis=0)
            shifted = jnp.concatenate(
                [prev[:, _FOLD_LANE - half:], cur[:, : _FOLD_LANE - half]],
                axis=1,
            )
        elif (rshift := half // _FOLD_LANE) < TR:
            # row shift straddling tile t-1: fetch its last rshift rows
            @pl.when(t > 0)
            def _fetch_straddle():
                get = pltpu.make_async_copy(
                    out_ref.at[k, pl.ds(t * TR - rshift, rshift)],
                    src_ref.at[pl.ds(0, rshift)],
                    rsem,
                )
                get.start()
                get.wait()

            shifted = jnp.concatenate(
                [src_ref[0:rshift], cur[: TR - rshift]], axis=0
            )
        else:
            # whole-tile shift: the source is exactly tile t - q
            q = rshift // TR

            @pl.when(t >= q)
            def _fetch_tile():
                get = pltpu.make_async_copy(
                    out_ref.at[k, pl.ds((t - q) * TR, TR)], src_ref, rsem
                )
                get.start()
                get.wait()

            shifted = src_ref[...]
        cur = f(cur, jnp.where(idx - half >= seg, shifted, ident))


def fold_levels_pallas(
    x2: jnp.ndarray,    # (R, 128) padded row-major values, R % tile_rows == 0
    seg2: jnp.ndarray,  # (R, 128) int32 padded segment starts
    *,
    op: str,
    levels: int,
    tile_rows: int,
    interpret: bool = False,
) -> jnp.ndarray:
    """Returns (levels, R, 128) doubling-fold levels (grid-tiled rows)."""
    R = x2.shape[0]
    if R % tile_rows or tile_rows % 8 or tile_rows & (tile_rows - 1):
        raise ValueError(
            f"fold tile_rows must be a pow2 multiple of 8 dividing R "
            f"(got tile_rows={tile_rows}, R={R})"
        )
    kernel = functools.partial(
        _fold_levels_kernel, op=op, levels=levels, tile_rows=tile_rows
    )
    return pl.pallas_call(
        kernel,
        grid=(R // tile_rows,),
        in_specs=[
            pl.BlockSpec((tile_rows, _FOLD_LANE), lambda t: (t, 0)),
            pl.BlockSpec((tile_rows, _FOLD_LANE), lambda t: (t, 0)),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct((levels, R, _FOLD_LANE), x2.dtype),
        scratch_shapes=[
            pltpu.VMEM((tile_rows, _FOLD_LANE), x2.dtype),
            pltpu.VMEM((tile_rows, _FOLD_LANE), x2.dtype),
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA,
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(x2, seg2)
