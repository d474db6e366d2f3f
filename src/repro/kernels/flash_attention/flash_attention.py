"""Flash attention for TPU (Pallas): causal / sliding-window, GQA-aware.

Block-wise online-softmax attention tiled for VMEM:

* grid = (batch, q_heads, num_q_blocks, num_kv_blocks); the last axis is
  sequential on TPU, so the running (max, denom, accum) state lives in VMEM
  scratch across kv steps of one q block.
* GQA without materializing broadcast KV: the kv BlockSpec's index_map
  folds the q-head -> kv-head mapping (h // group), so HBM holds KV once.
* Sliding-window masking skips fully-out-of-window kv blocks structurally
  (mask only; XLA grid is static) — the FLOPs still execute for skipped
  blocks in this static-grid formulation, which is the correct trade on
  TPU for moderate windows (dynamic grids cost more than masked MACs).
* MXU alignment: block_q and block_k default to 128; head_dim is padded to
  a multiple of 128 lanes by ops.py when needed.

Validated on CPU with interpret=True against ref.py (pure jnp).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention_kernel", "flash_attention_pallas"]

_NEG_INF = -1.0e30


def flash_attention_kernel(
    q_ref, k_ref, v_ref, o_ref,
    acc_ref, m_ref, l_ref,
    *,
    scale: float,
    causal: bool,
    window: Optional[int],
    block_q: int,
    block_k: int,
    seq_len: int,
):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32)  # (bq, d)
    k = k_ref[0, 0].astype(jnp.float32)  # (bk, d)
    v = v_ref[0, 0].astype(jnp.float32)  # (bk, d)

    # sanitize rows of partial kv blocks: OOB reads are undefined and would
    # otherwise poison p @ v through 0 * NaN
    kv_valid = (
        ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_k, 1), 0)
        < seq_len
    )
    v = jnp.where(kv_valid, v, 0.0)
    k = jnp.where(kv_valid, k, 0.0)

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # (bq, bk)

    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0
    )
    k_pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1
    )
    mask = k_pos < seq_len
    if causal:
        mask = mask & (k_pos <= q_pos)
    if window is not None:
        mask = mask & (k_pos > q_pos - window)
    s = jnp.where(mask, s, _NEG_INF)

    m_prev = m_ref[...]          # (bq,)
    m_cur = jnp.max(s, axis=-1)
    m_new = jnp.maximum(m_prev, m_cur)
    # guard fully-masked rows (all -inf): exp(-inf - -inf) -> use 0
    p = jnp.exp(s - m_new[:, None])
    p = jnp.where(mask, p, 0.0)
    alpha = jnp.where(
        m_prev == _NEG_INF, 0.0, jnp.exp(m_prev - m_new)
    )
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)[:, None]
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def flash_attention_pallas(
    q: jnp.ndarray,  # (B, H, S, D)
    k: jnp.ndarray,  # (B, Hkv, S, D)
    v: jnp.ndarray,  # (B, Hkv, S, D)
    *,
    causal: bool = True,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    B, H, S, D = q.shape
    Hkv = k.shape[1]
    assert H % Hkv == 0, (H, Hkv)
    group = H // Hkv
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    nq = pl.cdiv(S, block_q)
    nk = pl.cdiv(S, block_k)

    grid = (B, H, nq, nk)
    kernel = functools.partial(
        flash_attention_kernel,
        scale=scale,
        causal=causal,
        window=window,
        block_q=block_q,
        block_k=block_k,
        seq_len=S,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(
                (1, 1, block_q, D), lambda b, h, qi, ki: (b, h, qi, 0)
            ),
            pl.BlockSpec(
                (1, 1, block_k, D),
                lambda b, h, qi, ki: (b, h // group, ki, 0),
            ),
            pl.BlockSpec(
                (1, 1, block_k, D),
                lambda b, h, qi, ki: (b, h // group, ki, 0),
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, block_q, D), lambda b, h, qi, ki: (b, h, qi, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
