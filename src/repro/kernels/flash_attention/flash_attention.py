"""Flash (online-softmax) attention Pallas kernel — TPU target.

Not part of the Stark paper, but required substrate: the prefill_32k and
long-context shape cells are only lowerable if attention never
materializes the (Sq, Sk) score matrix. This kernel tiles Q into (bq, D)
VMEM blocks and streams K/V in (bk, D) blocks with the standard
running-max/running-denominator update; the accumulator never leaves VMEM.

Supports MHA/GQA/MQA (kv-head broadcast via the BlockSpec index map — no
materialized repeat), causal masking, and a sliding local window (for
recurrentgemma-style local attention).

Grid: (B, Hq, Sq/bq, Sk/bk), Sk innermost so the softmax state lives in
scratch across the KV sweep.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import default_interpret, pick_block

__all__ = ["flash_attention_pallas"]

_NEG_INF = -1e30


def _flash_kernel(
    causal: bool,
    window: Optional[int],
    scale: float,
    block_q: int,
    block_k: int,
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    acc_ref,
    m_ref,
    l_ref,
):
    iq, ik = pl.program_id(2), pl.program_id(3)
    q_off = iq * block_q
    k_off = ik * block_k

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def _step():
        q = q_ref[0, 0].astype(jnp.float32)  # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)  # (bk, D)
        v = v_ref[0, 0].astype(jnp.float32)  # (bk, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (bq, bk)

        rows = q_off + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        cols = k_off + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = jnp.ones((block_q, block_k), dtype=jnp.bool_)
        if causal:
            mask = jnp.logical_and(mask, rows >= cols)
        if window is not None:
            mask = jnp.logical_and(mask, rows - cols < window)
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_ref[...]  # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)  # (bq, bk)
        alpha = jnp.exp(m_prev - m_new)  # (bq, 1)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    # Block-level skip: fully-masked KV blocks do no work (the Pallas
    # analogue of flash attention's causal block pruning). A block is live
    # iff [k_off, k_off+bk) intersects union_rows (row-window, row] —
    # i.e. k_off <= q_off+bq-1 (causal) and k_off+bk-1 > q_off-window.
    if causal or window is not None:
        live = jnp.bool_(True)
        if causal:
            live = jnp.logical_and(live, k_off <= q_off + block_q - 1)
        if window is not None:
            live = jnp.logical_and(live, k_off + block_k - 1 > q_off - window)
        pl.when(live)(_step)
    else:
        _step()

    @pl.when(ik == pl.num_programs(3) - 1)
    def _flush():
        l = l_ref[...]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)


def flash_attention_pallas(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    block_q: int = 512,
    block_k: int = 512,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """O = softmax(QK^T * scale + mask) V, never materializing (Sq, Sk).

    Args:
      q: (B, Hq, Sq, D). k, v: (B, Hkv, Sk, D) with Hq % Hkv == 0.
      causal: apply causal mask (rows >= cols), offset so the LAST query
        aligns with the last key (standard decode/prefill convention when
        Sq == Sk; for Sq != Sk pass explicit full seqs).
      window: optional sliding window size (keys within [row-window+1, row]).
    """
    if window is not None and not causal:
        raise ValueError("sliding window requires causal=True (backward window)")
    if interpret is None:
        interpret = default_interpret()
    b, hq, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    assert hq % hkv == 0, (hq, hkv)
    group = hq // hkv
    scale = scale if scale is not None else d**-0.5
    bq = pick_block(sq, block_q, interpret=interpret)
    bk = pick_block(sk, block_k, interpret=interpret)

    kernel = functools.partial(_flash_kernel, causal, window, scale, bq, bk)
    return pl.pallas_call(
        kernel,
        grid=(b, hq, sq // bq, sk // bk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda bb, h, iq, ik: (bb, h, iq, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda bb, h, iq, ik: (bb, h // group, ik, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda bb, h, iq, ik: (bb, h // group, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d), lambda bb, h, iq, ik: (bb, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
