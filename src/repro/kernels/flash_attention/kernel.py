"""Flash attention forward kernel (online softmax, GQA-aware).

Layout: q (B, H, Sq, hd), k/v (B, KV, Sk, hd) — head-major so each grid
step streams contiguous (block, hd) tiles into VMEM.  Grid is
(B, H, nq, nk) with nk innermost: TPU grids execute sequentially, so the
fp32 VMEM scratch (acc, m, l) carries the online-softmax state across kv
blocks of one q block; the final kv step writes acc/l to the output tile.

Causality is handled at two levels: whole (iq, ik) tiles with no unmasked
entry are skipped with pl.when (the MXU never sees them — triangular work),
and the diagonal tile applies an iota mask.  A sliding window adds the
symmetric lower bound.  The m/l statistics live in (block_q, 128) VMEM
tiles (lane-replicated) to stay layout-friendly on the VPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_STAT_LANES = 128


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            scale: float, causal: bool, window: int,
            block_q: int, block_k: int, nk: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = iq * block_q
    k_start = ik * block_k
    run = jnp.bool_(True)
    if causal:
        run &= k_start < q_start + block_q
    if window:
        run &= (k_start + block_k) > (q_start - window + 1)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale            # (bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)                    # (bk, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (bq, bk)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = jnp.ones((block_q, block_k), jnp.bool_)
        if causal:
            mask &= qpos >= kpos
        if window:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, :1]                                  # (bq, 1)
        l_prev = l_ref[:, :1]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)                                 # (bq, bk)
        corr = jnp.exp(m_prev - m_new)                         # (bq, 1)
        l_new = l_prev * corr + p.sum(axis=-1, keepdims=True)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * corr + pv
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ik == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "block_q", "block_k", "interpret"))
def flash_attention_kernel(q, k, v, *, causal=True, window=0,
                           block_q=512, block_k=512, interpret=False):
    """q: (B, H, Sq, hd); k/v: (B, KV, Sk, hd) -> (B, H, Sq, hd)."""
    B, H, Sq, hd = q.shape
    KV, Sk = k.shape[1], k.shape[2]
    G = H // KV
    bq, bk = min(block_q, Sq), min(block_k, Sk)
    assert Sq % bq == 0 and Sk % bk == 0, (q.shape, k.shape, bq, bk)
    nq, nk = Sq // bq, Sk // bk
    scale = 1.0 / (hd ** 0.5)

    kernel = functools.partial(_kernel, scale=scale, causal=causal,
                               window=window, block_q=bq, block_k=bk, nk=nk)
    return pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, iq, ik: (b, h // G, ik, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, iq, ik: (b, h // G, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd), lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, hd), jnp.float32),
            pltpu.VMEM((bq, _STAT_LANES), jnp.float32),
            pltpu.VMEM((bq, _STAT_LANES), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
