"""Pallas TPU flash attention: fwd + bwd, GQA, causal, sliding window.

Tiling: grid (B, H, nq, nk) — the kv axis is the *last* (sequential on TPU)
grid dimension, so the online-softmax running state (m, l, acc) lives in VMEM
scratch and persists across kv steps. Block shapes are (block_q × head_dim)
and (block_k × head_dim) VMEM tiles, MXU-aligned (multiples of 128 on the
contracting/lane dims; head_dim up to 256 supported).

Causal/SWA masking is two-level: kv blocks entirely outside the visible
range are skipped with ``pl.when`` (no MXU work); partially-visible blocks
apply an element mask. The backward pass runs two kernels: dq (grid over kv
last) and dkv (grid over q last), both recomputing probabilities from the
saved per-row LSE, exactly like FlashAttention-2.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30


def _visible(causal, window, q0, k0, bq, bk):
    """Block-level visibility for (q0..q0+bq) × (k0..k0+bk)."""
    full_after = (k0 + bk - 1 <= q0) if causal else True
    any_vis = (k0 <= q0 + bq - 1) if causal else True
    if window:
        any_vis = jnp.logical_and(any_vis, k0 + bk - 1 > q0 - window)
        full_after = jnp.logical_and(full_after, k0 >= q0 + bq - window)
    return any_vis, full_after


def _element_mask(causal, window, q0, k0, bq, bk):
    qi = q0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    ki = k0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    m = jnp.ones((bq, bk), jnp.bool_)
    if causal:
        m &= ki <= qi
    if window:
        m &= ki > qi - window
    return m


# ----------------------------------------------------------------------
# forward
# ----------------------------------------------------------------------
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr, *,
                causal, window, scale, block_q, block_k, nk):
    qb, kb = pl.program_id(2), pl.program_id(3)
    q0 = qb * block_q
    k0 = kb * block_k

    @pl.when(kb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    any_vis, _ = _visible(causal, window, q0, k0, block_q, block_k)

    @pl.when(any_vis)
    def _compute():
        q = q_ref[0, 0]                      # (bq, D)
        k = k_ref[0, 0]                      # (bk, D)
        v = v_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (bq, bk)
        mask = _element_mask(causal, window, q0, k0, block_q, block_k)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev - m_new)
        # guard fully-masked rows: NEG_INF - NEG_INF would exp() to 1
        p = jnp.where(mask, jnp.exp(s - m_new[:, None]), 0.0)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1)
        m_scr[...] = m_new
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + pv

    @pl.when(kb == nk - 1)
    def _finalize():
        l = l_scr[...]
        safe = jnp.where(l > 0, l, 1.0)
        o_ref[0, 0] = (acc_scr[...] / safe[:, None]).astype(o_ref.dtype)
        lse_ref[0, 0] = m_scr[...] + jnp.log(safe)


def _fwd(q, k, v, *, causal, window, scale, block_q, block_k, interpret):
    B, Sq, H, D = q.shape
    _, Sk, KV, _ = k.shape
    G = H // KV
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    nq = pl.cdiv(Sq, block_q)
    nk = pl.cdiv(Sk, block_k)

    # layout: (B, H, S, D) blocks per (batch, head)
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    kernel = functools.partial(
        _fwd_kernel, causal=causal, window=window, scale=scale,
        block_q=block_q, block_k=block_k, nk=nk)
    out, lse = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, i, j, G=G: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, i, j, G=G: (b, h // G, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, h, i, j: (b, h, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, Sq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu_scratch((block_q,), jnp.float32),
            pltpu_scratch((block_q,), jnp.float32),
            pltpu_scratch((block_q, D), jnp.float32),
        ],
        interpret=interpret,
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3), lse


def pltpu_scratch(shape, dtype):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.VMEM(shape, dtype)


# ----------------------------------------------------------------------
# backward: dq kernel (kv sequential), dkv kernel (q sequential)
# ----------------------------------------------------------------------
def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               acc_scr, *, causal, window, scale, block_q, block_k, nk):
    kb = pl.program_id(3)
    q0 = pl.program_id(2) * block_q
    k0 = kb * block_k

    @pl.when(kb == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    any_vis, _ = _visible(causal, window, q0, k0, block_q, block_k)

    @pl.when(any_vis)
    def _compute():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        mask = _element_mask(causal, window, q0, k0, block_q, block_k)
        s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lse[:, None])
        dp = jax.lax.dot_general(do, v.astype(jnp.float32),
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * scale
        acc_scr[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(kb == nk - 1)
    def _fin():
        dq_ref[0, 0] = acc_scr[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_scr, dv_scr, *,
                causal, window, scale, block_q, block_k, nq):
    qb = pl.program_id(3)
    q0 = qb * block_q
    k0 = pl.program_id(2) * block_k

    @pl.when(qb == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    any_vis, _ = _visible(causal, window, q0, k0, block_q, block_k)

    @pl.when(any_vis)
    def _compute():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        mask = _element_mask(causal, window, q0, k0, block_q, block_k)
        s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lse[:, None])                      # (bq, bk)
        dv_scr[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v.astype(jnp.float32),
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * scale             # (bq, bk)
        dk_scr[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(qb == nq - 1)
    def _fin():
        dk_ref[0, 0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


def _bwd(res, g, *, causal, window, scale, block_q, block_k, interpret):
    q, k, v, out, lse = res
    do = g
    B, Sq, H, D = q.shape
    _, Sk, KV, _ = k.shape
    G = H // KV
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    nq, nk = pl.cdiv(Sq, block_q), pl.cdiv(Sk, block_k)

    qt, kt, vt = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    dot = do.transpose(0, 2, 1, 3)
    delta = jnp.sum(dot.astype(jnp.float32)
                    * out.transpose(0, 2, 1, 3).astype(jnp.float32), axis=-1)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, causal=causal, window=window,
                          scale=scale, block_q=block_q, block_k=block_k,
                          nk=nk),
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, i, j, G=G: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, i, j, G=G: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, h, i, j: (b, h, i)),
            pl.BlockSpec((1, 1, block_q), lambda b, h, i, j: (b, h, i)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D),
                               lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
        scratch_shapes=[pltpu_scratch((block_q, D), jnp.float32)],
        interpret=interpret,
    )(qt, kt, vt, dot, lse, delta)

    dkg, dvg = pl.pallas_call(
        functools.partial(_dkv_kernel, causal=causal, window=window,
                          scale=scale, block_q=block_q, block_k=block_k,
                          nq=nq),
        grid=(B, H, nk, nq),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, j, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, j, i, G=G: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, j, i, G=G: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, j, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, h, j, i: (b, h, i)),
            pl.BlockSpec((1, 1, block_q), lambda b, h, j, i: (b, h, i)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, j, i: (b, h, j, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, j, i: (b, h, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Sk, D), jnp.float32),
            jax.ShapeDtypeStruct((B, H, Sk, D), jnp.float32),
        ],
        scratch_shapes=[pltpu_scratch((block_k, D), jnp.float32),
                        pltpu_scratch((block_k, D), jnp.float32)],
        interpret=interpret,
    )(qt, kt, vt, dot, lse, delta)

    # reduce per-q-head grads to kv heads (GQA)
    dk = dkg.reshape(B, KV, G, Sk, D).sum(axis=2).transpose(0, 2, 1, 3)
    dv = dvg.reshape(B, KV, G, Sk, D).sum(axis=2).transpose(0, 2, 1, 3)
    return (dq.transpose(0, 2, 1, 3), dk.astype(k.dtype), dv.astype(v.dtype))


# ----------------------------------------------------------------------
@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q, k, v, causal, window, scale, block_q, block_k, interpret):
    out, _ = _fwd(q, k, v, causal=causal, window=window, scale=scale,
                  block_q=block_q, block_k=block_k, interpret=interpret)
    return out


def _flash_fwd(q, k, v, causal, window, scale, block_q, block_k, interpret):
    out, lse = _fwd(q, k, v, causal=causal, window=window, scale=scale,
                    block_q=block_q, block_k=block_k, interpret=interpret)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, window, scale, block_q, block_k, interpret, res, g):
    return _bwd(res, g, causal=causal, window=window, scale=scale,
                block_q=block_q, block_k=block_k, interpret=interpret)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, *, causal=True, window=0, q_offset=0,
                    kv_valid_len=None, scale=None,
                    block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                    interpret=False):
    """Public entry. q (B,Sq,H,D); k/v (B,Skv,KV,D). q_offset/kv_valid_len
    are not supported in the kernel path (full-sequence train/prefill only)."""
    assert kv_valid_len is None and (isinstance(q_offset, int)
                                     and q_offset == 0), \
        "kernel path covers full-sequence train/prefill"
    D = q.shape[-1]
    scale = scale if scale is not None else D ** -0.5
    return _flash(q, k, v, causal, window, scale, block_q, block_k, interpret)
