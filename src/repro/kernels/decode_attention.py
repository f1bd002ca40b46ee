"""Pallas TPU decode attention: ONE query against a long KV cache.

Flash-decoding-style layout: grid = (batch, q_heads, kv_blocks); the kv axis
is sequential with VMEM scratch carrying the online-softmax state — the
memory-bound inner loop streams [BK, D] cache tiles through VMEM exactly
once (this op IS the §Roofline memory term for every decode shape). GQA via
the q-head -> kv-head index map; positions >= `pos` (the valid length) are
masked via the block index so trailing cache garbage never contributes.

Validated in interpret mode against ref.decode_attention_ref.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0**30


def _decode_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
                   acc_scr, *, bk: int, nk: int, scale: float):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    pos = pos_ref[0]
    k_start = ki * bk

    @pl.when(k_start < pos)  # skip blocks entirely past the valid length
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)          # [1, D]
        k = k_ref[0].astype(jnp.float32)             # [BK, D]
        v = v_ref[0].astype(jnp.float32)             # [BK, D]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        s = jnp.where(kpos < pos, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=1)
        acc_scr[...] = acc_scr[...] * corr[:, None] + jax.lax.dot(p, v)
        m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-20)[:, None]
        o_ref[0, 0] = (acc_scr[...] / denom).astype(o_ref.dtype)


def decode_attention(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, pos, *,
    block_k: int = 512, interpret: bool | None = None,
) -> jnp.ndarray:
    """q [B,Hq,1,D]; k/v [B,Skv,Hkv,D]; pos: valid cache length (scalar).

    Returns [B,Hq,1,D]."""
    b, hq, _, d = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    bk = min(block_k, skv)
    if skv % bk:
        raise ValueError(f"cache len {skv} must divide block_k {bk}")
    nk = skv // bk
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    kt = jnp.swapaxes(k, 1, 2).reshape(b * hkv, skv, d)
    vt = jnp.swapaxes(v, 1, 2).reshape(b * hkv, skv, d)
    pos_arr = jnp.asarray([pos], jnp.int32)

    kernel = functools.partial(_decode_kernel, bk=bk, nk=nk,
                               scale=1.0 / np.sqrt(d))
    out = pl.pallas_call(
        kernel,
        grid=(b, hq, nk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, 1, d), lambda bi, h, ki: (bi, h, 0, 0)),
            pl.BlockSpec((1, bk, d),
                         lambda bi, h, ki: (bi * hkv + h // g, ki, 0)),
            pl.BlockSpec((1, bk, d),
                         lambda bi, h, ki: (bi * hkv + h // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, d), lambda bi, h, ki: (bi, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, 1, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((1,), jnp.float32),
            pltpu.VMEM((1,), jnp.float32),
            pltpu.VMEM((1, d), jnp.float32),
        ],
        interpret=interpret,
    )(pos_arr, q, kt, vt)
    return out
