"""Bucketed flash-decode GQA attention Pallas kernel.

The serving engine's aggregated launch: B decode requests (each a
fine-grained task — one new token against its KV cache) are fused into one
kernel with a request axis, the serving-level instance of the paper's
strategy 3.  Online-softmax over KV tiles keeps VMEM usage at
``(G, D) + (bs, D)`` per step; tiles entirely beyond a request's
``cache_len`` skip their compute (so aggregated requests of different
lengths do not pay for the longest one — the ragged analogue of the paper's
"tasks share the kernel but own their chunk").

q: (B, Hq, D); k/v cache: (B, S, Hkv, D); cache_len: (B,).  Grid is
(B, Hkv, S/bs); each (b, h) pair owns a G=Hq/Hkv query group, carried
running max / denominator / accumulator live in VMEM scratch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import pallas_call

NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref,
                   m_ref, l_ref, acc_ref, *, bs: int, n_s: int, scale: float):
    si = pl.program_id(2)

    @pl.when(si == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    cache_len = len_ref[0]
    live = si * bs < cache_len

    @pl.when(live)
    def _tile():
        q = q_ref[0, 0].astype(jnp.float32)            # (G, D)
        k = k_ref[0, :, 0].astype(jnp.float32)         # (bs, D)
        v = v_ref[0, :, 0].astype(jnp.float32)         # (bs, D)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale  # (G, bs)
        pos = si * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < cache_len, s, NEG_INF)
        m_prev = m_ref[...]                            # (G, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                         # (G, bs)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(si == n_s - 1)
    def _store():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     cache_len: jax.Array, *, bs: int = 512) -> jax.Array:
    """(B, Hq, D) x (B, S, Hkv, D) caches -> (B, Hq, D)."""
    b, hq, d = q.shape
    s, hkv = k_cache.shape[1], k_cache.shape[2]
    g = hq // hkv
    bs = min(bs, s)
    assert s % bs == 0, (s, bs)
    n_s = s // bs
    scale = 1.0 / (d ** 0.5)
    qg = q.reshape(b, hkv, g, d)
    out = pallas_call(
        functools.partial(_decode_kernel, bs=bs, n_s=n_s, scale=scale),
        grid=(b, hkv, n_s),
        in_specs=[
            pl.BlockSpec((1,), lambda bi, hi, si: (bi,)),
            pl.BlockSpec((1, 1, g, d), lambda bi, hi, si: (bi, hi, 0, 0)),
            pl.BlockSpec((1, bs, 1, d), lambda bi, hi, si: (bi, si, hi, 0)),
            pl.BlockSpec((1, bs, 1, d), lambda bi, hi, si: (bi, si, hi, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, d), lambda bi, hi, si: (bi, hi, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, d), jnp.float32),
        ],
    )(cache_len, qg, k_cache, v_cache)
    return out.reshape(b, hq, d)
