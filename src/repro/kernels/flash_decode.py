"""Pallas TPU kernel: single-token GQA decode attention (flash-style).

Decode attends ONE query token against a long KV cache — the hot loop of the
``decode_32k`` / ``long_500k`` serving shapes. Memory-bound: the roofline is
set by streaming K/V once through VMEM; the kernel therefore tiles the cache
sequence dimension and keeps the online-softmax state (m, l, acc) in VMEM
scratch across sequence blocks.

Layout: one grid row per KV head (GQA groups share a cache head), sequence
blocked by ``SEQ_BLOCK``. q is pre-grouped to [Hkv, G, D]; each step does two
MXU matmuls: logits = q_g @ k_blk^T  [G, SB]  and  acc += p @ v_blk  [G, D].
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .autotune import interpret_mode

SEQ_BLOCK = 512
LANES = 128


def _kernel(len_ref, q_ref, k_ref, v_ref, out_ref, acc_ref, m_ref, l_ref):
    sb = pl.program_id(1)
    num_sb = pl.num_programs(1)

    @pl.when(sb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0]                                  # [G, D] (pre-scaled)
    k = k_ref[0]                                  # [SB, D]
    v = v_ref[0]                                  # [SB, D]
    length = len_ref[0]
    sblk = k.shape[0]
    pos = sb * sblk + jax.lax.broadcasted_iota(jnp.int32, (1, sblk), 1)
    valid = pos < length                          # [1, SB]

    logits = jax.lax.dot_general(
        q.astype(jnp.float32), k.astype(jnp.float32),
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)       # [G, SB]
    logits = jnp.where(valid, logits, -1e30)

    m_prev = m_ref[:, :1]                         # [G, 1]
    m_cur = jnp.max(logits, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(logits - m_new)                   # [G, SB]
    p = jnp.where(valid, p, 0.0)
    alpha = jnp.exp(m_prev - m_new)               # [G, 1]
    l_new = alpha * l_ref[:, :1] + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
        p, v.astype(jnp.float32), preferred_element_type=jnp.float32)
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(sb == num_sb - 1)
    def _finish():
        out_ref[0] = acc_ref[...] / jnp.maximum(l_ref[:, :1], 1e-30)


@jax.jit
def flash_decode_pallas(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                        length: jnp.ndarray) -> jnp.ndarray:
    """q: [H, D]; k, v: [S, Hkv, D]; length: scalar. Returns [H, D] f32->q.dtype.

    Matches :func:`repro.kernels.ref.flash_decode_ref` (scale 1/sqrt(D))."""
    hq, d = q.shape
    s, hkv, _ = k.shape
    g = hq // hkv
    assert g * hkv == hq, (hq, hkv)
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32))
    qg = (q.astype(jnp.float32) * scale).reshape(hkv, g, d)
    # pad seq to SEQ_BLOCK; padded positions are masked via `length`
    s_pad = ((s + SEQ_BLOCK - 1) // SEQ_BLOCK) * SEQ_BLOCK
    kt = jnp.pad(jnp.moveaxis(k, 1, 0), ((0, 0), (0, s_pad - s), (0, 0)))
    vt = jnp.pad(jnp.moveaxis(v, 1, 0), ((0, 0), (0, s_pad - s), (0, 0)))
    length = jnp.asarray(length, jnp.int32).reshape(1)

    grid = (hkv, s_pad // SEQ_BLOCK)
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, g, d), lambda h, sb: (h, 0, 0)),
            pl.BlockSpec((1, SEQ_BLOCK, d), lambda h, sb: (h, sb, 0)),
            pl.BlockSpec((1, SEQ_BLOCK, d), lambda h, sb: (h, sb, 0)),
        ],
        out_specs=pl.BlockSpec((1, g, d), lambda h, sb: (h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((hkv, g, d), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((g, d), jnp.float32),
            pltpu.VMEM((g, LANES), jnp.float32),
            pltpu.VMEM((g, LANES), jnp.float32),
        ],
        interpret=interpret_mode(),
        name="flash_decode",
    )(length, qg, kt, vt)
    return out.reshape(hq, d).astype(q.dtype)
