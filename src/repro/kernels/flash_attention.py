"""Pallas kernel: blocked flash attention (the LM stack's compute hot-spot).

Canonical two-dimensional grid — ``(q_blocks, k_blocks)`` per (batch, head) —
with VMEM scratch carrying the online-softmax state (running max m, denominator
l, and the output accumulator).  K/V blocks stream through VMEM via BlockSpec;
causal blocks strictly above the diagonal are predicated off with ``pl.when``.
MXU-aligned when block_q/block_k are multiples of 128 and head_dim ∈ {64,128}.

Numerics: fp32 accumulation regardless of input dtype; masked logits use a
finite -1e30 and masked probabilities are zeroed explicitly so fully-masked
blocks cannot pollute the denominator.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  sm_scale: float, causal: bool, block_q: int, block_k: int):
    qi = pl.program_id(0)
    kj = pl.program_id(1)
    nk = pl.num_programs(1)

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # causal: skip blocks entirely above the diagonal
    run = (kj * block_k <= qi * block_q + block_q - 1) if causal else (kj >= 0)

    @pl.when(run)
    def _compute():
        q = q_ref[...].astype(jnp.float32)
        k = k_ref[...].astype(jnp.float32)
        v = v_ref[...].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale      # (bq, bk)
        if causal:
            qpos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            kpos = kj * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            mask = qpos >= kpos
            s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_cur = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.exp(s - m_cur[:, None])
        if causal:
            p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_cur)
        l_scr[...] = l_prev * alpha + p.sum(axis=-1)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_scr[...] = m_cur

    @pl.when(kj == nk - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[...] = (acc_scr[...] / denom[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "block_q", "block_k", "interpret"))
def _flash_single(q, k, v, *, causal: bool, block_q: int, block_k: int,
                  interpret: bool):
    """q (sq, d), k/v (sk, d) → (sq, d)."""
    sq, d = q.shape
    sk = k.shape[0]
    assert sq % block_q == 0 and sk % block_k == 0, (sq, sk, block_q, block_k)
    sm_scale = 1.0 / (d ** 0.5)
    grid = (sq // block_q, sk // block_k)
    return pl.pallas_call(
        functools.partial(_flash_kernel, sm_scale=sm_scale, causal=causal,
                          block_q=block_q, block_k=block_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_q, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_k, d), lambda i, j: (j, 0)),
            pl.BlockSpec((block_k, d), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block_q, d), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)


def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 128,
                    block_k: int = 128, interpret: bool):
    """Batched GQA flash attention.

    q: (batch, q_heads, sq, d); k, v: (batch, kv_heads, sk, d) with
    q_heads % kv_heads == 0.  Returns (batch, q_heads, sq, d).
    """
    b, hq, sq, d = q.shape
    hkv = k.shape[1]
    assert hq % hkv == 0
    group = hq // hkv
    qg = q.reshape(b, hkv, group, sq, d)
    fn = functools.partial(_flash_single, causal=causal, block_q=block_q,
                           block_k=block_k, interpret=interpret)
    # vmap over batch, kv-head, and query-group
    out = jax.vmap(jax.vmap(jax.vmap(fn, in_axes=(0, None, None)),
                            in_axes=(0, 0, 0)),
                   in_axes=(0, 0, 0))(qg, k, v)
    return out.reshape(b, hq, sq, d)
