"""Pallas TPU flash attention (online softmax), GQA + window + softcap.

Grid: (batch*kv_head_group, q_blocks, kv_blocks) with the kv dimension
'arbitrary' (sequential) so the online-softmax accumulators live in VMEM
scratch across kv steps.  Block sizes default to (512 q x 512 kv) —
with D=128 and f32 accumulation that is

    q tile 512*128*4 = 256 KB, k/v tiles 2*256 KB, acc 256 KB,
    m/l 2*2 KB  ->  ~1 MB of VMEM, leaving headroom for double buffering.

Causal + sliding-window masking is applied per (q_blk, kv_blk) tile.
Fully-masked tiles reduce to a no-op through the mask; the causal-skip
optimization (shrinking the kv loop per q block) is a §Perf hillclimb
item and is controlled by ``block_triangular``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  block_q: int, block_k: int, seq_len: int, causal: bool,
                  window: int, softcap: float, n_kv_blocks: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)          # (block_q, d)
    k = k_ref[0].astype(jnp.float32)          # (block_k, d)
    v = v_ref[0].astype(jnp.float32)
    d = q.shape[-1]

    logits = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
    logits = logits / jnp.sqrt(jnp.float32(d))
    if softcap > 0:
        logits = softcap * jnp.tanh(logits / softcap)

    qpos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_k), 0)
    kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_k), 1)
    mask = kpos < seq_len
    if causal:
        mask &= kpos <= qpos
    if window > 0:
        mask &= kpos > qpos - window
    logits = jnp.where(mask, logits, NEG_INF)

    m_prev = m_scr[...]                        # (block_q, 1)
    l_prev = l_scr[...]
    m_cur = jnp.max(logits, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(logits - m_new)                # (block_q, block_k)
    alpha = jnp.exp(m_prev - m_new)
    l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(ki == n_kv_blocks - 1)
    def _finalize():
        l = l_scr[...]
        safe = jnp.where(l > 0, l, 1.0)
        o_ref[0] = (acc_scr[...] / safe).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "softcap", "block_q", "block_k",
                     "interpret"))
def flash_attention_pallas(q, k, v, *, causal=True, window=0, softcap=0.0,
                           block_q=512, block_k=512, interpret=False):
    """q/k/v: [BH, S, D] (GQA head-groups pre-folded by ops.py)."""
    bh, s, d = q.shape
    n_q = s // block_q
    n_k = s // block_k
    grid = (bh, n_q, n_k)
    kernel = functools.partial(
        _flash_kernel, block_q=block_q, block_k=block_k, seq_len=s,
        causal=causal, window=window, softcap=softcap, n_kv_blocks=n_k)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # running max m
            pltpu.VMEM((block_q, 1), jnp.float32),   # running denom l
            pltpu.VMEM((block_q, d), jnp.float32),   # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
