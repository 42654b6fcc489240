"""Shared transformer building blocks (pure functions over param dicts).

Conventions
-----------
* Params are nested dicts of ``jnp`` arrays; per-layer params are stacked
  on a leading ``L`` axis and consumed by ``lax.scan`` (one compiled layer
  body regardless of depth — the compile-time and HBM win every
  production JAX trainer uses).
* Activations flow as ``[B, S, D]`` in ``cfg.dtype``; attention logits
  and softmax always f32.
* Three attention implementations:
    - 'ref'     : materializes [B,H,S,S] logits (oracle; smoke tests)
    - 'chunked' : pure-JAX online softmax over (q-chunk, kv-chunk) tiles —
                  flash-attention memory behaviour, lowers on any backend
                  (what the dry-run compiles)
    - 'flash'   : the Pallas kernel (TPU runtime path)
  All three are numerically cross-checked in tests.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

NEG_INF = -2.3819763e38  # large negative for masking in f32


def rms_norm(x, scale, eps: float = 1e-6):
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * (1.0 + scale.astype(jnp.float32))
    return out.astype(dt)


def rope(x, positions, theta: float = 10000.0):
    """x: [..., S, H, Dh]; positions [..., S] (broadcastable)."""
    dh = x.shape[-1]
    half = dh // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., :, None, None].astype(jnp.float32) * freq  # [...,S,1,half]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def _softcap(logits, cap: float):
    if cap and cap > 0:
        return cap * jnp.tanh(logits / cap)
    return logits


# ---------------------------------------------------------------------------
# Attention implementations
# ---------------------------------------------------------------------------

def _mask(qpos, kpos, causal: bool, window, prefix: int = 0):
    """qpos [*,Sq], kpos [*,Sk] -> bool [*,Sq,Sk]. window may be traced
    (0 = unlimited) so gemma2/hymba local-global alternation survives
    lax.scan over layers.  prefix > 0 opens a bidirectional zone over the
    first ``prefix`` positions (prefix-LM, paligemma-style)."""
    q = qpos[..., :, None]
    k = kpos[..., None, :]
    m = jnp.ones(jnp.broadcast_shapes(q.shape, k.shape), bool)
    if causal:
        c = k <= q
        if prefix:
            c |= (q < prefix) & (k < prefix)
        m &= c
    w = jnp.asarray(window)
    m &= (w <= 0) | (k > q - w)
    return m


def attn_ref(q, k, v, qpos, kpos, causal=True, window=0, softcap=0.0,
             prefix: int = 0):
    """q [B,Sq,Hq,Dh]; k/v [B,Sk,Hkv,Dh] -> [B,Sq,Hq,Dh]. Oracle."""
    b, sq, hq, dh = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    qf = q.astype(jnp.float32) * (dh ** -0.5)
    qf = qf.reshape(b, sq, hkv, g, dh)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qf, k.astype(jnp.float32))
    logits = _softcap(logits, softcap)
    m = _mask(qpos, kpos, causal, window, prefix)    # [B?,Sq,Sk] or [Sq,Sk]
    while m.ndim < logits.ndim:
        m = m[..., None, :, :] if m.ndim >= 3 else m[None]
    logits = jnp.where(m, logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p, v.astype(jnp.float32))
    return out.reshape(b, sq, hq, dh).astype(q.dtype)


def _pick_chunk(s: int, c: int) -> int:
    """Largest divisor of s that is <= c (whisper's 1500-frame encoder
    and other non-power-of-two sequences need a non-1024 tile)."""
    c = min(c, s)
    while s % c:
        c -= 1
    return c


def attn_chunked(q, k, v, qpos, kpos, causal=True, window=0, softcap=0.0,
                 chunk_q: int = 1024, chunk_k: int = 1024, prefix: int = 0):
    """Flash-style online softmax in pure JAX (scan over kv chunks inside
    scan over q chunks).  Peak live logits: [B,Hkv,G,cq,ck]."""
    b, sq, hq, dh = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    cq = _pick_chunk(sq, chunk_q)
    ck = _pick_chunk(k.shape[1], chunk_k)
    nq, nk = sq // cq, k.shape[1] // ck

    # keep q/k/v in compute dtype (bf16 on TPU); logits/softmax accumulate
    # in f32 via preferred_element_type — the MXU-native mixed precision
    qf = (q * jnp.asarray(dh ** -0.5, q.dtype)).reshape(b, nq, cq, hkv, g, dh)
    qf = qf.transpose(1, 0, 3, 4, 2, 5)              # [nq,B,Hkv,G,cq,dh]
    kf = k.reshape(b, nk, ck, hkv, dh).transpose(1, 0, 3, 2, 4)
    vf = v.reshape(b, nk, ck, hkv, dh).transpose(1, 0, 3, 2, 4)
    qp = qpos.reshape(nq, cq) if qpos.ndim == 1 else qpos.reshape(b, nq, cq)
    kp = kpos.reshape(nk, ck) if kpos.ndim == 1 else kpos.reshape(b, nk, ck)

    def q_step(_, qblk):
        qi, qc = qblk                                 # [B,Hkv,G,cq,dh]
        qpb = qp[qi] if qp.ndim == 2 else qp[:, qi]   # [cq] or [B,cq]

        @jax.checkpoint
        def kv_step(carry, kblk):
            m_p, l_p, acc = carry
            ki, kc, vc = kblk
            kpb = kp[ki] if kp.ndim == 2 else kp[:, ki]
            logits = jnp.einsum("bhgqd,bhkd->bhgqk", qc, kc,
                                preferred_element_type=jnp.float32)
            logits = _softcap(logits, softcap)
            msk = _mask(qpb, kpb, causal, window, prefix)
            while msk.ndim < logits.ndim:
                msk = msk[..., None, :, :] if msk.ndim >= 3 else msk[None]
            logits = jnp.where(msk, logits, NEG_INF)
            m_c = jnp.max(logits, axis=-1, keepdims=True)
            m_n = jnp.maximum(m_p, m_c)
            p = jnp.exp(logits - m_n)
            alpha = jnp.exp(m_p - m_n)
            l_n = alpha * l_p + jnp.sum(p, axis=-1, keepdims=True)
            acc = acc * alpha + jnp.einsum(
                "bhgqk,bhkd->bhgqd", p.astype(vc.dtype), vc,
                preferred_element_type=jnp.float32)
            return (m_n, l_n, acc), None

        m0 = jnp.full((b, hkv, g, cq, 1), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, hkv, g, cq, 1), jnp.float32)
        a0 = jnp.zeros((b, hkv, g, cq, dh), jnp.float32)
        (m_f, l_f, acc), _ = lax.scan(
            kv_step, (m0, l0, a0),
            (jnp.arange(nk), kf, vf))
        out = acc / jnp.where(l_f > 0, l_f, 1.0)
        return None, out

    _, outs = lax.scan(jax.checkpoint(q_step), None, (jnp.arange(nq), qf))
    # outs [nq,B,Hkv,G,cq,dh] -> [B,S,Hq,dh]
    out = outs.transpose(1, 0, 4, 2, 3, 5).reshape(b, sq, hq, dh)
    return out.astype(q.dtype)


def _online_block(q, k, v, qpos, kpos, state, causal, window, softcap,
                  prefix=0, chunk_k: int = 512):
    """Online-softmax update of (m, l, acc) against one kv block.
    q [B,Hkv,G,Sq,Dh]; k/v [B,Sk,Hkv,Dh]; state tensors [B,Hkv,G,Sq,*]."""
    b, sk, hkv, dh = k.shape
    ck = _pick_chunk(sk, chunk_k)
    nk = sk // ck
    kc = k.reshape(b, nk, ck, hkv, dh).transpose(1, 0, 3, 2, 4)
    vc = v.reshape(b, nk, ck, hkv, dh).transpose(1, 0, 3, 2, 4)
    kpc = kpos.reshape(nk, ck)

    @jax.checkpoint
    def kv_step(carry, xs):
        m_p, l_p, acc = carry
        kb, vb, kpb = xs
        logits = jnp.einsum("bhgqd,bhkd->bhgqk", q, kb,
                            preferred_element_type=jnp.float32)
        logits = _softcap(logits, softcap)
        msk = _mask(qpos, kpb, causal, window, prefix)
        while msk.ndim < logits.ndim:
            msk = msk[None]
        logits = jnp.where(msk, logits, NEG_INF)
        m_c = jnp.max(logits, axis=-1, keepdims=True)
        m_n = jnp.maximum(m_p, m_c)
        p = jnp.exp(logits - m_n)
        alpha = jnp.exp(m_p - m_n)
        l_n = alpha * l_p + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jnp.einsum(
            "bhgqk,bhkd->bhgqd", p.astype(vb.dtype), vb,
            preferred_element_type=jnp.float32)
        return (m_n, l_n, acc), None

    state, _ = lax.scan(kv_step, state, (kc, vc, kpc))
    return state


def attn_ring(q, k, v, *, mesh, axis: str = "model", batch_axes=("data",),
              causal=True, window=0, softcap=0.0, chunk_k: int = 512):
    """Ring attention (context parallelism): the sequence dim of q/k/v is
    sharded over ``axis``; kv blocks circulate the ring via ppermute while
    each chip online-softmaxes its local queries against every block.

    Per-chip collective volume: (M-1)/M of the LOCAL kv (B_loc * S *
    Hkv * Dh * 2 * 2 bytes) per layer — orders less than gathering
    activations when d_model >> Hkv*Dh (GQA), which is what makes it the
    prefill hillclimb for the big dense archs.  q/k/v: [B, S, H*, Dh]
    logically global.
    """
    from jax.sharding import PartitionSpec as P

    b, s, hq, dh = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    bspec = tuple(batch_axes) if batch_axes else None

    # With no causal/window masking the positions are dead code, but old
    # jax lowers the leftover axis_index to a PartitionId the SPMD
    # partitioner rejects — skip computing them entirely.
    needs_pos = causal or not (isinstance(window, int) and window == 0)

    def body(q_l, k_l, v_l):
        M = lax.axis_size(axis)
        bl, s_loc = q_l.shape[0], q_l.shape[1]
        if needs_pos:
            m_idx = lax.axis_index(axis)
            qpos = m_idx * s_loc + jnp.arange(s_loc, dtype=jnp.int32)
        else:
            m_idx = 0
            qpos = jnp.zeros(s_loc, dtype=jnp.int32)
        qf = (q_l * jnp.asarray(dh ** -0.5, q_l.dtype))             .reshape(bl, s_loc, hkv, g, dh).transpose(0, 2, 3, 1, 4)
        m0 = jnp.full((bl, hkv, g, s_loc, 1), NEG_INF, jnp.float32)
        l0 = jnp.zeros((bl, hkv, g, s_loc, 1), jnp.float32)
        a0 = jnp.zeros((bl, hkv, g, s_loc, dh), jnp.float32)

        def stage(carry, j):
            (k_c, v_c), st = carry
            src_shard = (m_idx - j) % M
            kpos = src_shard * s_loc + jnp.arange(s_loc, dtype=jnp.int32)
            st = _online_block(qf, k_c, v_c, qpos, kpos, st, causal,
                               window, softcap, chunk_k=chunk_k)
            perm = [(i, (i + 1) % M) for i in range(M)]
            k_c = lax.ppermute(k_c, axis, perm)
            v_c = lax.ppermute(v_c, axis, perm)
            return ((k_c, v_c), st), None

        ((_, _), (m_f, l_f, acc)), _ = lax.scan(
            stage, ((k_l, v_l), (m0, l0, a0)),
            jnp.arange(lax.axis_size(axis)))
        out = acc / jnp.where(l_f > 0, l_f, 1.0)
        # [B,Hkv,G,Sq,Dh] -> [B,Sq,Hq,Dh]
        out = out.transpose(0, 3, 1, 2, 4).reshape(bl, s_loc, hq, dh)
        return out.astype(q_l.dtype)

    spec = P(bspec, axis, None, None)
    fn = jax.shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=spec, check_vma=False)
    return fn(q, k, v)


def attn_decode(q, k_cache, v_cache, q_index, causal=True, window=0,
                softcap=0.0):
    """Single-token decode: q [B,1,Hq,Dh], caches [B,C,Hkv,Dh].
    q_index: current position (scalar or [B])."""
    b, _, hq, dh = q.shape
    c = k_cache.shape[1]
    hkv = k_cache.shape[2]
    g = hq // hkv
    qf = (q.astype(jnp.float32) * (dh ** -0.5)).reshape(b, hkv, g, dh)
    logits = jnp.einsum("bhgd,bkhd->bhgk", qf, k_cache.astype(jnp.float32))
    logits = _softcap(logits, softcap)
    kpos = jnp.arange(c)
    qi = jnp.atleast_1d(jnp.asarray(q_index))[:, None]   # [B or 1, 1]
    valid = kpos[None, :] <= qi if causal else jnp.ones((1, c), bool)
    w = jnp.asarray(window)
    valid &= (w <= 0) | (kpos[None, :] > qi - w)
    logits = jnp.where(valid[:, None, None, :], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgk,bkhd->bhgd", p, v_cache.astype(jnp.float32))
    return out.reshape(b, 1, hq, dh).astype(q.dtype)


def attention_output(q, k, v, qpos, kpos, impl: str, causal=True, window=0,
                     softcap=0.0, chunk: int = 1024, prefix: int = 0):
    if impl == "ref":
        return attn_ref(q, k, v, qpos, kpos, causal, window, softcap, prefix)
    if impl == "chunked":
        return attn_chunked(q, k, v, qpos, kpos, causal, window, softcap,
                            chunk_q=chunk, chunk_k=chunk, prefix=prefix)
    if impl == "flash":
        from repro.kernels.flash_attention.ops import flash_attention
        # flash kernel wants [B,H,S,D] and static window/softcap
        o = flash_attention(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                            v.transpose(0, 2, 1, 3), causal=causal,
                            window=int(window), softcap=float(softcap))
        return o.transpose(0, 2, 1, 3)
    raise ValueError(impl)


# ---------------------------------------------------------------------------
# Parameterized sublayers
# ---------------------------------------------------------------------------

def init_attn(key, cfg, layers: Optional[int] = None):
    d, qd, kvd = cfg.d_model, cfg.q_dim, cfg.kv_dim
    ks = jax.random.split(key, 4)
    shp = (lambda *s: ((layers,) + s) if layers else s)
    scale = d ** -0.5
    return {
        "wq": jax.random.normal(ks[0], shp(d, qd), jnp.float32) * scale,
        "wk": jax.random.normal(ks[1], shp(d, kvd), jnp.float32) * scale,
        "wv": jax.random.normal(ks[2], shp(d, kvd), jnp.float32) * scale,
        "wo": jax.random.normal(ks[3], shp(qd, d), jnp.float32)
              * (qd ** -0.5) / max(cfg.n_layers, 1) ** 0.5,
    }


def init_mlp(key, cfg, layers: Optional[int] = None, d_ff: Optional[int] = None):
    d = cfg.d_model
    f = d_ff if d_ff is not None else cfg.d_ff
    ks = jax.random.split(key, 3)
    shp = (lambda *s: ((layers,) + s) if layers else s)
    return {
        "w_gate": jax.random.normal(ks[0], shp(d, f), jnp.float32) * d ** -0.5,
        "w_up": jax.random.normal(ks[1], shp(d, f), jnp.float32) * d ** -0.5,
        "w_down": jax.random.normal(ks[2], shp(f, d), jnp.float32)
                  * (f ** -0.5) / max(cfg.n_layers, 1) ** 0.5,
    }


def mlp_apply(p, x, act: str = "silu"):
    dt = x.dtype
    gate = x @ p["w_gate"].astype(dt)
    up = x @ p["w_up"].astype(dt)
    actv = jax.nn.silu if act == "silu" else jax.nn.gelu
    return (actv(gate) * up) @ p["w_down"].astype(dt)


def qkv_proj(p, x, cfg):
    """x [B,S,D] -> q [B,S,Hq,Dh], k/v [B,S,Hkv,Dh]."""
    b, s, _ = x.shape
    dt = x.dtype
    q = (x @ p["wq"].astype(dt)).reshape(b, s, cfg.n_heads, cfg.d_head)
    k = (x @ p["wk"].astype(dt)).reshape(b, s, cfg.n_kv_heads, cfg.d_head)
    v = (x @ p["wv"].astype(dt)).reshape(b, s, cfg.n_kv_heads, cfg.d_head)
    return q, k, v


def out_proj(p, o, x_dtype):
    b, s, hq, dh = o.shape
    return o.reshape(b, s, hq * dh) @ p["wo"].astype(x_dtype)


def init_embed(key, cfg):
    ks = jax.random.split(key, 3)
    vp = cfg.padded_vocab
    p = {
        "embedding": jax.random.normal(
            ks[0], (vp, cfg.d_model), jnp.float32) * 0.02,
        "final_norm": jnp.zeros((cfg.d_model,), jnp.float32),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = jax.random.normal(
            ks[1], (cfg.d_model, vp), jnp.float32) \
            * cfg.d_model ** -0.5
    return p


def embed_tokens(p, tokens, cfg, dtype):
    x = jnp.take(p["embedding"], tokens, axis=0).astype(dtype)
    if cfg.family in ("vlm",):          # gemma-style embedding scaling
        x = x * jnp.asarray(cfg.d_model ** 0.5, dtype)
    return x


def unembed(p, x, cfg):
    x = rms_norm(x, p["final_norm"])
    if cfg.tie_embeddings:
        logits = x.astype(jnp.float32) @ p["embedding"].T.astype(jnp.float32)
    else:
        logits = x.astype(jnp.float32) @ p["lm_head"].astype(jnp.float32)
    logits = _softcap(logits, cfg.final_logit_softcap)
    if cfg.padded_vocab != cfg.vocab_size:
        pad_mask = jnp.arange(logits.shape[-1]) < cfg.vocab_size
        logits = jnp.where(pad_mask, logits, NEG_INF)
    return logits


def layer_windows(cfg) -> jnp.ndarray:
    """Per-layer sliding-window sizes [L] (0 = global/full attention)."""
    L = cfg.n_layers
    w = jnp.zeros((L,), jnp.int32)
    if cfg.window and cfg.local_global_period:
        # gemma2: even layers local, every `period`-th global
        ids = jnp.arange(L)
        w = jnp.where(ids % cfg.local_global_period == 0, cfg.window, 0)
    elif cfg.window:
        w = jnp.full((L,), cfg.window, jnp.int32)
        if cfg.global_layers:
            ids = jnp.arange(L)
            for gl in cfg.global_layers:
                w = jnp.where(ids == gl, 0, w)
    return w
