"""Kernel-layer microbenchmarks.

Pallas interpret-mode timings are meaningless (Python loop emulation), so
the wall-clock comparisons here are between the two *algorithmic layouts*
the platform can run on any backend:

  * ELL gather+combine (the kernel's memory-access pattern, jnp ref)
    vs COO segment_sum (the exact path) for the SpMV hot loop;
  * chunked online-softmax attention vs naive S^2 attention.

plus a correctness/roofline line for the Pallas kernels themselves
(interpret=True, tiny shapes) so `benchmarks.run` exercises them.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import time_fn, csv_row
from repro.core import graph as G
from repro.data import synthetic as S
from repro.kernels.ell_combine.ops import ell_spmv, ell_spmv_ref
from repro.kernels.pregel_superstep import (fused_superstep,
                                            fused_superstep_ref)
from repro.models.layers import attn_chunked, attn_ref
from repro.utils.compile_cache import enable_compile_cache


def run(out=print):
    rows = []
    # --- SpMV layouts ---------------------------------------------------
    src, dst = S.user_follow_graph(50_000, 8.0, seed=5)
    n = 50_000
    coo = G.build_coo(src, dst, n)
    ell = G.build_ell(np.asarray(coo.src)[:coo.n_edges],
                      np.asarray(coo.dst)[:coo.n_edges], n, 64)
    x = jnp.asarray(np.random.default_rng(0).standard_normal(n + 1),
                    jnp.float32)

    @jax.jit
    def spmv_coo(x):
        contrib = x[jnp.clip(coo.src, 0, n - 1)] * coo.w
        return jax.ops.segment_sum(contrib, coo.dst, num_segments=n + 1)[:n]

    @jax.jit
    def spmv_ell(x):
        return ell_spmv_ref(ell.nbr, ell.mask, ell.w, x, op="sum")

    t_coo, _ = time_fn(spmv_coo, x)
    t_ell, _ = time_fn(spmv_ell, x)
    out(csv_row("kernels/spmv_coo_segsum", t_coo, f"E={coo.n_edges}"))
    out(csv_row("kernels/spmv_ell_gather", t_ell,
                f"ratio={t_coo / t_ell:.2f}x"))

    # --- attention layouts ------------------------------------------------
    rng = np.random.default_rng(1)
    b, s, hq, hkv, dh = 1, 1024, 8, 2, 64
    q = jnp.asarray(rng.standard_normal((b, s, hq, dh)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, hkv, dh)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, hkv, dh)), jnp.float32)
    pos = jnp.arange(s)
    f_ref = jax.jit(lambda q, k, v: attn_ref(q, k, v, pos, pos))
    f_chk = jax.jit(lambda q, k, v: attn_chunked(q, k, v, pos, pos,
                                                 chunk_q=256, chunk_k=256))
    t_ref, o_ref = time_fn(f_ref, q, k, v)
    t_chk, o_chk = time_fn(f_chk, q, k, v)
    np.testing.assert_allclose(np.asarray(o_ref), np.asarray(o_chk),
                               rtol=2e-4, atol=2e-4)
    out(csv_row("kernels/attn_naive_s1024", t_ref, ""))
    out(csv_row("kernels/attn_chunked_s1024", t_chk,
                f"ratio={t_ref / t_chk:.2f}x"))

    # --- fused superstep layouts ------------------------------------------
    # dense path's superstep (gather -> [E] msgs -> segment-min) vs the
    # fused ELL gather+combine the pregel_superstep kernel packages
    @jax.jit
    def superstep_coo(x):
        msgs = x[jnp.clip(coo.src, 0, n - 1)] + coo.w
        return jax.ops.segment_min(msgs, coo.dst,
                                   num_segments=n + 1)[:n]

    @jax.jit
    def superstep_fused(x):
        return fused_superstep_ref(ell.nbr, ell.mask, ell.w, x,
                                   message=lambda s, w_: s + w_,
                                   op="min", identity=float("inf"))

    t_coo, _ = time_fn(superstep_coo, x)
    t_fus, _ = time_fn(superstep_fused, x)
    out(csv_row("kernels/superstep_coo_segmin", t_coo, f"E={coo.n_edges}"))
    out(csv_row("kernels/superstep_fused_ell", t_fus,
                f"ratio={t_coo / t_fus:.2f}x"))

    # --- Pallas kernels, interpret correctness ping -----------------------
    nbr = jnp.asarray(rng.integers(0, 256, (256, 128)), jnp.int32)
    mask = jnp.asarray(rng.random((256, 128)) < 0.5)
    w = jnp.asarray(rng.standard_normal((256, 128)), jnp.float32)
    xx = jnp.asarray(rng.standard_normal(256), jnp.float32)
    got = ell_spmv(nbr, mask, w, xx, op="sum")
    want = ell_spmv_ref(nbr, mask, w, xx, op="sum")
    err = float(jnp.max(jnp.abs(got - want)))
    out(csv_row("kernels/pallas_ell_interpret", 0.0, f"maxerr={err:.2e}"))
    sgot = fused_superstep(nbr, mask, w, xx,
                           message=lambda s, w_: s + w_, op="min",
                           identity=float("inf"), use_pallas=True)
    swant = fused_superstep_ref(nbr, mask, w, xx,
                                message=lambda s, w_: s + w_, op="min",
                                identity=float("inf"))
    serr = float(jnp.max(jnp.abs(sgot - swant)))
    out(csv_row("kernels/pallas_superstep_interpret", 0.0,
                f"maxerr={serr:.2e}"))
    return rows


if __name__ == "__main__":
    enable_compile_cache()
    run()
