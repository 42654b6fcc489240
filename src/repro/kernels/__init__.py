# Pallas TPU kernels for the platform's compute hot spots:
#   segment_runs     — the dense superstep's combine: a segmented scan
#                      along destination-sorted runs of edge slots; on
#                      the served path whenever the edge shards carry
#                      run offsets (one device).
#   ell_intersect    — sorted-row intersection counts, the inner loop of
#                      degree-ordered triangle counting; compiles for v5e.
#   ell_combine      — ELL gather+combine (SpMV / hash-to-min) and
#   pregel_superstep — the fused superstep: both gather with an in-kernel
#                      jnp.take that the chip's compiler refuses, so they
#                      run in interpret mode only and are off the served
#                      path.
#   flash_attention  — online-softmax attention for the LM serving cells
#                      (prefill_32k) of the assigned architectures.
# Each kernel ships kernel.py (pl.pallas_call + BlockSpec), ops.py (jit'd
# public wrapper, interpret=True on CPU), ref.py (pure-jnp oracle).
