"""Rehearse ``chip_smoke.py`` on the CPU at tiny scales.

The script's phases are imported and run as the chip would run them
(Pallas kernels in interpret mode here); the four-chip phase runs in a
child interpreter on four virtual CPU devices.  ``main()`` itself must
refuse to run without a TPU.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core import registry as R
from repro.core.algorithms.connected_components import (
    connected_components_reference)
from repro.core.algorithms.pagerank import pagerank_reference
from repro.core.algorithms.traversal import bfs_reference
from repro.core.algorithms.triangles import k_core_reference

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
import chip_smoke as C  # noqa: E402


def _env(**extra):
    env = {**os.environ, "PYTHONPATH": os.path.join(ROOT, "src"),
           "JAX_PLATFORMS": "cpu", **extra}
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return env


def test_main_refuses_without_tpu():
    r = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                       capture_output=True, text=True, timeout=300,
                       env=_env(), cwd=ROOT)
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert '"ok"' not in r.stdout


@pytest.fixture(scope="module")
def small():
    coo = C.graph500(9, seed=1)
    src, dst = C.host_edges(coo)
    return coo, src, dst, C.host_csr(src, dst, coo.n_vertices)


@pytest.mark.parametrize("which", ["cc", "bfs", "k_core", "pagerank"])
def test_references_agree_with_the_repo_oracles(small, which):
    coo, src, dst, adj = small
    V = coo.n_vertices
    if which == "pagerank":
        for tol, iters in ((1e-12, 10), (1e-6, 100)):
            got, n = C.pagerank_reference_csr(adj, 0.85, tol, iters)
            want, m = pagerank_reference(src, dst, V, tol=tol,
                                         max_iters=iters)
            assert n == m
            np.testing.assert_allclose(got, want, rtol=1e-10, atol=0)
    elif which == "cc":
        np.testing.assert_array_equal(
            C.cc_reference(adj), connected_components_reference(src, dst, V))
    elif which == "bfs":
        for s in C.search_keys(adj, 3, seed=0):
            np.testing.assert_array_equal(C.bfs_reference(adj, s),
                                          bfs_reference(src, dst, V, [s]))
    else:
        for k in (2, 8, 16):
            assert C.kcore_size_reference(adj, k) == \
                int(k_core_reference(src, dst, V, k).sum())


def test_service_phase_rehearsal():
    lines = []
    C.service_phase(C.graph500(10, seed=0), seed=0, out=lines.append)
    text = "\n".join(lines)
    assert "retries=0 dead_letters=0 fused_batches=1" in text
    assert sum("bfs[" in ln and "tier=batch" in ln and "fused_width=4" in ln
               for ln in lines) == 4
    assert "0 labels differ" in text and "0 distances differ" in text
    assert sum("ticket(s) drained" in ln for ln in lines) == 5


@pytest.mark.parametrize("fault", ["retry", "dead-letter", "mismatch"])
def test_service_phase_fails_loudly(fault, monkeypatch):
    coo = C.graph500(8, seed=0)
    if fault == "mismatch":
        monkeypatch.setattr(C, "kcore_size_reference",
                            lambda adj, k: -1)
    else:
        policy = R.FailNTimes(1) if fault == "retry" else R.FailAlways()
        R.install_fault("pagerank", policy)
    try:
        with pytest.raises(C.SmokeFailure):
            C.service_phase(coo, seed=0, out=lambda _: None)
    finally:
        R.uninstall_fault(None)


def test_kernel_phase_rehearsal():
    lines = []
    C.kernel_phase(C.graph500(8, seed=0), out=lines.append)
    assert any("triangles: ell_intersect kernel" in ln for ln in lines)


FOUR_CHIP_SCRIPT = textwrap.dedent("""
    import sys
    sys.path.insert(0, {root!r})
    import chip_smoke as C
    C.distributed_phase(C.graph500(10, seed=0), C.four_chip_mesh())
    print('FOUR_CHIP_OK')
""")


def test_four_chip_phase_rehearsal():
    r = subprocess.run(
        [sys.executable, "-c", FOUR_CHIP_SCRIPT.format(root=ROOT)],
        capture_output=True, text=True, timeout=600, cwd=ROOT,
        env=_env(XLA_FLAGS="--xla_force_host_platform_device_count=4"))
    assert "FOUR_CHIP_OK" in r.stdout, r.stderr[-3000:]
    assert r.stdout.count("one shard on each of 4 devices") == 2
    assert "0 labels differ" in r.stdout


@pytest.mark.parametrize("env_dir", [False, True])
def test_compile_cache_directory(tmp_path, env_dir):
    """Set: JAX's own variable wins.  Unset: the fixed
    <checkout>/.jax_cache.  Either way an entry's key holds the
    program's metadata, so a renamed scope compiles anew."""
    extra = {"JAX_COMPILATION_CACHE_DIR": str(tmp_path)} if env_dir else {}
    code = ("import jax\n"
            "from repro.utils.compile_cache import enable_compile_cache\n"
            "print(enable_compile_cache())\n"
            "print(jax.config.jax_compilation_cache_dir)\n"
            "print(jax.config.jax_compilation_cache_include_metadata_in_key)"
            "\n")
    env = _env()
    env.update(extra)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=300, env=env, cwd=ROOT)
    chosen, configured, metadata = r.stdout.split()
    want = str(tmp_path) if env_dir else os.path.join(ROOT, ".jax_cache")
    assert chosen == configured == want
    assert metadata == "True"
