"""Per-kernel interpret-mode sweeps vs the pure-jnp oracles (shape × dtype
grids), per the kernel contract in src/repro/kernels/."""
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.decode_attn import decode_attention
from repro.kernels.segment_agg import segment_agg
from repro.kernels.ssd_scan import ssd_scan


# --------------------------------------------------------------------------
# segment_agg
# --------------------------------------------------------------------------

@pytest.mark.parametrize("n,nseg,block", [
    (64, 8, 16), (100, 5, 32), (256, 128, 256), (1000, 17, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_segment_agg_sweep(n, nseg, block, dtype):
    rng = np.random.default_rng(n + nseg)
    segs = np.sort(rng.integers(0, nseg, n)).astype(np.int32)
    vals = rng.uniform(-10, 10, n).astype(np.float32)
    valid = rng.random(n) < 0.9
    v = jnp.asarray(vals, dtype)
    got = segment_agg(v, jnp.asarray(segs), jnp.asarray(valid), nseg,
                      block_rows=block, interpret=True)
    want = ref.segment_agg_ref(v, jnp.asarray(segs), jnp.asarray(valid), nseg)
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol)


def test_backend_probe_failure_is_not_read_as_no_tpu(monkeypatch):
    """A backend that fails to initialize must surface, not silently
    select the CPU fallback kernels."""
    from repro.kernels import ops

    def broken():
        raise RuntimeError("TPU backend failed to initialize")

    monkeypatch.delenv("REPRO_USE_PALLAS", raising=False)
    monkeypatch.setattr(jax, "default_backend", broken)
    with pytest.raises(RuntimeError, match="failed to initialize"):
        ops.want_pallas()


def test_compile_cache_directory(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR wins untouched; without it the cache
    goes to the fixed in-checkout directory."""
    from repro.launch import compile_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.configure_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        got = compile_cache.configure_compile_cache()
        assert got == str(compile_cache.CHECKOUT_CACHE)
        assert jax.config.jax_compilation_cache_dir == got
        repo_root = Path(__file__).resolve().parents[1]
        assert compile_cache.CHECKOUT_CACHE.parent == repo_root
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_segment_agg_all_invalid_segment():
    segs = jnp.asarray(np.array([0, 0, 2, 2], np.int32))
    vals = jnp.asarray(np.array([1., 2., 3., 4.], np.float32))
    valid = jnp.asarray(np.array([True, True, False, False]))
    got = segment_agg(vals, segs, valid, 3, block_rows=4, interpret=True)
    assert float(got[0, 0]) == 3.0        # sum seg0
    assert float(got[1, 2]) == 0.0        # count seg2
    assert np.isinf(float(got[2, 2]))     # min of empty = +inf


# --------------------------------------------------------------------------
# decode attention
# --------------------------------------------------------------------------

@pytest.mark.parametrize("bh,g,d,s,chunk", [
    (2, 8, 128, 256, 128), (1, 16, 128, 300, 128), (4, 8, 256, 512, 256),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_sweep(bh, g, d, s, chunk, dtype):
    rng = np.random.default_rng(bh * 100 + s)
    q = jnp.asarray(rng.standard_normal((bh, g, d)), dtype)
    k = jnp.asarray(rng.standard_normal((bh, s, d)), dtype)
    v = jnp.asarray(rng.standard_normal((bh, s, d)), dtype)
    kv_len = jnp.asarray(rng.integers(1, s + 1, bh).astype(np.int32))
    got = decode_attention(q, k, v, kv_len, chunk=chunk, interpret=True)
    want = ref.decode_attention_ref(q, k, v, kv_len)
    tol = 2e-5 if dtype == jnp.float32 else 4e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_decode_attention_tiny_cache():
    """kv_len=1: attends a single position exactly."""
    q = jnp.ones((1, 8, 128), jnp.float32)
    k = jnp.ones((1, 256, 128), jnp.float32)
    v = jnp.concatenate([jnp.full((1, 1, 128), 7.0),
                         jnp.zeros((1, 255, 128))], axis=1)
    out = decode_attention(q, k, v, jnp.asarray([1], jnp.int32),
                           chunk=128, interpret=True)
    np.testing.assert_allclose(np.asarray(out), 7.0, rtol=1e-6)


# --------------------------------------------------------------------------
# SSD scan
# --------------------------------------------------------------------------

@pytest.mark.parametrize("bh,t,p,n,chunk", [
    (2, 128, 64, 16, 32), (1, 256, 128, 32, 64), (3, 64, 32, 8, 64),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_scan_sweep(bh, t, p, n, chunk, dtype):
    rng = np.random.default_rng(t + p)
    x = jnp.asarray(rng.standard_normal((bh, t, p)) * 0.5, dtype)
    log_a = jnp.asarray(-np.abs(rng.standard_normal((bh, t))) * 0.1,
                        jnp.float32)
    b = jnp.asarray(rng.standard_normal((bh, t, n)) * 0.3, dtype)
    c = jnp.asarray(rng.standard_normal((bh, t, n)) * 0.3, dtype)
    got = ssd_scan(x, log_a, b, c, chunk=min(chunk, t), interpret=True)
    want = ref.ssd_scan_ref(x, log_a, b, c)
    tol = 2e-4 if dtype == jnp.float32 else 6e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_ssd_chunk_invariance():
    """The chunked execution (Merge across chunks) is invariant to chunk
    size — the associativity property Aggify's chunked executor relies on."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((1, 128, 32)) * 0.5, jnp.float32)
    log_a = jnp.asarray(-np.abs(rng.standard_normal((1, 128))) * 0.2,
                        jnp.float32)
    b = jnp.asarray(rng.standard_normal((1, 128, 8)) * 0.3, jnp.float32)
    c = jnp.asarray(rng.standard_normal((1, 128, 8)) * 0.3, jnp.float32)
    outs = [np.asarray(ssd_scan(x, log_a, b, c, chunk=cs, interpret=True))
            for cs in (16, 32, 64, 128)]
    for o in outs[1:]:
        np.testing.assert_allclose(outs[0], o, rtol=2e-4, atol=2e-4)


def test_ssd_chunked_jnp_matches_ref():
    """The chunked jnp lowering path (kernel math, no Pallas) must match
    the sequential oracle for several chunk sizes."""
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.standard_normal((2, 128, 32)) * 0.5, jnp.float32)
    log_a = jnp.asarray(-np.abs(rng.standard_normal((2, 128))) * 0.15,
                        jnp.float32)
    b = jnp.asarray(rng.standard_normal((2, 128, 8)) * 0.3, jnp.float32)
    c = jnp.asarray(rng.standard_normal((2, 128, 8)) * 0.3, jnp.float32)
    want = ref.ssd_scan_ref(x, log_a, b, c)
    for chunk in (16, 32, 64, 128):
        got = ref.ssd_scan_chunked(x, log_a, b, c, chunk=chunk)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)
