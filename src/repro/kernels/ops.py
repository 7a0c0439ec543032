"""jit'd public wrappers over the Pallas kernels with automatic fallback.

``use_pallas`` dispatch: on a real TPU backend the compiled kernels run;
on CPU (this container) the kernels execute in ``interpret=True`` mode for
correctness tests, while the *framework* call sites (models, engine) use
the jnp reference implementations by default so full-model smoke tests are
not slowed by the Python interpreter loop.  The dry-run lowers the jnp
path (identical math) — kernels are the TPU execution plan, refs are the
oracle and the CPU fallback.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from . import ref as _ref
from repro.configs import flags
from .decode_attn import decode_attention as _decode_pallas
from .segment_agg import fused_segment_agg as _fused_segagg
from .segment_agg import segment_agg as _segagg_pallas
from .ssd_scan import ssd_scan as _ssd_pallas


def _on_tpu() -> bool:
    # a backend that fails to initialize raises here; it is not "no TPU"
    return jax.default_backend() == "tpu"


def want_pallas(default: bool | None = None) -> bool:
    env = flags.value("REPRO_USE_PALLAS")
    if env is not None:
        return env not in ("0", "false", "False")
    if default is not None:
        return default
    return _on_tpu()


def segment_agg(vals, segs, valid, num_segments: int, *,
                use_pallas: bool | None = None, block_rows: int = 256):
    if want_pallas(use_pallas):
        return _segagg_pallas(vals, segs, valid, num_segments,
                              block_rows=block_rows,
                              interpret=not _on_tpu())
    return _ref.segment_agg_ref(vals, segs, valid, num_segments)


def fused_segment_agg(vals, segs, valid, num_segments: int, *,
                      use_pallas: bool | None = None, block_rows: int = 256,
                      block_segs: int | None = None):
    """Multi-column fused segmented aggregation → (C, 4, num_segments).
    Kernel on TPU (interpret under test), jnp segment ops otherwise."""
    if want_pallas(use_pallas):
        backend = "pallas" if _on_tpu() else "interpret"
    else:
        backend = "jnp"
    return _fused_segagg(vals, segs, valid, num_segments,
                         block_rows=block_rows, block_segs=block_segs,
                         backend=backend)


def decode_attention(q, k, v, kv_len, *, use_pallas: bool | None = None,
                     chunk: int = 128):
    if want_pallas(use_pallas):
        return _decode_pallas(q, k, v, kv_len, chunk=chunk,
                              interpret=not _on_tpu())
    return _ref.decode_attention_ref(q, k, v, kv_len)


def ssd_scan(x, log_a, b, c, *, use_pallas: bool | None = None,
             chunk: int = 64):
    if want_pallas(use_pallas):
        return _ssd_pallas(x, log_a, b, c, chunk=chunk,
                           interpret=not _on_tpu())
    # chunked dual form (same math as the kernel) — NOT the sequential
    # oracle, which would lower to a T-step scan
    return _ref.ssd_scan_chunked(x, log_a, b, c, chunk=chunk)
