"""jit'd public wrappers for the Pallas kernels.

On the CPU backend the kernels execute in interpret mode (the kernel body
runs in Python op-by-op — bit-accurate control flow, no Mosaic); on TPU they
compile natively. Any other backend is an error rather than a silent
interpreter run. ``repro.nn``/``repro.graph`` call through this module so
the switch is one place.
"""
from __future__ import annotations

import jax

from repro.kernels import flash_attention as _fa
from repro.kernels import lru_scan as _lru
from repro.kernels import segment_sum as _ss
from repro.kernels import snapshot_resolve as _sr


def _interpret() -> bool:
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(
            f"Pallas kernels compile for TPU and interpret on CPU only; "
            f"the default backend is {backend!r}")
    return backend == "cpu"


def segment_sum(values, segment_ids, num_segments, **kw):
    if num_segments > _ss.MAX_SEGMENTS:
        raise ValueError(
            f"segment_sum keeps all {num_segments} segments resident in "
            f"VMEM; the kernel compiles for at most "
            f"MAX_SEGMENTS={_ss.MAX_SEGMENTS} (kernels/segment_sum.py)")
    kw.setdefault("interpret", _interpret())
    return _ss.segment_sum(values, segment_ids, num_segments, **kw)


def snapshot_resolve(versions, values, query_version, **kw):
    kw.setdefault("interpret", _interpret())
    return _sr.snapshot_resolve(versions, values, query_version, **kw)


def liveness_mask(created, deleted, query_version, **kw):
    """Snapshot-mask hot path: expects the int32 data-plane stamp packing
    the graph store uses natively (sentinel = int32 max), so the stored
    ``created``/``deleted`` arrays feed the kernel without conversion."""
    kw.setdefault("interpret", _interpret())
    return _sr.liveness_mask(created, deleted, query_version, **kw)


def flash_attention(q, k, v, *, causal=True, window=None, **kw):
    kw.setdefault("interpret", _interpret())
    return _fa.flash_attention(q, k, v, causal=causal, window=window, **kw)


def lru_scan(a, b, h0=None, **kw):
    kw.setdefault("interpret", _interpret())
    return _lru.lru_scan(a, b, h0, **kw)
