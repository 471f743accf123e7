"""Pallas TPU kernels: vectorized snapshot resolution (paper §2.3.1).

``snapshot(v) = d(i_v), i_v = max{v' <= v}`` over a multi-version column
store: items (N, K) with K version slots (ascending, MAX-padded). The scan
over candidate versions is a VPU-parallel masked max across the K lanes,
and the value of the winning slot is picked by a masked lane sum (no
in-kernel gather) — one HBM pass over the version matrix.

Blocking: grid over item blocks; each instance holds an (NB, K) version tile
and the matching (NB, K) value tile in VMEM, emits (NB,) resolved values.
The query version rides in SMEM as a (1, 1) scalar.

Edge liveness (:func:`liveness_mask`) is the 2-slot case, but it does not go
through the (N, K) layout: a (N, 2) int32 array occupies a whole 128-lane
tile per row in HBM, 64x its payload, so at 2^26 rows it cannot even be
allocated on one chip. Liveness is instead computed elementwise on the two
stamp columns reshaped to lane-dense (rows, 128) tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_ITEM_BLOCK = 1024
LANES = 128
# (512, 128) int32 tiles: 256 KiB per operand block
DEFAULT_ROW_BLOCK = 512


def _query_scalar(query_version):
    return jnp.asarray(query_version, jnp.int32).reshape(1, 1)


_SMEM_SCALAR = pl.BlockSpec(memory_space=pltpu.SMEM)


def _kernel(q_ref, ver_ref, val_ref, out_ref, idx_ref):
    q = q_ref[0, 0]
    vers = ver_ref[...]                       # (NB, K) int32
    vals = val_ref[...]                       # (NB, K)
    # index of the newest eligible version; -1 if none
    k = jax.lax.broadcasted_iota(jnp.int32, vers.shape, 1)
    best = jnp.max(jnp.where(vers <= q, k, -1), axis=1)      # (NB,)
    # exactly one lane matches when best >= 0, none when best == -1 (-> 0)
    picked = jnp.sum(jnp.where(k == best[:, None], vals,
                               jnp.zeros_like(vals)), axis=1)
    out_ref[...] = picked
    idx_ref[...] = best


@functools.partial(jax.jit, static_argnames=("item_block", "interpret"))
def snapshot_resolve(versions, values, query_version, *,
                     item_block: int = DEFAULT_ITEM_BLOCK,
                     interpret: bool = False):
    """versions: (N, K) int32 ascending (pad = int32 max); values: (N, K);
    query_version: scalar int32. Returns (resolved (N,), index (N,) with -1
    for items having no version <= query)."""
    N, K = versions.shape
    if N == 0:
        return (jnp.zeros((0,), values.dtype), jnp.zeros((0,), jnp.int32))
    nb = min(item_block, N)
    pad = (-N) % nb
    if pad:
        maxv = jnp.iinfo(jnp.int32).max
        versions = jnp.pad(versions, ((0, pad), (0, 0)), constant_values=maxv)
        values = jnp.pad(values, ((0, pad), (0, 0)))
    Np = versions.shape[0]
    out, idx = pl.pallas_call(
        _kernel,
        grid=(Np // nb,),
        in_specs=[
            _SMEM_SCALAR,
            pl.BlockSpec((nb, K), lambda i: (i, 0)),
            pl.BlockSpec((nb, K), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((nb,), lambda i: (i,)),
            pl.BlockSpec((nb,), lambda i: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Np,), values.dtype),
            jax.ShapeDtypeStruct((Np,), jnp.int32),
        ],
        interpret=interpret,
    )(_query_scalar(query_version), versions, values)
    return out[:N], idx[:N]


def _liveness_kernel(q_ref, created_ref, deleted_ref, out_ref):
    q = q_ref[0, 0]
    live = (created_ref[...] <= q) & (q < deleted_ref[...])
    out_ref[...] = live.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("row_block", "interpret"))
def liveness_mask(created, deleted, query_version, *,
                  row_block: int = DEFAULT_ROW_BLOCK,
                  interpret: bool = False):
    """Edge liveness ``created <= q < deleted`` — the 2-slot snapshot rule
    (the newest eligible of [created, deleted] is 'created' exactly when
    the edge is live), evaluated elementwise in one HBM pass over the two
    stamp columns; the snapshot-mask hot path of the dynamic graph store.

    created/deleted: (N,) int32 data-plane-packed version stamps (deleted
    is MAX-padded until tombstoned). The dynamic graph store keeps its
    stamp arrays in this packing natively, so they arrive here as-is — no
    64→32-bit host repack on the query path. Returns (N,) bool.
    """
    created = jnp.asarray(created, jnp.int32)
    deleted = jnp.asarray(deleted, jnp.int32)
    N = created.shape[0]
    if N == 0:
        return jnp.zeros((0,), bool)
    rows = -(-N // LANES)
    rb = min(row_block, -(-rows // 8) * 8)
    rows_p = -(-rows // rb) * rb
    pad = rows_p * LANES - N
    if pad:
        # padding rows are never created, so never live (sliced off anyway)
        maxv = jnp.iinfo(jnp.int32).max
        created = jnp.pad(created, (0, pad), constant_values=maxv)
        deleted = jnp.pad(deleted, (0, pad), constant_values=maxv)
    block = pl.BlockSpec((rb, LANES), lambda i: (i, 0))
    out = pl.pallas_call(
        _liveness_kernel,
        grid=(rows_p // rb,),
        in_specs=[_SMEM_SCALAR, block, block],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct((rows_p, LANES), jnp.int32),
        interpret=interpret,
    )(_query_scalar(query_version), created.reshape(rows_p, LANES),
      deleted.reshape(rows_p, LANES))
    return out.reshape(-1)[:N] != 0
