"""Pallas TPU kernel: sorted segment-sum (the join-group-by hot spot).

TPU adaptation of the paper's join-group-by operator (DESIGN.md §2): instead
of a GPU warp-per-row scatter, the reduction is reformulated as an MXU
matmul: for each edge block, ``one_hot(segment_ids) @ values`` accumulates
into a VMEM-resident output column block. The one-hot compare runs on the
VPU; the (RB x EB) @ (EB x FB) products run on the MXU.

Blocking: grid = (F // FB, m // EB); the edge axis is the *inner* (fastest)
grid dim so the (n, FB) accumulator block stays resident in VMEM across the
whole edge sweep (Pallas keeps a block resident while its index_map output
is unchanged); it is zeroed at the first edge step and written back once.
Segment ids ride as a lane-dense ``(1, m)`` row, so their blocks match the
TPU's native tiling. Inside a step the one-hot is built ``ROW_BLOCK``
segment rows at a time, and only for the rows between the block's smallest
and largest segment id (a per-block SMEM pair): with sorted ids an edge
block touches one or two row blocks, so the work is linear in m.

Precision: the MXU multiplies bf16. Each f32 value is split into three
bf16 pieces (24 mantissa bits in all); the three piece columns are
concatenated as three sorted runs of edges. The one-hot is exact in bf16
and the products are summed in f32, so the result is as exact as an f32
segment sum.

VMEM bound: nothing tiles the segment axis, so the whole (n, FB)
accumulator is resident, and even a one-column block fills a 128-lane tile
per 8 rows: 512 bytes per segment row. The compiler's default scoped VMEM
allowance (16 MiB) would cap n near 32k, so the kernel asks for
:data:`VMEM_LIMIT_BYTES` of a v5e core's 128 MiB. :data:`MAX_SEGMENTS`
is the largest ``num_segments`` that compiles under it at PageRank's shape
(one feature column, 16 edges per segment): the next row block is refused
there. It compiles at 128 columns too (all checked by
``tests/test_tpu_compile.py``). ``repro.kernels.ops`` refuses larger calls
with a ``ValueError`` instead of letting Mosaic fail.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_EDGE_BLOCK = 512
DEFAULT_FEAT_BLOCK = 128
# segment rows per one-hot product inside the kernel
ROW_BLOCK = 512
# the kernel's scoped VMEM allowance, leaving 8 MiB of a v5e core's 128
VMEM_LIMIT_BYTES = 120 * 2**20
# largest num_segments that compiles under it: 478 row blocks, the +1
# padding row included
MAX_SEGMENTS = 478 * ROW_BLOCK - 1


def _kernel(rng_ref, seg_ref, val_ref, out_ref, *, edge_block: int):
    e = pl.program_id(1)

    @pl.when(e == 0)
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)

    segs = seg_ref[...]                                   # (1, EB)
    vals = val_ref[...]                                   # (EB, FB) bf16

    def rows(i, carry):
        r0 = pl.multiple_of(i * ROW_BLOCK, ROW_BLOCK)
        ids = r0 + jax.lax.broadcasted_iota(jnp.int32,
                                            (ROW_BLOCK, edge_block), 0)
        onehot = (ids == segs).astype(jnp.bfloat16)       # exact
        out_ref[pl.ds(r0, ROW_BLOCK), :] += jax.lax.dot(
            onehot, vals, preferred_element_type=jnp.float32)
        return carry

    jax.lax.fori_loop(rng_ref[0, 0, 0], rng_ref[0, 0, 1] + 1, rows, 0)


@functools.partial(jax.jit,
                   static_argnames=("num_segments", "edge_block",
                                    "feat_block", "interpret"))
def segment_sum(values, segment_ids, num_segments: int, *,
                edge_block: int = DEFAULT_EDGE_BLOCK,
                feat_block: int = DEFAULT_FEAT_BLOCK,
                interpret: bool = False):
    """values: (m, F) sorted by segment; segment_ids: (m,) int32 ascending
    (unsorted ids are summed correctly too, only slower). Returns
    (num_segments, F) f32. Pads m/F internally."""
    m, F = values.shape
    if m == 0:
        return jnp.zeros((num_segments, F), jnp.float32)
    rest = values.astype(jnp.float32)
    pieces = []
    for _ in range(3):
        # truncate to bf16 with a bit mask, not a cast: XLA may elide an
        # f32 -> bf16 -> f32 round trip, which would zero the later pieces
        bits = jax.lax.bitcast_convert_type(rest, jnp.uint32)
        head = jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                            jnp.float32)
        pieces.append(head.astype(jnp.bfloat16))          # exact
        rest = rest - head                                # exact
    values = jnp.concatenate(pieces)
    segment_ids = jnp.tile(segment_ids.astype(jnp.int32), 3)
    m *= 3
    eb = min(edge_block, max(m, 8))
    fb = min(feat_block, F)
    m_pad = (-m) % eb
    f_pad = (-F) % fb
    if m_pad or f_pad:
        values = jnp.pad(values, ((0, m_pad), (0, f_pad)))
        # padded edges point at segment n (dropped after)
        segment_ids = jnp.pad(segment_ids, (0, m_pad),
                              constant_values=num_segments)
    blocks = segment_ids.reshape(-1, eb)
    # the row blocks each edge block touches: [first, last]
    ranges = jnp.stack([blocks.min(axis=1), blocks.max(axis=1)],
                       axis=1)[:, None, :] // ROW_BLOCK
    # +1 row swallows padding; rounded to whole row blocks
    n_out = -(-(num_segments + 1) // ROW_BLOCK) * ROW_BLOCK
    grid = (values.shape[1] // fb, values.shape[0] // eb)
    out = pl.pallas_call(
        functools.partial(_kernel, edge_block=eb),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, 2), lambda f, e: (e, 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, eb), lambda f, e: (0, e)),
            pl.BlockSpec((eb, fb), lambda f, e: (e, f)),
        ],
        out_specs=pl.BlockSpec((n_out, fb), lambda f, e: (0, f)),
        out_shape=jax.ShapeDtypeStruct((n_out, values.shape[1]), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(ranges, segment_ids.reshape(1, -1), values)
    return out[:num_segments, :F]
