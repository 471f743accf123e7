"""Graph500 Kronecker graphs, made on the device from a seed in one jitted
call.

The graph follows the Graph500 generator that LDBC Graphalytics uses for
its ``graph500-<scale>`` data sets: ``edge_factor << scale`` edges, each
placed by ``scale`` independent quadrant draws with initiator
probabilities A, B, C and D = 1 - A - B - C, then vertex labels permuted
at random. As Graphalytics does, self-loops and duplicate undirected
pairs are dropped and the ids are compacted to the non-isolated vertices
(in order of their permuted label). The data sets are undirected, and the
store holds directed rows, so each pair becomes two rows, one in each
orientation: the first ``m / 2`` rows hold each pair in the orientation
of its first generated copy, in generation order, and the last ``m / 2``
the same pairs reversed. The generator also returns, for each Kronecker
label, the compacted id it became (-1 where no kept pair touches it), so
that an update stream drawn in the same label space (``streamgen``) lands
on the same vertices.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

_BIG = np.iinfo(np.int32).max


@dataclasses.dataclass(frozen=True)
class Graph:
    """Host copy of a generated graph: ``src``/``dst`` are its directed
    rows (int32), both orientations of every undirected pair; ``labels``
    maps each Kronecker label to its compacted id, or -1."""
    n: int
    src: np.ndarray
    dst: np.ndarray
    labels: Optional[np.ndarray] = None

    @property
    def m(self) -> int:
        return int(self.src.size)


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed, 64-bit seeds included."""
    if seed < 0 or seed >= 1 << 64:
        raise ValueError(f"seed {seed} is outside [0, 2**64)")
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def _kronecker(key, scale: int, m: int, a: float, b: float, c: float):
    """``m`` directed Kronecker edges over ``2**scale`` labels."""
    def level(i, carry):
        src, dst = carry
        u = jax.random.uniform(jax.random.fold_in(key, i), (m,))
        row = u >= a + b                           # quadrants C and D
        col = ((u >= a) & (u < a + b)) | (u >= a + b + c)   # B and D
        return (src | (row.astype(jnp.int32) << i),
                dst | (col.astype(jnp.int32) << i))
    zero = jnp.zeros((m,), jnp.int32)
    return jax.lax.fori_loop(0, scale, level, (zero, zero))


@functools.partial(jax.jit, static_argnames=(
    "scale", "edge_factor", "a", "b", "c"))
def _generate(key, *, scale, edge_factor, a, b, c):
    m = edge_factor << scale
    labels = 1 << scale
    k_edges, k_perm = jax.random.split(key)
    src, dst = _kronecker(k_edges, scale, m, a, b, c)
    perm = jax.random.permutation(k_perm, labels).astype(jnp.int32)
    src, dst = perm[src], perm[dst]
    # canonical undirected pair (hi, lo); self-loops sort last
    loop = src == dst
    hi = jnp.where(loop, _BIG, jnp.maximum(src, dst))
    lo = jnp.where(loop, _BIG, jnp.minimum(src, dst))
    idx = jnp.arange(m, dtype=jnp.int32)
    hi, lo, idx, flipped = jax.lax.sort(
        (hi, lo, idx, src > dst), num_keys=3)
    first = jnp.concatenate([jnp.ones((1,), bool),
                             (hi[1:] != hi[:-1]) | (lo[1:] != lo[:-1])])
    valid = first & (hi != _BIG)
    present = jnp.zeros((labels + 1,), bool)
    present = present.at[jnp.where(valid, lo, labels)].set(True)
    present = present.at[jnp.where(valid, hi, labels)].set(True)
    present = present[:labels]
    new_id = jnp.cumsum(present, dtype=jnp.int32) - 1
    hi_c = new_id[jnp.minimum(hi, labels - 1)]
    lo_c = new_id[jnp.minimum(lo, labels - 1)]
    # the kept pairs first, in generation order, as first drawn
    order = jnp.where(valid, idx, _BIG)
    _, src, dst = jax.lax.sort(
        (order, jnp.where(flipped, hi_c, lo_c),
         jnp.where(flipped, lo_c, hi_c)), num_keys=1)
    labels_c = jnp.where(present[perm], new_id[perm], -1)
    return {"n": present.sum(dtype=jnp.int32),
            "pairs": valid.sum(dtype=jnp.int32), "src": src, "dst": dst,
            "labels": labels_c}


def generate(seed: int, graph: dict) -> Graph:
    """Generate the undirected graph that a configuration's ``graph``
    section describes, and copy its rows to the host."""
    if graph.get("generator") != "graph500" or graph.get("directed") \
            is not False:
        raise ValueError("only the undirected Graph500 generator is "
                         f"implemented, not {graph}")
    got = _generate(seed_key(seed), scale=int(graph["scale"]),
                    edge_factor=int(graph["edge_factor"]),
                    a=float(graph["a"]), b=float(graph["b"]),
                    c=float(graph["c"]))
    got = jax.device_get(got)
    pairs = int(got["pairs"])
    src, dst = got["src"][:pairs], got["dst"][:pairs]
    return Graph(n=int(got["n"]), src=np.concatenate([src, dst]),
                 dst=np.concatenate([dst, src]), labels=got["labels"])
