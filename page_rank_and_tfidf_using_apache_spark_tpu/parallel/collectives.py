"""Thin named wrappers over the XLA collectives this framework uses.

Reference counterpart (SURVEY.md §5.8): the sort-based shuffle + netty
transport + torrent broadcast stack under every ``reduceByKey``/``join``.
The rebuild's communication vocabulary is three collectives, all compiled
into the iteration program by XLA and scheduled on ICI/DCN:

- ``psum``          cross-chip combine (the shuffle-reduce; BASELINE.json:5
                    "allreduced over ICI via lax.psum"): the replicated
                    PageRank layout's one collective a step, the owned
                    layout's head combine, sharded TF-IDF's DF
- ``pmax``          cross-chip max (sharded HITS' normalization)
- ``butterfly_all_gather`` the owned layouts' boundary exchange, as
                    log2(D) ``ppermute`` rounds

Kept as a module so the communication surface is explicit, greppable, and
mockable in tests — not because the wrappers add logic.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def psum(x: jax.Array, axis: str) -> jax.Array:
    return lax.psum(x, axis)


def pmax(x: jax.Array, axis: str) -> jax.Array:
    """Cross-chip max (the normalization collective of sharded HITS)."""
    return lax.pmax(x, axis)


def butterfly_all_gather(block: jax.Array, axis: str) -> jax.Array:
    """Recursive-doubling (butterfly) all-gather over ``ppermute``: each
    device contributes its fixed-width ``[B, ...]`` block and ends holding
    the ``[D*B, ...]`` concatenation in device order — log₂(D) ``ppermute``
    rounds, round k carrying a 2^k·B payload, so total bytes sent per
    device are (D-1)·B entries, same as the tree-optimal all-gather.

    This is the ``owned`` strategy's sparse boundary exchange (ISSUE 15;
    *Sparse Allreduce*'s padded hub-set exchange expressed as the native
    backend-portable collective DrJAX motivates): the blocks are the
    fixed-width padded boundary buffers, so only cut-crossing entries — not
    the O(n) rank vector — ever cross the interconnect.

    After round k a device's filled rows are exactly its ALIGNED 2^k-row
    group (the partner's group differs in bit k, so the union stays one
    aligned block): both the send slice and the receive placement are
    ``dynamic_slice``/``dynamic_update_slice`` at traced offsets with
    static sizes, keeping every shape fixed across iterations.
    """
    d = axis_size(axis)
    if d == 1:
        return block
    i = lax.axis_index(axis)
    buf = jnp.zeros((d,) + block.shape, block.dtype)
    buf = lax.dynamic_update_index_in_dim(buf, block, i, 0)
    rounds = d.bit_length() - 1  # d is a power of two (mesh contract)
    for k in range(rounds):
        width = 1 << k
        base = (i >> k) << k  # my aligned 2^k-row group
        partner_base = base ^ width
        chunk = lax.dynamic_slice_in_dim(buf, base, width, axis=0)
        perm = [(j, j ^ width) for j in range(d)]
        recv = lax.ppermute(chunk, axis, perm)
        buf = lax.dynamic_update_slice_in_dim(buf, recv, partner_base, axis=0)
    return buf.reshape((d * block.shape[0],) + block.shape[1:])


def axis_size(axis: str) -> int:
    if hasattr(lax, "axis_size"):
        return lax.axis_size(axis)
    # Older jax has no lax.axis_size; psum of a non-tracer constant folds
    # eagerly to ``1 * axis_size``, the canonical pmap-era idiom.
    return lax.psum(1, axis)
