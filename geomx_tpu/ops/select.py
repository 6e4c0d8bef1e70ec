"""Exact top-k by magnitude on the device, without a sort.

``lax.top_k`` sorts all n (value, index) pairs of a key to hand over 1%
of them. Here the k-th largest magnitude is found by counting — 16
passes over the key, two bits a pass of a non-negative float's integer
image, where integer order is float order — and the k positions at or
over it are then written down by a compaction whose gathers and
scatters move k and n/128 elements, never n. The positions are the set
``lax.top_k(abs(x), k)`` returns (ties at the k-th value go to the
lowest indices, as there), in ascending order, and their values come
out of the rows the compaction has gathered anyway. What decided a
position's membership is two numbers a key, the threshold and where the
ties at it stop leaving: they are handed out beside the positions, so
that a caller can write to all selected positions in one dense pass
(:func:`leaving`) where a scatter would write them one by one.

The key is viewed as rows of 128 lanes: row counts and their running
sums say which row every output slot falls into, and only those rows
are looked at lane by lane.
"""

from __future__ import annotations

__all__ = ["topk_by_magnitude", "topk_of_keys", "topk_flat", "leaving"]

_LANES = 128
# a float32's magnitude as an integer: order of the images is order of
# the magnitudes
_MAGNITUDE_BITS = 0x7FFFFFFF
# keys of one size whose segments together hold no more elements than
# this are selected side by side (one batched pass over all of them);
# larger ones one after the other under one loop, so that no group's
# temporaries approach the size of the flat vector
_SIDE_BY_SIDE_ELEMS = 1 << 22


def topk_by_magnitude(x, k: int):
    """The ``k`` entries of a float32 vector that are largest in
    magnitude: ``(positions, values, t, cut)``, positions ``k`` distinct
    ascending int32, ``values = x[positions]`` bit for bit; among equals
    at the k-th magnitude the lowest positions. ``-0.0`` counts as 0; a
    NaN would order above infinity. ``t`` and ``cut`` (int32 scalars)
    say the same as a rule, the one :func:`leaving` reads: the integer
    image of the k-th magnitude, and the position before which an
    element AT that magnitude is among the k."""
    import jax.numpy as jnp
    from jax import lax

    n = x.shape[0]
    if not 1 <= k <= n:
        raise ValueError(f"top-{k} of {n} elements")
    rows = -(-n // _LANES)
    # the pad is zeros at the end: at most a tie at magnitude 0, which
    # the real zeros before it win, so it is never selected
    raw = lax.bitcast_convert_type(
        jnp.pad(x, (0, rows * _LANES - n)).reshape(rows, _LANES), jnp.int32)
    magnitude_bits = jnp.int32(_MAGNITUDE_BITS)
    bits = raw & magnitude_bits

    # t: the largest value with count(bits >= t) >= k. Bit 30 first,
    # then two bits a pass: the three counts read the key once, and as
    # many candidates hold as the two bits' value says
    def holds(cand):
        return (jnp.sum(bits >= cand) >= k).astype(jnp.int32)

    def two_bits(i, t):
        shift = 28 - 2 * i
        return t | lax.shift_left(
            sum(holds(t | lax.shift_left(jnp.int32(j), shift))
                for j in (1, 2, 3)), shift)

    t = lax.fori_loop(0, 15, two_bits,
                      lax.shift_left(holds(jnp.int32(1 << 30)), 30))

    # every element over t leaves, and of those at t the first
    # ``need`` in index order: whole rows of them up to row ``edge``,
    # which gives what is still missing
    over_row = jnp.sum(bits > t, axis=1, dtype=jnp.int32)
    at_row = jnp.sum(bits == t, axis=1, dtype=jnp.int32)
    need = k - jnp.sum(over_row)
    at_upto = jnp.cumsum(at_row)
    whole = at_upto <= need
    edge = jnp.sum(whole, dtype=jnp.int32)
    edge_takes = need - jnp.max(jnp.where(whole, at_upto, 0))
    # the ties that leave lie before one position: in row ``edge`` the
    # lanes that fewer than ``edge_takes`` ties precede (past the last
    # row when every tie leaves: ``edge_takes`` is 0 there)
    at_edge = (lax.dynamic_index_in_dim(
        bits, jnp.minimum(edge, rows - 1), keepdims=False) == t
               ).astype(jnp.int32)
    cut = edge * _LANES + jnp.sum(
        jnp.cumsum(at_edge) - at_edge < edge_takes, dtype=jnp.int32)

    def ties_leaving(r, every):
        return jnp.where(r < edge, every,
                         jnp.where(r == edge, edge_takes, 0))

    row_ids = jnp.arange(rows, dtype=jnp.int32)
    count = over_row + ties_leaving(row_ids, at_row)
    start = jnp.cumsum(count) - count           # sums to k

    # the row of every output slot: each non-empty row marks its first
    # slot (an empty row shares its start with the next non-empty one,
    # which wins the max; trailing empty rows start at k and drop),
    # and a running maximum carries the mark to the row's other slots
    slot = jnp.arange(k, dtype=jnp.int32)
    row = lax.cummax(jnp.zeros(k, jnp.int32).at[start].max(
        row_ids, mode="drop", indices_are_sorted=True))
    first = jnp.concatenate([jnp.ones(1, bool), row[1:] != row[:-1]])
    rank = slot - lax.cummax(jnp.where(first, slot, 0))

    # those rows lane by lane; running counts along 128 lanes are a
    # product with a triangle of ones (0/1 operands, float32 sums:
    # exact)
    lane = jnp.arange(_LANES, dtype=jnp.int32)
    before = (lane[:, None] < lane[None, :]).astype(jnp.bfloat16)
    upto = (lane[:, None] <= lane[None, :]).astype(jnp.bfloat16)

    def running(mask, triangle):
        return jnp.dot(mask.astype(jnp.bfloat16), triangle,
                       preferred_element_type=jnp.float32)

    picked = raw[row]                           # [k, 128]
    at = (picked & magnitude_bits) == t
    takes = ties_leaving(row, _LANES)[:, None].astype(jnp.float32)
    leaves = ((picked & magnitude_bits) > t) | (
        at & (running(at, before) < takes))
    # slot ``rank`` of its row is the lane that that many leavers precede
    lane_of = jnp.sum(running(leaves, upto)
                      <= rank[:, None].astype(jnp.float32),
                      axis=1, dtype=jnp.int32)
    # its value out of the row already here (one term, so exact), not by
    # a second gather
    value = jnp.sum(jnp.where(lane[None, :] == lane_of[:, None], picked, 0),
                    axis=1)
    return (row * _LANES + lane_of,
            lax.bitcast_convert_type(value, jnp.float32), t, cut)


def leaving(x, t, cut):
    """The mask of the positions :func:`topk_by_magnitude` returned for
    ``x`` beside ``t`` and ``cut``: elementwise, so a consumer's fusion
    can form it as it reads ``x``."""
    import jax.numpy as jnp
    from jax import lax

    bits = lax.bitcast_convert_type(x, jnp.int32) & jnp.int32(
        _MAGNITUDE_BITS)
    return (bits > t) | (
        (bits == t) & (jnp.arange(x.shape[0], dtype=jnp.int32) < cut))


def topk_of_keys(v, offsets, size: int, k: int):
    """:func:`topk_by_magnitude` of the equal-sized keys that start at
    ``offsets`` of the flat vector ``v``: ``[len(offsets), k]``
    key-relative positions and their values, ``[len(offsets)]``
    thresholds and cuts, from ONE traced body whatever the number of
    keys."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    if len(offsets) * size <= _SIDE_BY_SIDE_ELEMS:
        segs = jnp.stack([v[o:o + size] for o in offsets])
        return jax.vmap(lambda seg: topk_by_magnitude(seg, k))(segs)
    return lax.map(
        lambda o: topk_by_magnitude(
            lax.dynamic_slice(v, (o,), (size,)), k),
        jnp.asarray(offsets, jnp.int32))


def topk_flat(v, offsets, sizes, ks):
    """Per-key top-k of a flat vector: key ``i`` is ``v[offsets[i]:
    offsets[i] + sizes[i]]`` and gives ``ks[i]`` entries. Returns their
    model-flat int32 positions (keys in order, each key's ascending: in
    all ascending and distinct when the keys are in flat order), their
    values, and the rules of membership, keys of one size together:
    ``(members, t, cut)`` with a threshold and a key-relative cut for
    each of the keys ``members`` lists. Keys of one size share one
    traced selection."""
    import jax.numpy as jnp

    alike = {}
    for i, shape in enumerate(zip(sizes, ks)):
        alike.setdefault(shape, []).append(i)
    idx, vals, rules = [None] * len(sizes), [None] * len(sizes), []
    for (size, k), members in alike.items():
        pos, val, t, cut = topk_of_keys(
            v, [offsets[i] for i in members], size, k)
        for g, i in enumerate(members):
            idx[i], vals[i] = pos[g] + jnp.int32(offsets[i]), val[g]
        rules.append((members, t, cut))
    return jnp.concatenate(idx), jnp.concatenate(vals), rules
