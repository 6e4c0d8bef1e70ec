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

The compaction has two forms, and :func:`runs_kernel` with
:func:`kernel_keys` says which a key takes:

- XLA's (a scatter of n/128 row marks, a gather of k rows of 128 lanes,
  9 ns an element on a v5e): the CPU suite, a mesh, keys selected side
  by side, small keys, and the oracle of the kernel's tests;
- the kernel (a TPU backend): ``ops/expand.py`` run backwards, a Pallas
  grid whose step holds a TILE of GROUP windows of 128 rows, which it
  reads, and a PART of CHUNK pieces of PIECE output slots, which it
  writes (:data:`GEOMETRY`). The steps are the (tile, part) pairs that can meet,
  in order (both ascend: at most tiles + parts - 1). Inside a step
  every WINDOW that holds slots of the part (it lies turned, lanes
  down and its rows along the lanes: XLA turns the key as it views it
  as rows) forms its leavers' mask elementwise from ``t`` and ``cut``
  (the rule :func:`leaving` states), their running count down each row
  by a product with a triangle of ones, and from it every row's leaver
  of rank 0, 1, ... (its integer image and its lane, by a masked sum
  of one term) for as many ranks as its fullest row holds. A PIECE
  finds each slot's row by two compares against the rows' first and
  last slots, and ONE product ``[48, 128] x [128, PIECE]`` fetches, for
  every slot, eight ranks of its row's leavers (four byte planes and
  the lane), the row's first slot and its number (0/1 against whole
  numbers under 2^8: exact in bfloat16 operands and float32 sums); the
  slot's own rank picks among the eight. No float value is ever
  multiplied, so an inf, a NaN, a denormal and ``-0.0`` arrive bit for
  bit.
"""

from __future__ import annotations

import functools
from typing import Optional

__all__ = ["topk_by_magnitude", "topk_of_keys", "topk_flat", "leaving",
           "runs_kernel", "kernel_keys", "SELECT_MIN_ELEMS"]

_LANES = 128
# a float32's magnitude as an integer: order of the images is order of
# the magnitudes
_MAGNITUDE_BITS = 0x7FFFFFFF
# keys of one size whose segments together hold no more elements than
# this are selected side by side (one batched pass over all of them);
# larger ones one after the other under one loop, so that no group's
# temporaries approach the size of the flat vector
_SIDE_BY_SIDE_ELEMS = 1 << 22
# the kernel's geometry: PIECE, the output slots one product fetches;
# GROUP, the windows of 128 rows in a grid step's tile of the key; CHUNK,
# the pieces in a grid step's part of the output (tests read it small)
GEOMETRY = (256, 32, 32)
# keys under this many elements keep XLA's compaction. The kernel is the
# faster form from 2^14 elements on a v5e (tools/select_kernel_bench.py
# crossover, ms a key under one loop: 0.109 against 0.117 there, 0.155
# against 0.194 at 2^19, 0.470 against 0.800 at 2^22, 3.25 against 6.45
# at 25.7M; PERF.md section 6, PR 63), but under 2^19 a key gains less
# than 0.04 ms and every key size is one more Mosaic call in the program
SELECT_MIN_ELEMS = 1 << 19


def topk_by_magnitude(x, k: int, kernel: bool = False):
    """The ``k`` entries of a float32 vector that are largest in
    magnitude: ``(positions, values, t, cut)``, positions ``k`` distinct
    ascending int32, ``values = x[positions]`` bit for bit; among equals
    at the k-th magnitude the lowest positions. ``-0.0`` counts as 0; a
    NaN would order above infinity. ``t`` and ``cut`` (int32 scalars)
    say the same as a rule, the one :func:`leaving` reads: the integer
    image of the k-th magnitude, and the position before which an
    element AT that magnitude is among the k. ``kernel``: the
    compaction's form (:func:`kernel_keys` decides; the result is the
    same to the bit)."""
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
    if kernel:
        from geomx_tpu.ops import pallas_interpret

        pos, image = _compactor(rows, k, pallas_interpret(), GEOMETRY)(
            x, count, jnp.stack([t, cut]))
        return pos, lax.bitcast_convert_type(image, jnp.float32), t, cut
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


def runs_kernel(v, size: int, mesh=None,
                forced: Optional[bool] = None) -> bool:
    """THE rule for the form of a key's compaction, asked of the flat
    vector ``v`` the key lies in and its ``size``: ``ops.kernel_form``
    (Pallas compiles, no mesh in play: the predicate
    ``expand.runs_kernel`` is) at :data:`SELECT_MIN_ELEMS` elements and
    more. ``forced`` is for tests: the answer itself."""
    from geomx_tpu.ops import kernel_form

    return kernel_form(v, size, SELECT_MIN_ELEMS, mesh, forced)


def _alike(sizes, ks):
    """Keys by shape: ``{(size, k): [key, ...]}``, in first-seen order."""
    alike = {}
    for i, shape in enumerate(zip(sizes, ks)):
        alike.setdefault(shape, []).append(i)
    return alike


def kernel_keys(v, sizes, ks, mesh=None):
    """The keys of :func:`topk_flat` whose compaction runs in the
    kernel: those of the size groups that are selected one key after
    the other (not side by side under ``vmap``) and that
    :func:`runs_kernel` lets. A function of what a trace sees (the
    backend, a mesh, the keys' static sizes); ``v`` may be a
    ``ShapeDtypeStruct``."""
    return sorted(
        i for (size, _k), members in _alike(sizes, ks).items()
        if size * len(members) > _SIDE_BY_SIDE_ELEMS
        and runs_kernel(v, size, mesh) for i in members)


def topk_of_keys(v, offsets, size: int, k: int, kernel: bool = False):
    """:func:`topk_by_magnitude` of the equal-sized keys that start at
    ``offsets`` of the flat vector ``v``: ``[len(offsets), k]``
    key-relative positions and their values, ``[len(offsets)]``
    thresholds and cuts, from ONE traced body whatever the number of
    keys. ``kernel``: the compaction's form, for keys selected one
    after the other."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    if len(offsets) * size <= _SIDE_BY_SIDE_ELEMS:
        segs = jnp.stack([v[o:o + size] for o in offsets])
        return jax.vmap(lambda seg: topk_by_magnitude(seg, k))(segs)
    # (XLA's form is asked for as it always was: what stands in for
    # topk_by_magnitude in a test takes a key and k)
    form = {"kernel": True} if kernel else {}
    return lax.map(
        lambda o: topk_by_magnitude(
            lax.dynamic_slice(v, (o,), (size,)), k, **form),
        jnp.asarray(offsets, jnp.int32))


def topk_flat(v, offsets, sizes, ks, mesh=None):
    """Per-key top-k of a flat vector: key ``i`` is ``v[offsets[i]:
    offsets[i] + sizes[i]]`` and gives ``ks[i]`` entries. Returns their
    model-flat int32 positions (keys in order, each key's ascending: in
    all ascending and distinct when the keys are in flat order), their
    values, and the rules of membership, keys of one size together:
    ``(members, t, cut)`` with a threshold and a key-relative cut for
    each of the keys ``members`` lists. Keys of one size share one
    traced selection, in the form :func:`kernel_keys` gives them
    (``mesh``: a caller's whose operands GSPMD shards over ``Auto``
    axes, which a trace does not show)."""
    import jax.numpy as jnp

    kernel = set(kernel_keys(v, sizes, ks, mesh))
    idx, vals, rules = [None] * len(sizes), [None] * len(sizes), []
    for (size, k), members in _alike(sizes, ks).items():
        pos, val, t, cut = topk_of_keys(
            v, [offsets[i] for i in members], size, k,
            kernel=members[0] in kernel)
        for g, i in enumerate(members):
            idx[i], vals[i] = pos[g] + jnp.int32(offsets[i]), val[g]
        rules.append((members, t, cut))
    return jnp.concatenate(idx), jnp.concatenate(vals), rules


# -- the compaction's kernel form -------------------------------------------

@functools.lru_cache(maxsize=None)
def _compactor(rows: int, k: int, interpret: bool, geometry):
    """The kernel form of the compaction of a key of ``rows`` rows,
    ``(x [n] float32, count [rows], rule = [t, cut]) -> (positions [k],
    images [k])`` (``interpret``: Pallas's interpret mode, as
    ``ops.pallas_interpret()`` said). A ``jit`` of its own, so that a
    program lowers the kernel once and calls it. ``geometry``:
    :data:`GEOMETRY` when the caller was traced."""
    import jax

    return jax.jit(functools.partial(_compacted, k=k, interpret=interpret,
                                     geometry=geometry))


def _steps(tile_slot, ntiles: int, nparts: int, part: int):
    """The tile of every grid step (the step's part is the step less its
    tile): ``ops/expand.py``'s table with rows read and the list
    written. ``tile_slot[g]`` is the first output slot of tile ``g``'s
    rows, so a part starts in the last tile that starts at or before
    its first slot. Tile ``g`` takes the parts from the last one that
    starts before it (which tile ``g - 1`` ended on) to the last one
    that starts in it; every part starts in some tile, so the steps are
    exactly tiles + parts - 1. Counted by comparing (both lists are
    short), not scattered."""
    import jax.numpy as jnp

    ids = jnp.arange(ntiles, dtype=jnp.int32)
    starts_in = jnp.sum(
        tile_slot[None, :] <= jnp.arange(nparts, dtype=jnp.int32)[:, None]
        * part, axis=1, dtype=jnp.int32) - 1                    # [nparts]
    last = jnp.maximum(jnp.sum(starts_in[None, :] <= ids[:, None], axis=1,
                               dtype=jnp.int32) - 1, 0)
    first_step = jnp.concatenate([jnp.zeros(1, jnp.int32), last[:-1]]) + ids
    steps = jnp.arange(ntiles + nparts - 1, dtype=jnp.int32)
    return jnp.sum(first_step[None, :] <= steps[:, None], axis=1,
                   dtype=jnp.int32) - 1


def _compacted(x, count, rule, *, k, interpret, geometry):
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    piece, group, chunk = geometry
    rows = count.shape[0]
    tile_rows, part = group * _LANES, chunk * piece
    ntiles, nparts = -(-rows // tile_rows), -(-k // part)
    # the key's image in whole windows of 128 rows, every window turned
    # (lanes down, its rows along the lanes): the copy that views the
    # key as rows is made anyway, and turned here the kernel need not
    windows = -(-rows // _LANES)
    turned = lax.bitcast_convert_type(
        jnp.pad(x, (0, windows * _LANES * _LANES - x.shape[0])).reshape(
            windows, _LANES, _LANES).swapaxes(1, 2).reshape(-1, _LANES),
        jnp.int32)
    # row r's slots are [first[r], first[r] + count[r]); the rows added
    # to fill the last tile hold none. Running sums in two levels, a
    # window's 128 rows by a product with a triangle of ones (counts to
    # 128 in bfloat16 operands, float32 sums: exact) and the windows'
    # totals by a short cumsum: XLA takes seconds to compile a long one
    count = jnp.pad(count, (0, ntiles * tile_rows - rows)).reshape(
        -1, _LANES)                                     # a window a row
    lane = jnp.arange(_LANES, dtype=jnp.int32)
    within = jnp.dot(count.astype(jnp.bfloat16),
                     (lane[:, None] <= lane[None, :]).astype(jnp.bfloat16),
                     preferred_element_type=jnp.float32).astype(jnp.int32)
    # a window's first slot (and the list's end), and the most leavers
    # one of its rows holds
    window_slot = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), jnp.cumsum(within[:, -1])])
    window_most = jnp.max(count, axis=1)
    last = window_slot[:-1, None] + within
    first = last - count
    tile_of = _steps(window_slot[:-1:group], ntiles, nparts, part)

    def kernel(tile_of, window_slot, window_most, rule, turned_ref,
               first_ref, last_ref, pos_ref, image_ref, found_ref, lane_ref):
        step = pl.program_id(0)
        g = tile_of[step]
        c = step - g
        t, cut = rule[0], rule[1]
        before = jnp.maximum(step - 1, 0)

        @pl.when((step == 0) | (before - tile_of[before] != c))
        def _():
            pos_ref[...] = jnp.zeros_like(pos_ref)
            image_ref[...] = jnp.zeros_like(image_ref)

        # a window lies turned: lanes down, its rows along the lanes
        lane_ids = lax.broadcasted_iota(jnp.int32, (_LANES, _LANES), 0)
        row_ids = lax.broadcasted_iota(jnp.int32, (_LANES, _LANES), 1)
        where_ids = row_ids * _LANES + lane_ids
        upto = (row_ids <= lane_ids).astype(jnp.bfloat16)
        slot_ids = lax.broadcasted_iota(jnp.int32, (piece, _LANES), 0)
        in_piece = lax.broadcasted_iota(jnp.int32, (1, piece), 1)
        eighth = lax.broadcasted_iota(jnp.int32, (8, _LANES), 0)
        rank_ids = lax.broadcasted_iota(
            jnp.int32, (8, piece), 0).astype(jnp.float32)
        row_of = lax.broadcasted_iota(
            jnp.int32, (8, _LANES), 1).astype(jnp.float32)

        def a_window(w, _):
            at = g * group + w
            slots = window_slot[at], window_slot[at + 1]
            # the pieces of this part that hold slots of the window
            pieces = (jnp.maximum(slots[0] // piece, c * chunk),
                      jnp.minimum((slots[1] + piece - 1) // piece,
                                  (c + 1) * chunk))

            @pl.when((slots[1] > slots[0]) & (pieces[1] > pieces[0]))
            def _():
                # [lane, row]
                turned = turned_ref[pl.ds(
                    pl.multiple_of(w * _LANES, _LANES), _LANES), :]
                # the rule `leaving` states: over t, or at t before cut
                ties = (where_ids < cut - at * (_LANES * _LANES)
                        ).astype(jnp.int32)
                leaves = (turned & _MAGNITUDE_BITS) > t - ties
                # how many leavers a row holds up to each lane, and
                # only on the leavers: the row's leaver of rank j is
                # the lane that reads j + 1
                ranks = jnp.where(leaves, jnp.dot(
                    upto, leaves.astype(jnp.bfloat16),
                    preferred_element_type=jnp.float32), 0.0)
                most = window_most[at]

                def a_rank(j, _):
                    # every row's leaver of rank j, image and lane (one
                    # term a sum: exact), rows along the lanes
                    its = ranks == (j + 1).astype(jnp.float32)
                    found_ref[pl.ds(j, 1), :] = jnp.sum(
                        jnp.where(its, turned, 0), axis=0, keepdims=True)
                    lane_ref[pl.ds(j, 1), :] = jnp.sum(
                        jnp.where(its, lane_ids, 0), axis=0, keepdims=True)

                lax.fori_loop(0, most, a_rank, None)
                first = first_ref[pl.ds(w, 1), :]               # [1, rows]
                last = last_ref[pl.ds(w, 1), :]

                def a_piece(p, _):
                    # [slot, row] is 1 where the slot is the row's
                    slot = slot_ids + p * piece
                    its = ((first <= slot) & (slot < last)
                           ).astype(jnp.bfloat16)
                    # beside eight ranks' bytes and lanes, a row's
                    # first slot from the piece's (a matching row
                    # starts under 128 slots before the piece and
                    # inside it: whole numbers bfloat16 holds), its
                    # number, and a one
                    beside = jnp.where(
                        eighth == 0, jnp.clip(first - p * piece, -_LANES,
                                              piece - 1).astype(jnp.float32),
                        jnp.where(eighth == 1, row_of, 1.0))

                    def eight_ranks(e, _):
                        ranked = pl.ds(pl.multiple_of(e * 8, 8), 8)
                        found = found_ref[ranked, :]
                        held = jnp.concatenate([
                            (lax.shift_right_logical(found, 8 * b) & 255
                             ).astype(jnp.float32) for b in range(4)]
                            + [lane_ref[ranked, :].astype(jnp.float32),
                               beside]).astype(jnp.bfloat16)    # [48, rows]
                        got = lax.dot_general(
                            held, its, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [48, piece]
                        rank = (in_piece - e * 8).astype(
                            jnp.float32) - got[40:41]
                        mine = (rank_ids == rank) & (got[42:43] > 0.0)

                        def picked(at_rows):
                            return jnp.sum(
                                jnp.where(mine, got[at_rows:at_rows + 8],
                                          0.0), axis=0, keepdims=True
                            ).astype(jnp.int32)                 # [1, piece]

                        hit = jnp.sum(mine.astype(jnp.int32), axis=0,
                                      keepdims=True) > 0
                        row = got[41:42].astype(jnp.int32)
                        where = jnp.where(
                            hit, (at * _LANES + row) * _LANES + picked(32),
                            0)
                        image = picked(0) | lax.shift_left(picked(8), 8) \
                            | lax.shift_left(picked(16), 16) \
                            | lax.shift_left(picked(24), 24)
                        in_part = pl.ds(p - c * chunk, 1)
                        pos_ref[in_part, :] = pos_ref[in_part, :] | where
                        image_ref[in_part, :] = image_ref[in_part, :] | image

                    lax.fori_loop(0, (most + 7) // 8, eight_ranks, None)

                lax.fori_loop(pieces[0], pieces[1], a_piece, None)

        lax.fori_loop(0, group, a_window, None)

    params = {} if interpret else dict(
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)))
    in_tile = lambda s, tile_of, *_: (tile_of[s], 0)
    out_part = lambda s, tile_of, *_: (s - tile_of[s], 0)
    pos, image = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(ntiles + nparts - 1,),
            in_specs=[pl.BlockSpec((tile_rows, _LANES), in_tile),
                      pl.BlockSpec((group, _LANES), in_tile),
                      pl.BlockSpec((group, _LANES), in_tile)],
            out_specs=[pl.BlockSpec((chunk, piece), out_part)] * 2,
            scratch_shapes=[pltpu.VMEM((_LANES, _LANES), jnp.int32)] * 2),
        out_shape=[jax.ShapeDtypeStruct((nparts * chunk, piece),
                                        jnp.int32)] * 2,
        interpret=interpret, **params,
    )(tile_of, window_slot, window_most, rule, turned, first, last)
    return pos.reshape(-1)[:k], image.reshape(-1)[:k]
