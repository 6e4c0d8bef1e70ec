"""A sorted sparse list becomes a dense vector, without a scatter.

``dense_from_sorted(values, positions, size)`` is
``zeros(size).at[positions].add(values)`` for positions that ascend
strictly, with every position at or past ``size`` dropped: what the
device trainer's ``apply_chunk`` makes of a round's aggregate. XLA's
scatter on a TPU walks the list a slot at a time (9 ns a slot on a v5e,
pads included: 29 ms for 3.26M slots into 163M elements, 35 times what
writing the dense result costs). But a list that ascends is the
selection's compaction (``ops/select.py``) run backwards: viewed as
rows of 128 lanes, every run of rows owns one contiguous run of the
list, so the list can be expanded tile by tile in one streaming pass.

Two forms, and :func:`runs_kernel` says which:

- the scatter (:func:`scattered`): the CPU suite, a mesh, a short list,
  and the oracle of the kernel's tests;
- the kernel (a TPU backend): a Pallas grid whose step holds a TILE of
  ``GROUP * ROWS`` rows and a PART of ``CHUNK`` pieces of ``PIECE``
  slots. The steps are the (tile, part) pairs that can meet, in order
  (both ascend, so they are at most tiles + parts - 1: a tile takes its
  parts one after the other, and the part two tiles share is a step of
  each). Inside a step every piece writes the rows it can hold slots
  of, from its first slot's row to the row before the next piece's
  first slot, a WINDOW of ``ROWS`` rows at a time (at 2.6 slots a row,
  the cells' density, 256 slots span about 100 rows: one window). A
  piece becomes a window's rows on the MXU:
  ``out[r, l] = sum_s [row_s == r] * byte_s * [lane_s == l]`` over the
  piece's slots, the bytes those of the value's INTEGER image, two to
  a product (``b0 + 256 b1`` and ``b2 + 256 b3``: whole numbers under
  2^16, exact in bfloat16 operands and float32 sums, and a position
  receives at most one slot). No float value is ever multiplied, so an
  inf or a NaN lands on its own position and nowhere else; a slot
  outside the window matches no row and adds nothing, so a piece that
  straddles tiles or windows needs no cutting and pads need no care;
  and a window ORs into the tile, so rows met twice take no harm.

Bit for bit the scatter's result: there ``0.0 + -0.0`` is ``+0.0`` and
the add flushes a denormal, so an image with no exponent bit is cleared
here, in the elementwise pass that reads the kernel's output.
"""

from __future__ import annotations

import functools
from typing import Optional

__all__ = ["dense_from_sorted", "scattered", "runs_kernel",
           "EXPAND_MIN_SLOTS"]

_LANES = 128
ROWS = 128      # rows a window: what one product writes
PIECE = 256     # slots a piece: what one product reads
GROUP = 32      # windows' worth of rows in a grid step's output tile
CHUNK = 32      # pieces in a grid step's part of the list
_FAR = (1 << 31) - 1
# under this many slots the scatter stays. The kernel is the faster
# form from 2^13 slots on a v5e (tools/expand_bench.py crossover: 0.63
# against 0.64 ms there, 1.0 against 1.9 at 2^17, 1.8 against 5.6 at
# 2^19; PERF.md section 6, PR 61), but under 2^19 an apply gains less
# than 4 ms and every executable with the kernel in it costs about 6 s
# of a cold set-up: a round cut into many small chunks (a declared
# link: sixteen executables a worker) keeps them on the scatter
EXPAND_MIN_SLOTS = 1 << 19


def runs_kernel(positions, mesh=None, forced: Optional[bool] = None) -> bool:
    """THE rule for the form of :func:`dense_from_sorted`:
    ``ops.kernel_form`` (Pallas compiles, no mesh in play) for a list of
    at least :data:`EXPAND_MIN_SLOTS` slots; the scatter otherwise. One
    algorithm that wants another form at another size. ``forced`` is
    for tests: the answer itself."""
    from geomx_tpu.ops import kernel_form

    return kernel_form(positions, positions.shape[0], EXPAND_MIN_SLOTS,
                       mesh, forced)


def dense_from_sorted(values, positions, size: int, mesh=None):
    """float32 ``[size]`` holding ``values[s]`` at ``positions[s]`` and
    +0.0 elsewhere. ``positions`` int32, strictly ascending; a position
    at or past ``size`` is dropped with its value (the pads of an
    upload). In the form :func:`runs_kernel` gives."""
    if runs_kernel(positions, mesh):
        from geomx_tpu.ops import pallas_interpret

        return _expander(size, pallas_interpret())(values, positions)
    return scattered(values, positions, size)


def scattered(values, positions, size: int):
    """The scatter-add into zeros. Told that the positions ascend, are
    distinct and may lie outside, XLA puts no sort before it."""
    import jax.numpy as jnp

    return jnp.zeros((size,), values.dtype).at[positions].add(
        values, indices_are_sorted=True, unique_indices=True, mode="drop")


def _steps(pos, ntiles: int, nparts: int, tile: int, part: int):
    """The tile of every grid step (the step's part is the step less its
    tile). Tile ``g`` takes the parts from the last one that starts
    before its first element (which tile ``g - 1`` ended on) to the last
    one that starts before its end: counted, not searched, because a
    part's first position says which tile it starts in. Steps left over
    (the bound is reached only when every part but one straddles) fall
    to the last tile with parts past its last, which hold nothing for
    it."""
    import jax.numpy as jnp
    from jax import lax

    starts_in = pos[::part] // tile                     # [nparts]
    before_end = jnp.cumsum(jnp.zeros(ntiles, jnp.int32).at[starts_in].add(
        1, indices_are_sorted=True, mode="drop"))
    last = jnp.maximum(before_end - 1, 0)
    ids = jnp.arange(ntiles, dtype=jnp.int32)
    first_step = jnp.concatenate([jnp.zeros(1, jnp.int32), last[:-1]]) + ids
    return lax.cummax(jnp.zeros(ntiles + nparts - 1, jnp.int32).at[
        first_step].max(ids, indices_are_sorted=True, unique_indices=True))


@functools.lru_cache(maxsize=None)
def _expander(size: int, interpret: bool,
              geometry=(ROWS, PIECE, GROUP, CHUNK)):
    """The kernel form for ``size`` elements, ``(values, positions) ->
    dense`` (``interpret``: Pallas's interpret mode, as
    ``ops.pallas_interpret()`` said). A ``jit`` of its own, so that a
    program lowers the kernel once and calls it. ``geometry`` is for
    ``tools/expand_bench.py``: the shipped one is the module's."""
    import jax

    return jax.jit(functools.partial(_expanded, size=size,
                                     interpret=interpret,
                                     geometry=geometry))


def _expanded(values, positions, *, size, interpret, geometry):
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows, piece, group, chunk = geometry
    slots = positions.shape[0]
    if slots == 0 or size == 0:
        return jnp.zeros((size,), jnp.float32)
    tile_rows = group * rows
    tile, part = tile_rows * _LANES, chunk * piece
    ntiles, nparts = -(-size // tile), -(-slots // part)
    if ntiles * tile > _FAR:
        raise ValueError(f"{size} elements in tiles of {tile} pass 2^31")
    # the list in whole parts; what is added lies past every tile
    pos = jnp.pad(positions, (0, nparts * part - slots),
                  constant_values=_FAR)
    image = jnp.pad(lax.bitcast_convert_type(values, jnp.int32),
                    (0, nparts * part - slots))
    # where every piece starts, and an end for the last one
    first = jnp.concatenate([pos[::piece], jnp.full(1, _FAR, jnp.int32)])
    tile_of = _steps(pos, ntiles, nparts, tile, part)

    def kernel(tile_of, first, pos_ref, image_ref, out_ref):
        t = pl.program_id(0)
        g = tile_of[t]
        c = t - g
        tile_start = g * tile

        @pl.when((t == 0) | (tile_of[jnp.maximum(t - 1, 0)] != g))
        def _():
            out_ref[...] = jnp.zeros_like(out_ref)

        row_ids = lax.broadcasted_iota(jnp.int32, (rows, piece), 0)
        lane_ids = lax.broadcasted_iota(jnp.int32, (_LANES, piece), 0)

        def a_piece(j, _):
            # the tile's rows this piece can hold slots of: from its
            # first slot's to the row before the next piece's first
            p = c * chunk + j
            top = jnp.maximum(
                lax.shift_right_arithmetic(first[p] - tile_start, 7), 0)
            bottom = jnp.minimum(
                lax.shift_right_arithmetic(first[p + 1] - 1 - tile_start, 7),
                tile_rows - 1)
            top = top - top % 8
            at_piece = pl.ds(j, 1)
            rel = pos_ref[at_piece, :] - tile_start             # [1, piece]
            bits = image_ref[at_piece, :]
            row = lax.shift_right_arithmetic(rel, 7)
            lane = (lane_ids == (rel & (_LANES - 1))).astype(jnp.bfloat16)
            lanes = jnp.concatenate([lane, lane], axis=1)
            b0, b1, b2, b3 = (
                (lax.shift_right_arithmetic(bits, 8 * k) & 255).astype(
                    jnp.float32) * (256.0 if k % 2 else 1.0)
                for k in range(4))

            def a_window(k, _):
                # ``rows`` rows of the tile at a time (the last window
                # moved up to end with the tile: a row met twice gets
                # the same bits twice)
                w = pl.multiple_of(
                    jnp.minimum(top + k * rows, tile_rows - rows), 8)
                hit = row_ids == row - w

                def held(b):
                    return jnp.where(hit, b, 0.0).astype(jnp.bfloat16)

                halves = lax.dot_general(
                    jnp.concatenate([
                        jnp.concatenate([held(b0), held(b1)], axis=1),
                        jnp.concatenate([held(b2), held(b3)], axis=1)]),
                    lanes, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)     # [2 rows, 128]
                found = halves[:rows].astype(jnp.int32) | lax.shift_left(
                    halves[rows:].astype(jnp.int32), 16)
                out_ref[pl.ds(w, rows), :] = out_ref[pl.ds(w, rows), :] | found

            lax.fori_loop(0, (bottom - top + rows) // rows, a_window, None)

        lax.fori_loop(0, chunk, a_piece, None)

    params = {} if interpret else dict(
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(ntiles + nparts - 1,),
            in_specs=[pl.BlockSpec((chunk, piece),
                                   lambda t, tile_of, first:
                                   (t - tile_of[t], 0))] * 2,
            out_specs=pl.BlockSpec((tile_rows, _LANES),
                                   lambda t, tile_of, first:
                                   (tile_of[t], 0))),
        out_shape=jax.ShapeDtypeStruct((ntiles * tile_rows, _LANES),
                                       jnp.int32),
        interpret=interpret, **params,
    )(tile_of, first, pos.reshape(-1, piece), image.reshape(-1, piece))
    out = out.reshape(-1)[:size]
    # the scatter ADDS to +0.0, and a float add flushes a denormal: -0.0
    # and whatever has no exponent land as +0.0
    return lax.bitcast_convert_type(
        jnp.where(out & jnp.int32(0x7F800000) == 0, 0, out), jnp.float32)
