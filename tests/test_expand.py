"""``ops/expand.py``: the kernel form (Pallas in interpret mode here)
against the scatter-add it replaces, bit for bit by the integer image,
and the rule that picks the form."""

from functools import partial

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from geomx_tpu import ops
from geomx_tpu.ops import expand

SHIPPED = (expand.ROWS, expand.PIECE, expand.GROUP, expand.CHUNK)
# a tile of 8,192 elements and a part of 1,024 slots: many tiles, parts
# and windows at sizes an interpreted kernel walks in a second
SMALL = (16, 128, 4, 8)
TILE = SMALL[0] * 128 * SMALL[2]
FAR = (1 << 31) - 1


def _images(values, positions, size, geometry=SMALL):
    """(kernel, scatter) results as int32 images."""
    v = jnp.asarray(values, jnp.float32)
    p = jnp.asarray(positions, jnp.int32)
    got = expand._expander(size, True, geometry)(v, p)
    want = expand.scattered(v, p, size)
    assert got.shape == want.shape == (size,) and got.dtype == jnp.float32
    return np.asarray(got).view(np.int32), np.asarray(want).view(np.int32)


def _upload(size, slots, real, seed=0, span=None, start=0):
    """A list as the trainer's ``_chunk_up`` leaves it: ``real`` entries
    at distinct ascending positions in ``[start, start + span)``, then
    pads, slot ``s`` at ``size + s`` with value 0.0."""
    rng = np.random.default_rng(seed)
    span = size - start if span is None else span
    pos = start + np.sort(rng.choice(span, real, replace=False))
    positions = np.concatenate([pos, size + np.arange(real, slots)])
    values = np.zeros(slots, np.float32)
    values[:real] = rng.standard_normal(real).astype(np.float32)
    return values, positions.astype(np.int32)


CASES = {
    # size, slots, real entries, and where they lie
    "whole tiles": dict(size=4 * TILE, slots=3000, real=2700),
    "a tile and a bit": dict(size=TILE + 1, slots=600, real=500),
    "one element short of a tile": dict(size=TILE - 1, slots=600, real=600),
    "under a row": dict(size=100, slots=64, real=40),
    "one element": dict(size=1, slots=4, real=1),
    "off any multiple": dict(size=100_001, slots=2049, real=2049),
    "all pads": dict(size=3 * TILE + 77, slots=2500, real=0),
    "every slot real": dict(size=4096, slots=4096, real=4096),
    "one slot": dict(size=5 * TILE, slots=1, real=1),
    "all in one tile": dict(size=6 * TILE, slots=5000, real=4800,
                            start=2 * TILE + 300, span=TILE - 300),
    "all in one window": dict(size=6 * TILE, slots=2500, real=2000,
                              start=3 * TILE + 128, span=16 * 128),
    "all in one row's reach": dict(size=2 * TILE, slots=1500, real=128,
                                   start=TILE + 128, span=128),
    "a piece across many windows": dict(size=40 * TILE, slots=300,
                                        real=290),
    "a piece across many tiles": dict(size=400 * TILE, slots=128,
                                      real=100),
    "more parts than tiles": dict(size=TILE + 5, slots=7000, real=6000),
    "the last tile alone": dict(size=8 * TILE, slots=3000, real=2000,
                                start=7 * TILE),
    "the first tile alone": dict(size=8 * TILE, slots=3000, real=2000,
                                 span=TILE),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_kernel_is_the_scatter_bit_for_bit(case):
    kw = dict(CASES[case])
    size = kw.pop("size")
    values, positions = _upload(size, seed=len(case), **kw)
    got, want = _images(values, positions, size)
    np.testing.assert_array_equal(got, want)
    assert np.count_nonzero(got) == np.count_nonzero(values)


@pytest.mark.parametrize("size,slots,real", [
    (expand.ROWS * 128 * expand.GROUP + 12_345, 20_000, 18_000),
    (3 * expand.ROWS * 128 * expand.GROUP, 9_000, 100),
    (70_000, 1_400, 1_400),
])
def test_the_shipped_geometry(size, slots, real):
    values, positions = _upload(size, slots, real, seed=size % 97)
    got, want = _images(values, positions, size, SHIPPED)
    np.testing.assert_array_equal(got, want)


def test_an_empty_list_is_zeros():
    got, want = _images(np.zeros(0, np.float32), np.zeros(0, np.int32), 777)
    np.testing.assert_array_equal(got, want)
    assert not got.any()


def test_the_ends_of_the_vector_and_the_pads_beside_them():
    """Slots at position 0 and ``size - 1``; pads at ``size`` itself
    (inside the last tile's rows, past the vector) and far past it."""
    size = 2 * TILE + 300
    positions = np.array([0, 1, 127, 128, size - 2, size - 1,
                          size, size + 1, 3 * TILE - 1, 3 * TILE,
                          1 << 30, FAR - 1, FAR], np.int32)
    values = np.arange(1, len(positions) + 1, dtype=np.float32)
    got, want = _images(values, positions, size)
    np.testing.assert_array_equal(got, want)
    assert np.count_nonzero(got) == 6
    assert got.view(np.float32)[size - 1] == 6.0


def test_pads_near_the_end_of_int32():
    """What a chunk whose elements and slots together approach 2^31
    would upload: pads that ascend to the last int32."""
    size = 3 * TILE + 5
    values, positions = _upload(size, 1500, 1000, seed=5)
    positions[1000:] = FAR - np.arange(500)[::-1]
    got, want = _images(values, positions, size)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("odd", [-0.0, np.inf, -np.inf, np.nan,
                                 np.float32(1e-45), np.float32(-1e-39),
                                 np.float32(-3e38)])
def test_an_odd_value_lands_on_its_position_and_nowhere_else(odd):
    """No float product touches a value: an inf or a NaN in a piece must
    not reach the piece's other positions (0 x inf); -0.0 and a denormal
    land as the scatter's add to +0.0 lands them, +0.0."""
    size = 3 * TILE
    values, positions = _upload(size, 2600, 2500, seed=11)
    at = [0, 1, 1249, 2499]
    values[at] = odd
    got, want = _images(values, positions, size)
    np.testing.assert_array_equal(got, want)
    dense = got.view(np.float32)
    ordinary = np.ones(size, bool)
    ordinary[positions[at]] = False
    assert np.isfinite(dense[ordinary]).all()
    flushed = abs(odd) < np.finfo(np.float32).tiny
    assert np.count_nonzero(dense[ordinary]) == np.count_nonzero(values) - (
        0 if odd == 0 else len(at))
    if flushed:
        assert not got[positions[at]].any()       # +0.0, not -0.0
    else:
        np.testing.assert_array_equal(
            got[positions[at]], np.full(4, odd, np.float32).view(np.int32))


def test_every_bit_of_a_value_arrives():
    """Images that exercise each of the four bytes and both halves."""
    size = TILE + 999
    images = np.array([0x00800001, 0x008000FF, 0x0080FF00, 0x00FF0000,
                       0x7F000000, 0x7FFFFFFF, 0x01020304, -1,
                       0x00808000, 0x00810000, -(1 << 31) + (1 << 23),
                       0x0080FFFF], np.int64).astype(np.int32)
    positions = (np.arange(len(images)) * 701 + 3).astype(np.int32)
    v = jnp.asarray(images.view(np.float32))
    got = np.asarray(expand._expander(size, True, SMALL)(
        v, jnp.asarray(positions))).view(np.int32)
    np.testing.assert_array_equal(got[positions], images)
    assert np.count_nonzero(got) == len(images)


def test_the_steps_table_covers_every_tile_and_part():
    """A tile's steps take consecutive parts, from the part the tile
    before ended on; every part that holds a slot of the tile is among
    them; the table's length is tiles + parts - 1."""
    size, slots = 9 * TILE + 40, 11 * 1024 - 7
    _values, positions = _upload(size, slots, 9000, seed=3)
    part, ntiles, nparts = 1024, 10, 11
    pos = np.concatenate([positions, np.full(nparts * part - slots, FAR)])
    tile_of = np.asarray(expand._steps(jnp.asarray(pos, jnp.int32), ntiles,
                                       nparts, TILE, part))
    assert tile_of.shape == (ntiles + nparts - 1,)
    assert (np.diff(tile_of) >= 0).all() and set(tile_of) == set(range(10))
    parts_of = {g: set() for g in range(ntiles)}
    for t, g in enumerate(tile_of):
        parts_of[int(g)].add(t - int(g))
    for s, p in enumerate(pos):
        if p < ntiles * TILE:
            assert s // part in parts_of[int(p) // TILE], (s, p)


# -- the rule -----------------------------------------------------------------

def test_the_rule_off_the_chip_is_the_scatter():
    long = jax.ShapeDtypeStruct((expand.EXPAND_MIN_SLOTS,), jnp.int32)
    assert ops.pallas_interpret()
    assert not expand.runs_kernel(long)
    assert expand.runs_kernel(long, forced=True)
    assert not expand.runs_kernel(long, forced=False)


def test_the_rule_where_pallas_compiles(monkeypatch):
    """A TPU backend stood in for: the kernel from ``EXPAND_MIN_SLOTS``
    slots, never under a mesh, whoever shows it."""
    monkeypatch.setattr(ops, "pallas_interpret", lambda: False)
    long = jax.ShapeDtypeStruct((expand.EXPAND_MIN_SLOTS,), jnp.int32)
    short = jax.ShapeDtypeStruct((expand.EXPAND_MIN_SLOTS - 1,), jnp.int32)
    assert expand.runs_kernel(long) and not expand.runs_kernel(short)
    mesh = jax.make_mesh((2,), ("dp",))
    assert not expand.runs_kernel(long, mesh)
    with jax.set_mesh(mesh):
        assert not expand.runs_kernel(long)
    # cell 2's twelve small chunks stay on the scatter, its two large
    # ones and every unshaped cell's one chunk take the kernel
    assert 141_742 < expand.EXPAND_MIN_SLOTS <= 771_946


def test_the_function_takes_the_form_the_rule_gives(monkeypatch):
    size = 2 * TILE
    values, positions = _upload(size, 900, 800, seed=8)
    v, p = jnp.asarray(values), jnp.asarray(positions)

    def names(forced=None, **kw):
        with monkeypatch.context() as m:
            m.setattr(expand, "runs_kernel",
                      partial(expand.runs_kernel, forced=forced))
            text = str(jax.make_jaxpr(
                lambda v, p: expand.dense_from_sorted(v, p, size, **kw)
                )(v, p))
        # the kernel form's own scatters write its steps' table
        return ("pallas_call" in text,
                f"f32[{size}] = scatter-add" in text.replace(":", " ="))

    assert names() == (False, True)
    assert names(forced=True) == (True, False)
    monkeypatch.setattr(expand, "EXPAND_MIN_SLOTS", 900)
    monkeypatch.setattr(ops, "pallas_interpret", lambda: False)
    assert names() == (True, False)
    assert names(mesh=jax.make_mesh((2,), ("dp",))) == (False, True)
    monkeypatch.setattr(expand, "EXPAND_MIN_SLOTS", 901)
    assert names() == (False, True)
    monkeypatch.undo()
    want = np.asarray(expand.dense_from_sorted(v, p, size)).view(np.int32)
    monkeypatch.setattr(expand, "runs_kernel",
                        partial(expand.runs_kernel, forced=True))
    np.testing.assert_array_equal(
        np.asarray(expand.dense_from_sorted(v, p, size)).view(np.int32), want)


def test_the_kernel_lowers_to_one_mosaic_call():
    """For a TPU, with no chip and no libtpu: one ``tpu_custom_call``,
    no scatter but the steps' table's two small ones, no sort."""
    size, slots = 40_000_000, 800_000
    lowered = expand._expander(size, False).trace(
        jax.ShapeDtypeStruct((slots,), jnp.float32),
        jax.ShapeDtypeStruct((slots,), jnp.int32)).lower(
            lowering_platforms=("tpu",))
    text = lowered.as_text()
    assert text.count("tpu_custom_call") == 1, text[:2000]
    scatters = [line for line in text.splitlines()
                if '"stablehlo.scatter"' in line]
    assert len(scatters) == 2 and "stablehlo.sort" not in text, scatters
    assert not any(str(size) in line or str(slots) in line
                   for line in scatters), scatters


def test_forcing_is_how_a_test_drives_the_kernel(monkeypatch):
    """``monkeypatch.setattr(expand, "runs_kernel", partial(...,
    forced=True))`` reaches ``dense_from_sorted``: the rule is looked up
    where it is called."""
    monkeypatch.setattr(expand, "runs_kernel",
                        partial(expand.runs_kernel, forced=True))
    v = jnp.ones(8, jnp.float32)
    p = jnp.arange(8, dtype=jnp.int32) * 3
    text = str(jax.make_jaxpr(
        lambda v, p: expand.dense_from_sorted(v, p, 100))(v, p))
    assert "pallas_call" in text
