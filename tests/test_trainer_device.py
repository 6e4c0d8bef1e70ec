"""DeviceResidentTrainer: device-resident params, BSC-compressed link.

Validates the cnn_bsc-style round (aggregator PS, worker-side optimizer)
over a LIVE two-party in-process HiPS topology: exactness at
threshold=1.0 (top-k covers everything -> must equal dense data-parallel
SGD), replica consistency, convergence at sparse thresholds, and the
compact-payload claim.
"""

import gc
import threading
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from geomx_tpu.simulate import InProcessHiPS
from geomx_tpu.trainer_device import DeviceResidentTrainer

TARGET = np.arange(1.0, 9.0, dtype=np.float32).reshape(2, 4)


def _grad_fn(leaves, X, y):
    """Quadratic bowl: loss = 0.5*||w - target||^2 (per worker batch
    shift given by X so worker grads differ)."""
    w = leaves[0]
    diff = w - jnp.asarray(TARGET) + X
    return 0.5 * jnp.sum(diff * diff), [diff]


def _run_two_workers(threshold, rounds=30, lr=0.2, momentum=0.0):
    topo = InProcessHiPS(num_parties=2, workers_per_party=1).start()
    results = {}
    try:
        def worker(kv):
            widx = 0 if kv is topo.workers[0] else 1
            tr = DeviceResidentTrainer(
                [np.zeros((2, 4), np.float32)], kv, _grad_fn,
                threshold=threshold, learning_rate=lr, momentum=momentum)
            # worker batches pull in opposite directions; the MEAN grad
            # points at TARGET exactly
            shift = jnp.asarray(0.5 if widx == 0 else -0.5)
            for _ in range(rounds):
                tr.step(shift, None)
            results[widx] = tr.leaves[0]

        def master_init(kv):
            kv.init(0, np.zeros((2, 4), np.float32))
            kv.wait()

        t = threading.Thread(target=lambda: topo.run_workers(
            worker, include_master=master_init, timeout=300))
        t.start()
        t.join(300)
        assert not t.is_alive(), "workers hung"
    finally:
        topo.stop()
    return results


def test_dense_threshold_matches_plain_sgd():
    """threshold=1.0 selects every coordinate -> the distributed run
    must track plain full-gradient SGD on the mean gradient exactly
    (BSC with k=n is lossless)."""
    res = _run_two_workers(threshold=1.0, rounds=25, lr=0.2)
    w = np.zeros((2, 4), np.float32)
    for _ in range(25):
        w = w - 0.2 * (w - TARGET)  # mean of the two shifted grads
    np.testing.assert_allclose(res[0], w, rtol=1e-5, atol=1e-5)


def test_replicas_stay_identical():
    res = _run_two_workers(threshold=0.5, rounds=20)
    np.testing.assert_array_equal(res[0], res[1])


def test_sparse_threshold_converges():
    """With k=2 of 8 coords per round, the iterate lands in a bounded
    neighborhood of the optimum (BSC residual feedback batches deferred
    coordinates, so persistent worker dissent -> bounded oscillation,
    not exact convergence — reference behavior)."""
    res = _run_two_workers(threshold=0.25, rounds=150, lr=0.15)
    err = np.abs(res[0] - TARGET)
    assert float(err.mean()) < 0.25 and float(err.max()) < 0.6, res[0]


def test_momentum_variant_matches_heavyball():
    """threshold=1.0 makes the wire lossless, so the local momentum
    update must equal plain heavyball SGD on the mean gradient."""
    res = _run_two_workers(threshold=1.0, rounds=30, lr=0.05, momentum=0.9)
    w = np.zeros((2, 4), np.float32)
    mom = np.zeros_like(w)
    for _ in range(30):
        mom = 0.9 * mom + (w - TARGET)
        w = w - 0.05 * mom
    np.testing.assert_allclose(res[0], w, rtol=1e-5, atol=1e-5)


def test_payload_is_compact():
    """The device->host payload is k = ceil(total*threshold) pairs."""
    from geomx_tpu.kvstore import create as kv_create

    kv = kv_create("local")
    tr = DeviceResidentTrainer(
        [np.zeros((100,), np.float32)], kv, _grad_fn_100,
        threshold=0.02, learning_rate=0.1)
    assert tr.k == 2
    # and a local round still works end to end
    tr.step(jnp.asarray(0.0), None)
    assert tr.leaves[0].shape == (100,)


def _grad_fn_100(leaves, X, y):
    w = leaves[0]
    return 0.5 * jnp.sum(w * w), [w + 1.0]


def test_warmup_compiles_without_state_change():
    from geomx_tpu.kvstore import create as kv_create

    kv = kv_create("local")
    tr = DeviceResidentTrainer(
        [np.zeros((16,), np.float32)], kv, _grad_fn_16,
        threshold=0.5, learning_rate=0.1)
    before = tr.leaves[0].copy()
    tr.warmup(jnp.asarray(0.0), None)
    np.testing.assert_array_equal(tr.leaves[0], before)
    tr.step(jnp.asarray(0.0), None)  # and a real round still works
    assert not np.array_equal(tr.leaves[0], before)


class _Knot:
    """Garbage only the collector can free."""

    def __init__(self):
        self.me = self


@pytest.fixture
def heap_account():
    """Telemetry on; gives what a counter has gained since. (Nothing is
    frozen going in and the heap is thawed going out: conftest's
    ``_thawed_heap`` is every test's teardown.)"""
    from geomx_tpu import telemetry

    was_on = telemetry.enabled()
    telemetry.enable(True)
    before = telemetry.snapshot()["counters"]
    try:
        yield lambda name: (telemetry.snapshot()["counters"].get(name, 0)
                            - before.get(name, 0))
    finally:
        telemetry.enable(was_on)


def _warm_trainer():
    from geomx_tpu.kvstore import create as kv_create

    return DeviceResidentTrainer(
        [np.zeros((16,), np.float32)], kv_create("local"), _grad_fn_16,
        threshold=0.5, learning_rate=0.1)


def test_warmup_moves_the_heap_out_of_the_collectors_sight(heap_account):
    from geomx_tpu import telemetry

    tr = _warm_trainer()
    gc.collect()                        # the suite's garbage so far
    tracked = len(gc.get_objects())
    knot = weakref.ref(_Knot())         # dead already, and a cycle
    # (a collection parks the interpreter's few hundred immortals there)
    assert gc.get_freeze_count() < 0.01 * tracked and knot() is not None
    tr.warmup(jnp.asarray(0.0), None)
    frozen = gc.get_freeze_count()
    # jax, numpy, the suite and the programs: all of it, near enough
    assert frozen > 0.9 * tracked
    assert len(gc.get_objects()) < 0.1 * tracked
    # the collection came first: no garbage went into the freeze
    assert knot() is None
    # (a frozen object whose last reference goes is freed like any
    # other: the count read later is the gauge less a frame or two)
    gauge = telemetry.snapshot()["gauges"]["host.gc_frozen_objects"]
    assert frozen <= gauge < frozen + 100
    assert heap_account("host.gc_freezes") == 1


def test_a_second_warmup_freezes_what_was_built_since(heap_account):
    from geomx_tpu import telemetry

    first, second = _warm_trainer(), _warm_trainer()
    first.warmup(jnp.asarray(0.0), None)
    once = gc.get_freeze_count()
    before = second.leaves[0].copy()
    second.warmup(jnp.asarray(0.0), None)
    first.warmup(jnp.asarray(0.0), None)    # and the same trainer again
    twice = gc.get_freeze_count()
    # additive and small: nothing is thawed, nothing is frozen twice
    assert 0.99 * once < twice < 1.1 * once
    gauge = telemetry.snapshot()["gauges"]["host.gc_frozen_objects"]
    assert twice <= gauge < twice + 100
    assert heap_account("host.gc_freezes") == 3
    np.testing.assert_array_equal(second.leaves[0], before)
    for tr in (first, second):              # and real rounds still work
        tr.step(jnp.asarray(0.0), None)
        assert not np.array_equal(tr.leaves[0], before)


def test_a_frozen_trainer_that_is_dropped_is_freed(heap_account):
    """What the freeze must not cost a process that makes trainers again
    and again: a frozen object in a dead cycle is never freed, so the
    trainer and its device state may sit in no cycle; the last
    reference's going frees them, frozen or not."""
    tr = _warm_trainer()
    tr.warmup(jnp.asarray(0.0), None)
    tr.step(jnp.asarray(0.0), None)
    trainer, flat = weakref.ref(tr), weakref.ref(tr._flat)
    frozen = gc.get_freeze_count()
    del tr
    assert trainer() is None and flat() is None
    assert gc.get_freeze_count() < frozen


def test_the_collector_stays_on_after_warmup(heap_account):
    """Frozen is not disabled: the thresholds are the interpreter's, and
    a cycle that dies after the freeze is collected like any other."""
    thresholds, was_enabled = gc.get_threshold(), gc.isenabled()
    tr = _warm_trainer()
    tr.warmup(jnp.asarray(0.0), None)
    assert gc.get_threshold() == thresholds
    assert gc.isenabled() == was_enabled
    tr.step(jnp.asarray(0.0), None)
    knot = weakref.ref(_Knot())
    assert knot() is not None
    gc.collect()
    assert knot() is None
    # what a thaw gives back is what was frozen
    frozen = gc.get_freeze_count()
    gc.unfreeze()
    assert gc.get_freeze_count() == 0 and len(gc.get_objects()) >= frozen


def _grad_fn_16(leaves, X, y):
    w = leaves[0]
    return 0.5 * jnp.sum(w * w), [w + 1.0]


def test_packed_wire_is_int32_and_index_exact():
    """Round-4 chip regression: the packed device<->host payload must be
    an INT32 array (floats bitcast int-wards), never float32 with
    indices bitcast float-wards. Indices < 2^23 bitcast to float32 are
    denormals, and TPU float data movement inside jit flushes denormals
    to zero — on the first real-TPU capture every index collapsed to 0
    and headline accuracy fell to chance (0.0967).
    CPU can't reproduce the flush, so this asserts the wire CONTRACT:
    dtype int32 end-to-end and bit-exact recovery of small indices."""
    from geomx_tpu.kvstore import create as kv_create

    rng = np.random.default_rng(3)
    w = rng.standard_normal(500).astype(np.float32)

    def gfn(leaves, X, y):
        return jnp.sum(leaves[0]), [jnp.asarray(w)]

    kv = kv_create("local")
    tr = DeviceResidentTrainer([np.zeros(500, np.float32)], kv, gfn,
                               threshold=0.01, learning_rate=1.0)
    _loss, (packed,), _u, _v = tr._fwd_chunks(tr._flat, tr._u, tr._v,
                                              jnp.asarray(0.0), None)
    assert np.asarray(packed).dtype == np.int32
    k = tr.k
    p = np.asarray(packed)       # the one chunk's pack: [vals(k), idx(k)]
    assert p.shape == (2 * k,)
    idx = p[k:]
    vals = p[:k].view(np.float32)
    # exact top-k of the rigged gradient: u=g, v=g -> top-|g| coords
    expect = np.argsort(-np.abs(w), kind="stable")[:k]
    assert set(idx.tolist()) == set(expect.tolist())
    np.testing.assert_array_equal(np.sort(np.abs(vals)),
                                  np.sort(np.abs(w[expect])))


def test_local_store_sparse_round_returns_the_selection():
    """KVStoreLocal answers the trainer's verb on the spot: one worker,
    no updater, so a key's aggregate is its own selection, less entries
    of value 0 (the servers' ack carries nonzeros only), in index
    order; an empty selection comes back empty; an index outside the
    key is refused."""
    from geomx_tpu.kvstore import create as kv_create

    kv = kv_create("local")
    kv.init(0, np.zeros((3, 4), np.float32))
    kv.init(1, np.zeros(5, np.float32))
    fut = kv.push_pull_bsc_batch_async(
        [0, 1],
        [np.array([3.0, 0.0, -1.0], np.float32), np.zeros(0, np.float32)],
        [np.array([11, 2, 1]), np.zeros(0, np.int64)],
        priority=-1, slice_bytes=0)
    assert fut.done()
    agg = fut.results(timeout=1)
    np.testing.assert_array_equal(agg[0][0], np.array([-1.0, 3.0],
                                                      np.float32))
    np.testing.assert_array_equal(agg[0][1], [1, 11])
    assert agg[0][0].dtype == np.float32 and agg[0][1].dtype == np.int64
    assert agg[1][0].size == 0 and agg[1][1].size == 0
    with pytest.raises(IndexError):
        kv.push_pull_bsc_batch_async([1], [np.ones(1, np.float32)],
                                     [np.array([5])])


def test_local_store_sparse_round_refuses_an_updater():
    """With an updater the store holds weights, not an aggregate: the
    round is refused with the trainer's own words for it."""
    from geomx_tpu.kvstore import create as kv_create
    from geomx_tpu.optimizer import SGD

    kv = kv_create("local")
    kv.set_optimizer(SGD(learning_rate=0.1))
    tr = DeviceResidentTrainer(
        [np.zeros((16,), np.float32)], kv, _grad_fn_16,
        threshold=0.5, learning_rate=0.1)
    with pytest.raises(RuntimeError, match="requires aggregator mode"):
        tr.step(jnp.asarray(0.0), None)


def _by_sorting(x, k):
    """What ``ops.select.topk_by_magnitude`` stands in for: the
    positions ``lax.top_k`` gives, put in ascending order, x there,
    and the rule read off them: the smallest magnitude among them and
    where the last of those at it lies."""
    pos = jnp.sort(jax.lax.top_k(jnp.abs(x), k)[1]).astype(jnp.int32)
    bits = jax.lax.bitcast_convert_type(jnp.abs(x[pos]), jnp.int32)
    t = bits.min()
    return pos, x[pos], t, jnp.max(jnp.where(bits == t, pos, -1)) + 1


def _three_rounds(wire_codec):
    """Every leaf of both workers after three rounds of a two-party job
    over keys of several sizes (two of them alike), with ties in the
    gradient; and what the rounds booked on the selection's and the
    reset's counters."""
    from geomx_tpu import telemetry

    shapes, grad_fn = _SHAPES, _bowl()
    extra = {"wire_codec": wire_codec} if wire_codec else {}
    topo = InProcessHiPS(num_parties=2, workers_per_party=1,
                         extra_cfg=extra).start()
    results = {}
    was_on = telemetry.enabled()
    telemetry.enable(True)
    before = dict(telemetry.snapshot()["counters"])
    try:
        def worker(kv):
            widx = 0 if kv is topo.workers[0] else 1
            tr = DeviceResidentTrainer(
                [np.zeros(s, np.float32) for s in shapes], kv, grad_fn,
                threshold=0.05, learning_rate=0.1)
            for _ in range(3):
                tr.step(jnp.asarray(0.5 if widx == 0 else -0.25), None)
            results[widx] = [l.copy() for l in tr.leaves]

        def master_init(kv):
            for i, s in enumerate(shapes):
                kv.init(i, np.zeros(s, np.float32))
            kv.wait()

        t = threading.Thread(target=lambda: topo.run_workers(
            worker, include_master=master_init, timeout=300))
        t.start()
        t.join(300)
        assert not t.is_alive(), "workers hung"
    finally:
        topo.stop()
        after = telemetry.snapshot()["counters"]
        telemetry.enable(was_on)
    booked = {name: after.get(name, 0) - before.get(name, 0)
              for name in ("step.select_threshold_keys",
                           "step.dense_reset_keys",
                           "trainer.upload_inplace_keys")}
    return results, booked, len(shapes)


@pytest.mark.parametrize("wire_codec", ["", "fp16"])
def test_selection_without_a_sort_trains_bit_for_bit(wire_codec,
                                                     monkeypatch):
    """Three rounds with the trainer's selection as it is against the
    same rounds with ``lax.top_k`` in its place: every leaf of every
    worker bit-equal (the same set leaves each key, so the servers sum
    the same pairs), ``bsc`` and ``bsc16``; every key of every round is
    booked on the selection's counter (no key keeps a sort, so there is
    no second counter), on the dense reset's (no key keeps its
    scatters) and on the held upload's (no key's parts are joined)."""
    from geomx_tpu.ops import select

    got, booked, keys = _three_rounds(wire_codec)
    assert booked == {"step.select_threshold_keys": keys * 3 * 2,
                      "step.dense_reset_keys": keys * 3 * 2,  # x workers
                      "trainer.upload_inplace_keys": keys * 3 * 2}
    monkeypatch.setattr(select, "topk_by_magnitude", _by_sorting)
    want, _booked, _keys = _three_rounds(wire_codec)
    for widx in (0, 1):
        for a, b in zip(got[widx], want[widx]):
            np.testing.assert_array_equal(a, b)
    assert any(np.any(l != 0) for l in got[0])


# -- the step scatters only where it must ------------------------------------

_SHAPES = [(40, 16), (640,), (7,), (40, 16), (3, 50), (129,)]


def _bowl():
    """A gradient with ties in it (quarters) over keys of several
    sizes, two of them alike and two no multiple of 128."""
    rng = np.random.default_rng(11)
    target = [np.round(rng.standard_normal(s) * 4).astype(np.float32) / 4
              for s in _SHAPES]

    def grad_fn(leaves, X, y):
        diffs = [w - jnp.asarray(t) + X for w, t in zip(leaves, target)]
        return sum(0.5 * jnp.sum(d * d) for d in diffs), diffs

    return grad_fn


def _local_trainer(wire_codec="", slice_bytes=0, shapes=None,
                   grad_fn=None, store=None, **kw):
    """A trainer over the local store, which answers a round with the
    selection itself; the two settings the device step reads off a
    store's configuration are handed to it as one. The bowl over
    ``_SHAPES`` unless ``shapes`` (and their ``grad_fn``) are given."""
    from types import SimpleNamespace

    from geomx_tpu.kvstore import create as kv_create

    kv = store or kv_create("local")
    kv.cfg = SimpleNamespace(wire_codec=wire_codec,
                             p3_slice_bytes=slice_bytes)
    kw = dict(dict(threshold=0.05, learning_rate=0.1, momentum=0.9), **kw)
    if shapes is None:
        shapes, grad_fn = _SHAPES, _bowl()
    return DeviceResidentTrainer(
        [np.zeros(s, np.float32) for s in shapes], kv, grad_fn, **kw)


def _scattering_reference(tr, wire16, lr=0.1, momentum=0.9):
    """The round as it was before the step stopped scattering what it
    can write densely: ``lax.top_k`` a key, ``u`` and ``v`` reset by
    ``x.at[idx].set`` at the selected positions, the aggregate applied
    by a scatter-add nobody told anything."""
    grad_fn = _bowl()
    offsets = [int(o) for o in tr._offsets[:-1]]

    @jax.jit
    def fwd(flat, u, v, X):
        leaves = [flat[o:o + s].reshape(sh) for o, s, sh in
                  zip(offsets, tr._sizes, _SHAPES)]
        _loss, grads = grad_fn(leaves, X, None)
        u = 0.9 * u + jnp.concatenate([g.reshape(-1) for g in grads])
        v = v + u
        idx = jnp.concatenate([
            jnp.sort(jax.lax.top_k(jnp.abs(v[o:o + s]), k)[1]) + o
            for o, s, k in zip(offsets, tr._sizes, tr._ks)])
        vals = v[idx]
        u = u.at[idx].set(0.0)
        if wire16:
            narrowed = vals.astype(jnp.float16).astype(jnp.float32)
            v = v.at[idx].set(vals - narrowed)
            vals = narrowed
        else:
            v = v.at[idx].set(0.0)
        return vals, idx, u, v

    @jax.jit
    def apply(flat, mom, vals, idx):
        mom = momentum * mom + jnp.zeros_like(flat).at[idx].add(vals)
        return flat - lr * mom, mom

    return fwd, apply


@pytest.mark.parametrize("slice_bytes", [0, 96])
@pytest.mark.parametrize("wire_codec", ["", "fp16"])
def test_three_rounds_equal_the_scattering_reference(wire_codec,
                                                     slice_bytes):
    """``flat``, momentum, ``u``, ``v`` and what goes to the wire after
    each of three rounds, bit for bit those of the reference that
    resets by scatter and applies by an unhinted scatter-add: float32
    and ``bsc16`` wires, the round in one chunk and in several."""
    tr = _local_trainer(wire_codec, slice_bytes)
    assert (len(tr._chunks) == 1) == (slice_bytes == 0)
    fwd, apply = _scattering_reference(tr, bool(wire_codec))
    sent = []
    inner = tr.kv.push_pull_bsc_batch_async

    def recording(keys, vlist, ilist, **kw):
        sent.append((list(keys), [np.array(a) for a in vlist],
                     [np.array(a) for a in ilist]))
        return inner(keys, vlist, ilist, **kw)

    tr.kv.push_pull_bsc_batch_async = recording
    flat, mom, u, v = (jnp.zeros(tr.total, jnp.float32) for _ in range(4))
    for rnd in range(3):
        X = jnp.asarray(0.5 - 0.25 * rnd)
        del sent[:]
        tr.step(X, None)
        vals, idx, u, v = fwd(flat, u, v, X)
        flat, mom = apply(flat, mom, vals, idx)
        for name, got, want in (("flat", tr._flat, flat),
                                ("momentum", tr._mom, mom),
                                ("u", tr._u, u), ("v", tr._v, v)):
            np.testing.assert_array_equal(
                np.asarray(got).view(np.uint32),
                np.asarray(want).view(np.uint32),
                err_msg=f"{name} after round {rnd}")
        # the packs, as the store got them: key by key the reference's
        # values and key-relative positions
        keys = [k for ks, _v, _i in sent for k in ks]
        assert keys == list(range(len(_SHAPES)))
        np.testing.assert_array_equal(
            np.concatenate([a for _k, vs, _i in sent for a in vs])
            .view(np.uint32), np.asarray(vals).view(np.uint32))
        np.testing.assert_array_equal(
            np.concatenate([a + int(tr._offsets[k]) for ks, _v, il in sent
                            for k, a in zip(ks, il)]), np.asarray(idx))
    assert np.count_nonzero(np.asarray(tr._v)) > 0 < np.count_nonzero(flat)


def _primitives(jaxpr):
    """Names of the primitives of a jaxpr, those of its sub-jaxprs
    (loops, calls) included."""
    names = set()
    for eqn in jaxpr.eqns:
        names.add(eqn.primitive.name)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            names |= _primitives(sub)
    return names


def test_the_step_scatters_only_where_it_must():
    """``fwd_chunks`` writes nothing by ``x.at[idx].set`` any more (the
    one scatter left is the compaction's ``scatter-max`` of n/128 row
    marks in ``ops/select.py``), and ``apply_chunk``'s scatter-add is
    told that its positions ascend, are distinct and may lie outside."""
    tr = _local_trainer()
    X = jnp.asarray(0.5)
    names = _primitives(jax.make_jaxpr(tr._fwd_chunks)(
        tr._flat, tr._u, tr._v, X, None).jaxpr)
    assert "scatter" not in names and "sort" not in names, names
    assert "scatter_max" in names or "scatter-max" in names, names
    _lo, _hi, flo, fsize, cap = tr._chunk_meta[0]
    text = tr._apply_chunk.lower(
        tr._flat, tr._mom, jnp.zeros(2 * cap, jnp.int32), flo,
        fsize).as_text()
    scatters = [line for line in text.splitlines()
                if "stablehlo.scatter" in line]
    assert len(scatters) == 1, text
    assert "indices_are_sorted = true" in scatters[0]
    assert "unique_indices = true" in scatters[0]
    assert "stablehlo.sort" not in text


@pytest.fixture(scope="module")
def one_chip():
    """A described TPU v5e chip, for compiling without one."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:    # noqa: BLE001 — whatever keeps libtpu away
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_the_chips_compiler_puts_no_sort_before_the_apply(one_chip):
    """Compiled for a v5e, ``apply_chunk`` holds its scatter and no
    sort: the hints reach the compiler that used to sort the positions
    itself."""
    tr = _local_trainer()
    _lo, _hi, flo, fsize, cap = tr._chunk_meta[0]
    flat = jax.ShapeDtypeStruct((tr.total,), jnp.float32,
                                sharding=one_chip)
    up = jax.ShapeDtypeStruct((2 * cap,), jnp.int32, sharding=one_chip)
    text = tr._apply_chunk.lower(flat, flat, up, flo,
                                 fsize).compile().as_text()
    assert " scatter(" in text and " sort(" not in text


# -- the apply's two forms (ops/expand.py) ------------------------------------

def _forced(monkeypatch, answer=True):
    """The op's rule answers ``answer`` from here on: the kernel runs
    interpreted on this backend."""
    from functools import partial

    from geomx_tpu.ops import expand

    monkeypatch.setattr(expand, "runs_kernel",
                        partial(expand.runs_kernel, forced=answer))


def _apply_primitives(tr, ci=0):
    _lo, _hi, flo, fsize, cap = tr._chunk_meta[ci]
    return _primitives(tr._apply_chunk.trace(
        tr._flat, tr._mom, jnp.zeros(2 * cap, jnp.int32), flo,
        fsize).jaxpr.jaxpr)


def _meshed(kv):
    """The local store dressed as a mesh party's: a mesh and how to
    replicate over it."""
    from jax.sharding import NamedSharding, PartitionSpec

    kv.mesh = jax.make_mesh((2,), ("dp",))
    kv.replicated_sharding = lambda: NamedSharding(kv.mesh, PartitionSpec())
    return kv


@pytest.mark.parametrize("where,kernel", [
    ("a CPU backend", False),
    ("a CPU backend, forced", True),
    ("where Pallas compiles, under the floor", False),
    ("where Pallas compiles, at the floor", True),
    ("where Pallas compiles, with a mesh", False),
    ("forced off where Pallas compiles", False),
])
def test_the_form_apply_chunk_takes(where, kernel, monkeypatch):
    """The rule is the op's, asked with what the trainer knows (its
    upload's slots, its store's mesh); the count a round books is the
    number of chunks it answered yes for."""
    from geomx_tpu import ops
    from geomx_tpu.kvstore import create as kv_create
    from geomx_tpu.ops import expand

    cap = int(sum(max(int(np.prod(s) * 0.05), 1) for s in _SHAPES))
    store = None
    if "Pallas compiles" in where:
        monkeypatch.setattr(ops, "pallas_interpret", lambda: False)
        monkeypatch.setattr(expand, "EXPAND_MIN_SLOTS",
                            cap + ("under" in where))
    if "mesh" in where:
        store = _meshed(kv_create("local"))
    if "forced" in where:
        _forced(monkeypatch, kernel)
    tr = _local_trainer(store=store)
    assert tr._chunk_meta[0][4] == cap
    assert tr._expand_applies == int(kernel)
    names = _apply_primitives(tr)
    # (the kernel form has a scatter-add of its own: a part a count
    # into its steps' table)
    assert ("pallas_call" in names) == kernel, names
    assert "scatter-add" in names or kernel, names


@pytest.mark.parametrize("slice_bytes", [0, 96])
def test_rounds_with_the_kernel_forced_are_the_scatters_rounds(
        slice_bytes, monkeypatch):
    """Three rounds applied by the kernel (interpreted) leave ``flat``
    and the momentum what the scatter leaves, bit for bit: one chunk
    and a plan of several; ``trainer.expand_applies`` moves by the
    chunks a round."""
    from geomx_tpu import telemetry

    def rounds(tr):
        for _ in range(3):
            tr.step(jnp.asarray(0.5), None)
        return (np.asarray(tr._flat).view(np.int32),
                np.asarray(tr._mom).view(np.int32))

    want = rounds(_local_trainer(slice_bytes=slice_bytes))
    _forced(monkeypatch)
    tr = _local_trainer(slice_bytes=slice_bytes)
    assert tr._expand_applies == len(tr._chunks) >= 1 + bool(slice_bytes)
    assert "pallas_call" in _apply_primitives(tr, len(tr._chunks) - 1)
    was_on = telemetry.enabled()
    telemetry.enable(True)
    try:
        before = telemetry.snapshot()["counters"].get(
            "trainer.expand_applies", 0)
        got = rounds(tr)
        assert telemetry.snapshot()["counters"][
            "trainer.expand_applies"] - before == 3 * len(tr._chunks)
    finally:
        telemetry.enable(was_on)
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[1], want[1])
    assert np.count_nonzero(got[0]) and np.count_nonzero(got[1])


def _tpu_apply(monkeypatch):
    """A trainer whose ``apply_chunk`` takes the kernel as a TPU backend
    would compile it (Mosaic, not interpreted), and its arguments."""
    from geomx_tpu import ops

    monkeypatch.setattr(ops, "pallas_interpret", lambda: False)
    _forced(monkeypatch)
    tr = _local_trainer()
    _lo, _hi, flo, fsize, cap = tr._chunk_meta[0]
    return tr, (flo, fsize), cap


def test_the_lowered_apply_stays_small(monkeypatch):
    """Set-up follows a program's text (PR 47): lowered for a TPU, the
    apply with the kernel is ONE Mosaic call and under 40,000
    characters (23,618 when it was written; the scatter's 3,108), and
    the scatter form is the text it was."""
    plain = _local_trainer()
    _lo, _hi, flo, fsize, cap = plain._chunk_meta[0]
    up = jnp.zeros(2 * cap, jnp.int32)

    def text(tr):
        return tr._apply_chunk.trace(tr._flat, tr._mom, up, flo, fsize
                                     ).lower(lowering_platforms=("tpu",)
                                             ).as_text()

    scatter = text(plain)
    assert "tpu_custom_call" not in scatter and len(scatter) < 4_000
    tr, _static, _cap = _tpu_apply(monkeypatch)
    kernel = text(tr)
    assert kernel.count("tpu_custom_call") == 1
    assert len(kernel) < 40_000, len(kernel)
    big = [line for line in kernel.splitlines()
           if '"stablehlo.scatter"' in line and f"<{fsize}x" in line]
    assert not big, big


def test_the_chips_compiler_takes_the_apply_with_the_kernel(
        one_chip, monkeypatch):
    """Compiled for a v5e: the kernel is there, and no scatter of the
    chunk's size."""
    tr, (flo, fsize), cap = _tpu_apply(monkeypatch)
    flat = jax.ShapeDtypeStruct((tr.total,), jnp.float32,
                                sharding=one_chip)
    up = jax.ShapeDtypeStruct((2 * cap,), jnp.int32, sharding=one_chip)
    text = tr._apply_chunk.lower(flat, flat, up, flo,
                                 fsize).compile().as_text()
    assert "tpu_custom_call" in text
    assert not [line for line in text.splitlines()
                if " scatter(" in line and f"f32[{fsize}]" in line]


def test_chunk_up_pads_past_the_chunk_in_ascending_order():
    """The slots an aggregate leaves over hold 0.0 at positions that
    ascend, distinct, at or past the chunk's end, every chunk from its
    own (slot ``s`` holds ``fsize + s``, whatever the round filled: the
    scatter is told that the whole list ascends without a repeat and
    drops what lies outside); an upload at full capacity has no pad and
    applies all the same."""
    tr = _local_trainer(slice_bytes=96, momentum=0.0, learning_rate=1.0)
    assert len(tr._chunks) > 1
    padded = 0
    for ci, ch in enumerate(tr._chunks):
        _lo, _hi, flo, fsize, cap = tr._chunk_meta[ci]
        first = ch.items[0]
        agg = {i: (np.zeros(0, np.float32), np.zeros(0, np.int64))
               for i in ch.items}
        agg[first] = (np.array([2.0], np.float32), np.array([3]))
        up = tr._chunk_up(ci, agg)
        assert up.dtype == np.int32 and up.shape == (2 * cap,)
        np.testing.assert_array_equal(up[:cap].view(np.float32)[1:], 0.0)
        assert up[cap] == 3
        pad = up[cap + 1:]
        assert np.all(pad >= fsize) and np.all(np.diff(up[cap:]) > 0)
        np.testing.assert_array_equal(
            pad, np.arange(fsize + 1, fsize + cap))
        padded += cap > 1
    assert padded
    # full capacity: every slot of the last chunk a real entry
    want = np.asarray(tr._flat).copy()
    sizes = [tr._sizes[i] for i in ch.items]
    assert cap <= sum(sizes)
    taken, agg = 0, {}
    for i, size in zip(ch.items, sizes):
        n = min(size, cap - taken)
        agg[i] = (np.full(n, 0.5, np.float32), np.arange(n))
        want[tr._offsets[i]:tr._offsets[i] + n] -= 0.5
        taken += n
    up = tr._chunk_up(ci, agg)
    assert taken == cap and np.all(np.diff(up[cap:]) > 0)
    assert np.all(up[cap:] < fsize)
    tr._flat, tr._mom = tr._apply_chunk(tr._flat, tr._mom,
                                        jnp.asarray(up), flo, fsize)
    np.testing.assert_array_equal(np.asarray(tr._flat), want)
    with pytest.raises(RuntimeError, match="exceeds chunk upload"):
        agg[first] = (np.ones(sizes[0] + 1, np.float32),
                      np.arange(sizes[0] + 1))
        tr._chunk_up(ci, agg)


def test_a_sharded_keys_parts_join_in_the_keys_order():
    """Shards answer in any order; what the trainer gets for the key
    ascends all the same (``apply_chunk`` is told so), an empty part
    anywhere among them, and one part alone is handed on as it is."""
    from geomx_tpu.kvstore.dist import KVStoreDist

    late = (np.array([3.0, 4.0], np.float32), np.array([70, 90]))
    early = (np.array([1.0], np.float32), np.array([5], np.int32))
    empty = (np.zeros(0, np.float32), np.zeros(0, np.int64))
    for parts in ([late, empty, early], [early, late], [empty, late, early]):
        vals, idx = KVStoreDist._join_bsc_parts(parts)
        np.testing.assert_array_equal(idx, [5, 70, 90])
        np.testing.assert_array_equal(vals, [1.0, 3.0, 4.0])
    assert KVStoreDist._join_bsc_parts([late]) is late
    for parts in ([], [empty], [empty, empty]):
        vals, idx = KVStoreDist._join_bsc_parts(parts)
        assert vals.size == 0 and idx.size == 0


# -- the upload is a buffer the trainer holds, written in place ---------------

def _chunk_up_as_it_was(tr, ci, agg):
    """``_chunk_up`` before the trainer held its uploads: the keys'
    parts joined by two concatenates into a fresh zeroed array, the
    slots left over at ``fsize, fsize + 1, ..`` from the first of them.
    The oracle: the held buffer equals it bit for bit on the real slots
    and keeps the same promise on the pad."""
    _sel_lo, _sel_hi, flo, fsize, cap = tr._chunk_meta[ci]
    ups, upi = [], []
    for i in tr._chunks[ci].items:
        avals, aidx = agg[tr.begin_key + i]
        ups.append(avals)
        upi.append(aidx + (int(tr._offsets[i]) - flo))
    cat_v = np.concatenate(ups)
    cat_i = np.concatenate(upi)
    n = len(cat_v)
    if n > cap:
        raise RuntimeError("aggregated selection exceeds chunk upload")
    up = np.zeros(2 * cap, np.int32)
    up[:n] = np.asarray(cat_v, np.float32).view(np.int32)
    up[cap:cap + n] = cat_i.astype(np.int32)
    up[cap + n:] = np.arange(fsize, fsize + cap - n, dtype=np.int32)
    return up


def _assert_upload(got, want, n, fsize, cap, where):
    """``got`` is ``want`` bit for bit on the ``n`` real slots; its pad
    holds the bits of 0.0 at ``fsize + s``; and the whole list of
    positions keeps what the scatter is promised."""
    assert got.dtype == want.dtype == np.int32, where
    assert got.shape == want.shape == (2 * cap,), where
    assert got[:n].tobytes() == want[:n].tobytes(), where
    assert got[cap:cap + n].tobytes() == want[cap:cap + n].tobytes(), where
    assert not got[n:cap].any(), where        # +0.0, not a stale value
    np.testing.assert_array_equal(
        got[cap + n:], np.arange(fsize + n, fsize + cap), err_msg=str(where))
    pos = got[cap:].astype(np.int64)
    assert np.all(np.diff(pos) > 0), where    # ascending, distinct
    assert np.all(pos[:n] < fsize) and np.all(pos[n:] >= fsize), where
    assert np.all(pos >= 0) and pos[-1] < 1 << 31, where


def _an_aggregate(tr, ci, counts, seed, idx_dtype=np.int64, frame=False):
    """An aggregate for chunk ``ci`` with ``counts[j]`` entries for its
    j-th key: distinct ascending key-relative positions, values with
    both zeros among them; ``frame`` hands every part out as the native
    van does, a read-only view into one buffer of bytes."""
    rng = np.random.default_rng(seed)
    agg = {}
    for i, n in zip(tr._chunks[ci].items, counts):
        idx = np.sort(rng.choice(tr._sizes[i], n, replace=False)).astype(
            idx_dtype)
        vals = rng.standard_normal(n).astype(np.float32)
        vals[::5] = [0.0, -0.0][i % 2]
        if frame:
            wire = b"\0" * 4 + vals.tobytes() + idx.tobytes()
            vals = np.frombuffer(wire, np.float32, n, offset=4)
            idx = np.frombuffer(wire, idx_dtype, n, offset=4 + 4 * n)
            assert not (vals.flags.writeable or idx.flags.writeable)
        agg[tr.begin_key + i] = (vals, idx)
    return agg


def _spread(tr, ci, total, empty=()):
    """``total`` entries (as many of them as there is room for) over
    chunk ``ci``'s keys in proportion to their sizes, none to the keys
    at the places ``empty`` (negative: from the end)."""
    items = tr._chunks[ci].items
    empty = {e % len(items) for e in empty}
    room = [0 if j in empty else tr._sizes[i] for j, i in enumerate(items)]
    total = min(total, sum(room))
    counts = [total * r // max(sum(room), 1) for r in room]
    for j, r in enumerate(room):
        counts[j] += min(r - counts[j], total - sum(counts))
    assert sum(counts) == total
    return counts


# a case: the chunk plan, then the rounds ONE trainer makes in a row,
# each (share of the chunk's capacity taken, keys left empty, how the
# positions come); a share over 1 is the aggregate that must raise
_INT32_FRAME = dict(idx_dtype=np.int32, frame=True)
_UPLOAD_CASES = {
    "frame_int32_readonly_views": (0, [(0.6, (), _INT32_FRAME)]),
    "int64_positions": (0, [(0.6, (), {})]),
    "int64_frame_views": (0, [(0.5, (), dict(frame=True))]),
    "empty_key_first": (0, [(0.5, (0,), _INT32_FRAME)]),
    "empty_key_in_the_middle": (0, [(0.5, (2,), _INT32_FRAME)]),
    "empty_key_last": (0, [(0.5, (-1,), {})]),
    "every_key_empty_from_the_start": (0, [(0.0, (), {})]),
    "full_capacity_no_pad": (0, [(1.0, (), _INT32_FRAME)]),
    "grows_shrinks_empties_fills": (
        0, [(0.3, (), _INT32_FRAME), (0.9, (), {}), (0.4, (1,), _INT32_FRAME),
            (0.0, (), {}), (1.0, (), _INT32_FRAME), (0.2, (), {}),
            (0.2, (0, 3), _INT32_FRAME)]),
    "a_round_after_one_that_raised": (
        0, [(0.7, (), {}), (1.5, (), _INT32_FRAME), (0.4, (), {})]),
    "several_chunks": (
        96, [(0.8, (), _INT32_FRAME), (0.4, (0,), {}), (1.0, (), {}),
             (0.0, (), _INT32_FRAME), (0.5, (), {})]),
}


@pytest.mark.parametrize("case", sorted(_UPLOAD_CASES))
def test_the_held_upload_against_the_joined_one(case):
    """What ``_chunk_up`` writes into the buffer the trainer holds is,
    on the real slots, bit for bit the upload its concatenating form
    made of the same aggregate, and pad by the rule on the rest: round
    after round on one trainer (an entry of a longer round never
    survives into a shorter one's pad) and chunk by chunk; and the
    counter moves by the keys written."""
    from geomx_tpu import telemetry

    slice_bytes, rounds = _UPLOAD_CASES[case]
    tr = _local_trainer(slice_bytes=slice_bytes, threshold=0.2)
    assert (len(tr._chunks) > 1) == bool(slice_bytes)
    was_on, written = telemetry.enabled(), 0
    telemetry.enable(True)
    try:
        before = telemetry.snapshot()["counters"].get(
            "trainer.upload_inplace_keys", 0)
        for rnd, (share, empty, how) in enumerate(rounds):
            for ci in range(len(tr._chunks)):
                _lo, _hi, _flo, fsize, cap = tr._chunk_meta[ci]
                counts = _spread(tr, ci, int(share * cap), empty)
                agg = _an_aggregate(tr, ci, counts, seed=100 * rnd + ci,
                                    **how)
                if share > 1:
                    held = tr._uploads[ci].copy()
                    for form in (_chunk_up_as_it_was, type(tr)._chunk_up):
                        with pytest.raises(RuntimeError,
                                           match="exceeds chunk upload"):
                            form(tr, ci, agg)
                    np.testing.assert_array_equal(tr._uploads[ci], held)
                    continue
                want = _chunk_up_as_it_was(tr, ci, agg)
                got = tr._chunk_up(ci, agg)
                written += len(tr._chunks[ci].items)
                assert got is tr._uploads[ci]
                _assert_upload(got, want, sum(counts), fsize, cap,
                               (case, rnd, ci))
                if share == 1.0:
                    assert sum(counts) == cap
        after = telemetry.snapshot()["counters"].get(
            "trainer.upload_inplace_keys", 0)
    finally:
        telemetry.enable(was_on)
    assert after - before == written > 0


def test_the_words_of_an_aggregate_over_the_capacity():
    """An aggregate with more entries than the upload has slots is a
    server running an optimizer: the error says so, word for word as it
    did (that it leaves the held buffer alone is the case
    ``a_round_after_one_that_raised`` above)."""
    tr = _local_trainer(threshold=0.2)
    (_lo, _hi, _flo, _fsize, cap), = tr._chunk_meta
    over = _an_aggregate(tr, 0, _spread(tr, 0, cap + 1), 2)
    with pytest.raises(RuntimeError) as err:
        tr._chunk_up(0, over)
    assert str(err.value) == (
        f"aggregated selection ({cap + 1}) exceeds chunk upload capacity "
        f"({cap}) — is the PS tier running an optimizer? "
        "DeviceResidentTrainer requires aggregator mode")


def test_a_round_allocates_nothing_the_size_of_the_upload():
    """Two calls hand out the one buffer the trainer holds for the
    chunk, and a call's peak of traced memory stays under a constant
    far below one part of the upload (the joined form took five of
    them), int32 positions or int64, and when a round leaves over what
    the last one filled only that stretch is made anew."""
    import tracemalloc

    # never stepped: no grad_fn is traced
    tr = _local_trainer(shapes=[(1 << 21,), (1 << 10, 1 << 10)],
                        threshold=0.125)
    (_lo, _hi, _flo, fsize, cap), = tr._chunk_meta
    assert cap >= 300_000
    rounds = [([cap // 2, cap // 3], 1, np.int32),
              ([cap // 2, cap // 3 - 1000], 2, np.int64),
              ([cap // 2 - 1000, cap // 3], 3, np.int32)]
    aggs = [_an_aggregate(tr, 0, counts, seed, dtype, frame=True)
            for counts, seed, dtype in rounds]
    first = tr._chunk_up(0, aggs[0])
    peaks = []
    for agg, (counts, _seed, _dtype) in zip(aggs, rounds):
        tracemalloc.start()
        try:
            again = tr._chunk_up(0, agg)
            peaks.append(tracemalloc.get_traced_memory()[1])
        finally:
            tracemalloc.stop()
        assert again is first is tr._uploads[0]
        _assert_upload(again, _chunk_up_as_it_was(tr, 0, agg), sum(counts),
                       fsize, cap, counts)
    assert max(peaks) < 256 << 10 < 4 * cap, peaks


def _thinning(tr):
    """Make ``tr``'s store answer round ``r`` with every ``keep[r]``-th
    entry of a key's selection (none where 0), so that a chunk's real
    entries grow, shrink and vanish from round to round as an aggregate
    over workers does."""
    keep = [2, 1, 0, 3, 1]
    inner, calls = tr.kv.push_pull_bsc_batch_async, [0]

    def thinned(keys, vlist, ilist, **kw):
        step = keep[calls[0] // len(tr._chunks) % len(keep)]
        calls[0] += 1
        cut = slice(None, None, step) if step else slice(0, 0)
        return inner(keys, [np.asarray(v)[cut] for v in vlist],
                     [np.asarray(i)[cut] for i in ilist], **kw)

    tr.kv.push_pull_bsc_batch_async = thinned


@pytest.mark.parametrize("slice_bytes", [0, 96])
def test_rounds_on_held_uploads_end_where_fresh_uploads_do(slice_bytes):
    """Five rounds of ``step`` whose aggregates grow, shrink and are
    empty, in one chunk and in several, end bit for bit in the
    parameters and momentum of the same rounds with a fresh upload made
    each time: on the CPU backend ``device_put`` may alias the numpy
    buffer, so this is also what says that no buffer is rewritten while
    an apply can still read it, and that no chunk's is another's."""
    held = _local_trainer(slice_bytes=slice_bytes, threshold=0.2)
    fresh = _local_trainer(slice_bytes=slice_bytes, threshold=0.2)
    fresh._chunk_up = lambda ci, agg: _chunk_up_as_it_was(fresh, ci, agg)
    assert (len(held._chunks) > 1) == bool(slice_bytes)
    assert len({u.ctypes.data for u in held._uploads}) == len(held._chunks)
    filled = []
    for tr in (held, fresh):
        _thinning(tr)
    for rnd in range(5):
        X = jnp.asarray(0.5 - 0.25 * rnd)
        assert held.step(X, None) == fresh.step(X, None)
        filled.append(sum(held._filled))
        for name in ("_flat", "_mom", "_u", "_v"):
            np.testing.assert_array_equal(
                np.asarray(getattr(held, name)).view(np.uint32),
                np.asarray(getattr(fresh, name)).view(np.uint32),
                err_msg=f"{name} after round {rnd}")
    assert filled[2] == 0 and filled[1] > filled[0] > 0  # it did vary
    assert filled[1] > filled[3] > 0
    assert np.any(np.asarray(held._flat) != 0)
