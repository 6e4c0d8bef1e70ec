"""A sparse payload's positions on the two tiers of a live topology:
coded (``compression.entries.CODED``) on the party-global link, both
directions, int32 on the LAN; the aggregate, and everything the LAN
carries, what a tree without the code gives to the bit."""

import numpy as np
import pytest

from geomx_tpu import telemetry
from geomx_tpu.compression import SPARSE_TAGS
from geomx_tpu.compression.entries import CODED, decode_positions
from geomx_tpu.kvstore import server as server_mod
from geomx_tpu.ps.kv_app import _unpack_kv
from geomx_tpu.ps.van import Van
from geomx_tpu.simulate import InProcessHiPS

SIZES = {0: 400_000, 1: 60_000, 2: 513}


def _counter(name):
    return sum(v for k, v in telemetry.snapshot()["counters"].items()
               if k.startswith(name))


def _selection(widx, rnd, n):
    """1% of a key: distinct positions in the order ``lax.top_k`` would
    give them, whole-numbered values (their float32 sums are exact in
    any order)."""
    rng = np.random.default_rng(1000 * widx + 10 * rnd + n % 7)
    idx = rng.choice(n, max(n // 100, 1), replace=False)
    return (rng.integers(1, 9, idx.size).astype(np.float32),
            idx.astype(np.int64))


def _sparse_rounds(monkeypatch, *, sizes=SIZES, threshold=0.01, rounds=2,
                   workers_per_party=1, extra_cfg=None, coded=True,
                   by_magnitude=False):
    """``rounds`` Bi-Sparse rounds of two parties through the real vans
    -> (what each worker got a round, the sparse frames sent, the two
    counters, ``telemetry.wan_bytes()``). ``coded=False`` is the tree
    without the code; ``by_magnitude`` makes the party servers'
    selection hand its positions in descending order."""
    frames = []
    real_send = Van._send_one

    def send_one(van, target, msg):
        if (not msg.is_control and len(msg.data) > 4
                and msg.meta.compr in SPARSE_TAGS):
            kvs = _unpack_kv(msg)
            frames.append({
                "tier": "global" if van.is_global else "local",
                "request": bool(msg.meta.request),
                "compr": msg.meta.compr, "keys": list(kvs.keys),
                "lens": list(kvs.lens),
                "vals": [np.array(v) for v in kvs.vals],
                "aux": [np.array(a) for a in kvs.aux],
            })
        return real_send(van, target, msg)

    # both patches are this run's alone: the next run starts from the
    # tree as it is
    with monkeypatch.context() as patch:
        patch.setattr(Van, "_send_one", send_one)
        if not coded:
            patch.setattr(server_mod, "_link_positions", lambda idx: idx)
        got, late, booked, wan = _run(sizes, threshold, rounds,
                                      workers_per_party, extra_cfg,
                                      by_magnitude, frames)
    assert not any(v.size for f in frames[late:]
                   if len(f["keys"]) < len(sizes) for v in f["vals"])
    return (got, [f for f in frames[late:] if len(f["keys"]) == len(sizes)],
            booked, wan)


def _run(sizes, threshold, rounds, workers_per_party, extra_cfg,
         by_magnitude, frames):
    topo = InProcessHiPS(num_parties=2, workers_per_party=workers_per_party,
                         extra_cfg=extra_cfg).start()
    got = {}
    try:
        def master_init(kv):
            kv.set_gradient_compression({"type": "bsc",
                                         "threshold": threshold})
            for k, n in sizes.items():
                kv.init(k, np.zeros(n, np.float32))
            kv.wait()

        def init(kv):
            for k, n in sizes.items():
                kv.init(k, np.zeros(n, np.float32))
            kv.wait()

        topo.run_workers(init, include_master=master_init, timeout=120)
        if by_magnitude:
            for srv in topo.servers:
                if not srv.has_global_tier:
                    continue

                def compress_push(arr, state_key=None,
                                  _real=srv.gc.compress_push, **kw):
                    vals, idx, tag = _real(arr, state_key, **kw)
                    return vals[::-1], idx[::-1], tag

                srv.gc.compress_push = compress_push
        # an init-time pull-back may still be on its way: it carries no
        # entry (the store is zeros) and is not a round's frame
        late = len(frames)
        telemetry.reset()
        telemetry.enable(True)

        def train(kv):
            widx = topo.workers.index(kv)
            out = got[widx] = []
            for rnd in range(rounds):
                sel = [_selection(widx, rnd, n) for n in sizes.values()]
                agg = kv.push_pull_bsc_batch(
                    list(sizes), [s[0] for s in sel], [s[1] for s in sel],
                    timeout=120)()
                out.append({k: agg[k] for k in sizes})

        topo.run_workers(train, timeout=240)
        booked = (_counter("wire.index_bytes_coded"),
                  _counter("wire.index_bytes_plain"))
        wan = telemetry.wan_bytes()
    finally:
        telemetry.reset()
        topo.stop()
    return got, late, booked, wan


def _same_results(a, b):
    assert a.keys() == b.keys()
    for widx in a:
        assert len(a[widx]) == len(b[widx])
        for x, y in zip(a[widx], b[widx]):
            for k in x:
                np.testing.assert_array_equal(x[k][1], y[k][1])
                np.testing.assert_array_equal(
                    x[k][0].view(np.uint32), y[k][0].view(np.uint32))


def _parts(frames, tier):
    """The frames of a tier by their content, whatever order the
    threads sent them in: (direction, tag, keys, every part's type and
    bytes)."""
    return sorted(
        (f["request"], f["compr"], tuple(f["keys"]),
         tuple((v.dtype.str, v.tobytes()) for v in f["vals"]),
         tuple((a.dtype.str, a.tobytes()) for a in f["aux"]))
        for f in frames if f["tier"] == tier)


def test_the_link_carries_the_code_and_the_lan_does_not(monkeypatch):
    got, frames, (coded, plain), wan = _sparse_rounds(monkeypatch)
    wan_frames = [f for f in frames if f["tier"] == "global"]
    lan_frames = [f for f in frames if f["tier"] == "local"]
    # a round: each party's forward and its answer; each worker's push
    # and its answer
    assert len(wan_frames) == 8 and len(lan_frames) == 8
    assert {f["request"] for f in wan_frames} == {True, False}
    entries = nbytes = 0
    for f in wan_frames:
        for vals, aux, n in zip(f["vals"], f["aux"], f["lens"]):
            assert aux.dtype == CODED and aux.size % 4 == 0
            idx = decode_positions(aux, vals.size, n)
            assert idx.dtype == np.int32 and (np.diff(idx) > 0).all()
            entries += vals.size
            nbytes += aux.nbytes
    for f in lan_frames:
        assert all(aux.dtype == np.int32 for aux in f["aux"])
    # what was booked is what was sent, and none of it went plain
    assert coded == nbytes and plain == 0
    assert 1.0 <= nbytes / entries <= 1.4
    # 8 bytes an entry without the code: headers and all, at most 0.70
    assert wan / entries <= 0.70 * 8
    # the tree without the code: the same aggregate to the bit, the
    # same LAN to the byte, the link's values untouched
    was, was_frames, (c0, p0), was_wan = _sparse_rounds(monkeypatch,
                                                        coded=False)
    _same_results(got, was)
    assert _parts(frames, "local") == _parts(was_frames, "local")
    assert c0 == 0 and p0 == 4 * entries
    assert wan / was_wan <= 0.70
    then = [f for f in was_frames if f["tier"] == "global"]
    key = lambda f: (f["request"], [v.tobytes() for v in f["vals"]])  # noqa
    for f, g in zip(sorted(wan_frames, key=key), sorted(then, key=key)):
        assert f["keys"] == g["keys"] and f["compr"] == g["compr"]
        for vals, aux, plain_idx, n in zip(f["vals"], f["aux"], g["aux"],
                                           f["lens"]):
            assert plain_idx.dtype == np.int32
            np.testing.assert_array_equal(
                decode_positions(aux, vals.size, n), plain_idx)
    # every worker of the round applies the same
    for rnd in range(2):
        for k in SIZES:
            a, b = got[0][rnd][k], got[1][rnd][k]
            np.testing.assert_array_equal(a[1], b[1])
            np.testing.assert_array_equal(a[0], b[0])


def test_what_the_workers_apply_is_the_float32_sum_of_the_pushes(
        monkeypatch):
    """Two parties of two workers, every party server forwarding all it
    holds (threshold 1): four pushes a key through the coded link come
    back as their numpy float32 sum, bit for bit, to all four."""
    sizes = {0: 5_000, 1: 300, 2: 1}
    got, frames, (coded, plain), _wan = _sparse_rounds(
        monkeypatch, sizes=sizes, threshold=1.0, rounds=1,
        workers_per_party=2)
    assert coded > 0 and plain == 0
    assert all(aux.dtype == CODED for f in frames if f["tier"] == "global"
               for aux in f["aux"])
    for k, n in sizes.items():
        want = np.zeros(n, np.float32)
        for widx in range(4):
            vals, idx = _selection(widx, 0, n)
            np.add.at(want, idx, vals)
        for widx in range(4):
            vals, idx = got[widx][0][k]
            dense = np.zeros(n, np.float32)
            dense[idx] = vals
            np.testing.assert_array_equal(dense.view(np.uint32),
                                          want.view(np.uint32))
            assert (vals != 0).all() and idx.size == np.count_nonzero(want)


def test_positions_by_magnitude_go_plain_and_are_booked(monkeypatch):
    """A selection that hands its positions in another order than
    ascending crosses as it always did (int32), booked as a miss; the
    global server's answer, which is ``Entries``, is coded all the
    same, and the aggregate is the ascending selection's."""
    want, _f, _b, _w = _sparse_rounds(monkeypatch, rounds=1)
    got, frames, (coded, plain), _wan = _sparse_rounds(
        monkeypatch, rounds=1, by_magnitude=True)
    forwards = [f for f in frames if f["tier"] == "global" and f["request"]]
    answers = [f for f in frames
               if f["tier"] == "global" and not f["request"]]
    assert len(forwards) == len(answers) == 2
    for f in forwards:
        assert all(aux.dtype == np.int32 for aux in f["aux"])
        assert any((np.diff(aux) < 0).any() for aux in f["aux"])
    assert plain == sum(aux.nbytes for f in forwards for aux in f["aux"])
    assert all(aux.dtype == CODED for f in answers for aux in f["aux"])
    assert coded == sum(aux.nbytes for f in answers for aux in f["aux"])
    _same_results(got, want)


def test_a_bsc16_round_codes_its_positions_too(monkeypatch):
    """The quantized combined wire: float16 values on every leg, and on
    the link the same coded positions; the tag goes on naming the
    values."""
    got, frames, (coded, plain), _wan = _sparse_rounds(
        monkeypatch, sizes={0: 60_000, 1: 513}, rounds=1,
        extra_cfg={"wire_codec": "fp16"})
    wan_frames = [f for f in frames if f["tier"] == "global"]
    assert len(wan_frames) == 4 and coded > 0 and plain == 0
    for f in frames:
        assert f["compr"] == "bsc16"
        for vals, aux in zip(f["vals"], f["aux"]):
            assert vals.dtype == np.float16
            assert aux.dtype == (CODED if f["tier"] == "global"
                                 else np.int32)
    was, _f, _b, _w = _sparse_rounds(
        monkeypatch, sizes={0: 60_000, 1: 513}, rounds=1,
        extra_cfg={"wire_codec": "fp16"}, coded=False)
    _same_results(got, was)
    assert any(v[0].size for v in got[0][0].values())
