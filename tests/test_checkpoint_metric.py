"""Checkpoint/resume, metric library, and Trainer tests."""

import numpy as np
import pytest

from geomx_tpu import checkpoint, metric
from geomx_tpu.optimizer import Adam, SGD
from geomx_tpu.trainer import Trainer
from geomx_tpu.kvstore.local import KVStoreLocal


# -- checkpoint ----------------------------------------------------------

def test_checkpoint_roundtrip(tmp_path):
    prefix = str(tmp_path / "model")
    params = {"dense": {"w": np.arange(6, dtype=np.float32).reshape(2, 3),
                        "b": np.zeros(3, np.float32)}}
    meta = {"iter": 42, "lr": 0.1}
    path = checkpoint.save_checkpoint(prefix, 3, params, metadata=meta)
    assert path.endswith("model-0003.ckpt")
    got, opt, got_meta = checkpoint.load_checkpoint(prefix, 3)
    np.testing.assert_array_equal(got["dense"]["w"], params["dense"]["w"])
    np.testing.assert_array_equal(got["dense"]["b"], params["dense"]["b"])
    assert opt is None
    assert got_meta["iter"] == 42 and abs(got_meta["lr"] - 0.1) < 1e-9


def test_latest_checkpoint(tmp_path):
    prefix = str(tmp_path / "ck")
    assert checkpoint.latest_checkpoint(prefix) is None
    for e in (1, 4, 2):
        checkpoint.save_checkpoint(prefix, e, [np.zeros(2, np.float32)])
    assert checkpoint.latest_checkpoint(prefix) == 4


def test_optimizer_state_roundtrip(tmp_path):
    fname = str(tmp_path / "opt.states")
    opt = Adam(learning_rate=0.01)
    w = np.ones(4, np.float32)
    for _ in range(3):
        w = opt.update(0, w, np.full(4, 0.5, np.float32))
    checkpoint.save_optimizer_states(fname, opt)

    opt2 = Adam(learning_rate=0.01)
    checkpoint.load_optimizer_states(fname, opt2)
    s1, s2 = opt.get_states()[0], opt2.get_states()[0]
    assert s2["t"] == s1["t"] == 3
    np.testing.assert_allclose(s2["m"], s1["m"])
    np.testing.assert_allclose(s2["v"], s1["v"])
    # both must produce identical continued trajectories
    w1 = opt.update(0, w.copy(), np.full(4, 0.5, np.float32))
    w2 = opt2.update(0, w.copy(), np.full(4, 0.5, np.float32))
    np.testing.assert_allclose(w1, w2)


def test_kvstore_optimizer_state_save_load(tmp_path):
    kv = KVStoreLocal()
    kv.set_optimizer(SGD(learning_rate=0.1, momentum=0.9))
    kv.init(0, np.zeros(4, np.float32))
    kv.push(0, np.ones(4, np.float32))
    fname = str(tmp_path / "kv.states")
    kv.save_optimizer_states(fname)

    kv2 = KVStoreLocal()
    kv2.set_optimizer(SGD(learning_rate=0.1, momentum=0.9))
    kv2.load_optimizer_states(fname)
    np.testing.assert_allclose(kv2._optimizer.get_states()[0],
                               kv._optimizer.get_states()[0])


# -- metric --------------------------------------------------------------

def test_accuracy_and_topk():
    acc = metric.create("acc")
    scores = np.array([[0.1, 0.9], [0.8, 0.2], [0.3, 0.7]])
    labels = np.array([1, 0, 0])
    acc.update(labels, scores)
    assert acc.get() == ("accuracy", pytest.approx(2 / 3))

    topk = metric.TopKAccuracy(top_k=2)
    s = np.array([[0.5, 0.3, 0.2], [0.1, 0.2, 0.7]])
    topk.update(np.array([1, 0]), s)  # label1 in top2 of row0; label0 not
    assert topk.get()[1] == pytest.approx(0.5)


def test_f1_and_regression_metrics():
    f1 = metric.F1()
    f1.update(np.array([1, 1, 0, 0]), np.array([1, 0, 1, 0]))
    # tp=1 fp=1 fn=1 -> prec=rec=0.5 -> f1=0.5
    assert f1.get()[1] == pytest.approx(0.5)

    mae = metric.create("mae")
    mae.update(np.array([1.0, 2.0]), np.array([2.0, 4.0]))
    assert mae.get()[1] == pytest.approx(1.5)

    rmse = metric.create("rmse")
    rmse.update(np.array([0.0, 0.0]), np.array([3.0, 4.0]))
    assert rmse.get()[1] == pytest.approx(np.sqrt(12.5))


def test_cross_entropy_perplexity_composite():
    ce = metric.CrossEntropy()
    probs = np.array([[0.5, 0.5], [0.9, 0.1]])
    ce.update(np.array([0, 0]), probs)
    expect = -(np.log(0.5) + np.log(0.9)) / 2
    assert ce.get()[1] == pytest.approx(expect)

    comp = metric.create(["acc", "mae"])
    comp.update(np.array([1]), np.array([[0.2, 0.8]]))
    names, values = comp.get()
    assert names == ["accuracy", "mae"]

    with pytest.raises(ValueError):
        metric.create("nope")


# -- trainer -------------------------------------------------------------

def test_trainer_local_sgd_step(tmp_path):
    kv = KVStoreLocal()
    kv.set_optimizer(SGD(learning_rate=0.5))
    w = [np.ones((2, 2), np.float32), np.zeros(3, np.float32)]
    tr = Trainer([l.copy() for l in w], kv)
    tr.step([np.ones((2, 2), np.float32), np.ones(3, np.float32)])
    np.testing.assert_allclose(tr.leaves[0], 0.5 * np.ones((2, 2)))
    np.testing.assert_allclose(tr.leaves[1], -0.5 * np.ones(3))

    # checkpoint + resume restores parameters
    prefix = str(tmp_path / "tr")
    tr.save(prefix, 1, metadata={"it": 7})
    kv2 = KVStoreLocal()
    kv2.set_optimizer(SGD(learning_rate=0.5))
    tr2 = Trainer.load(prefix, 1, kv2)
    np.testing.assert_allclose(tr2.leaves[0], tr.leaves[0])
    np.testing.assert_allclose(tr2.leaves[1], tr.leaves[1])


def test_dist_optimizer_states_roundtrip(tmp_path):
    """In HiPS the live optimizer states sit on the global server; the
    master worker's save must fetch them over the command channel."""
    from tests.harness import Topology, _parallel

    topo = Topology().start(sync_global=True)
    fname = str(tmp_path / "dist.states")
    try:
        topo.master.set_optimizer(Adam(learning_rate=0.01))
        w0 = np.ones((4, 4), np.float32)

        def init_on(kv):
            kv.init(0, w0)
            if not kv.is_master_worker:
                kv.pull(0)

        _parallel([lambda kv=kv: init_on(kv)
                   for kv in topo.workers + [topo.master]])

        def push_pull(kv):
            kv.push(0, np.ones((4, 4), np.float32))
            kv.pull(0)
            kv.wait()

        for _ in range(2):
            _parallel([lambda kv=kv: push_pull(kv) for kv in topo.workers])

        topo.master.save_optimizer_states(fname)
        import json
        with open(fname) as f:
            per_server = json.load(f)
        from geomx_tpu import checkpoint as ck
        states = ck.deserialize_states(
            bytes.fromhex(next(iter(per_server.values()))))
        # server updater is keyed by (key, shard_offset); Adam ran 2
        # rounds on key 0 -> t == 2 with nonzero moments
        assert states[(0, 0)]["t"] == 2
        assert np.abs(states[(0, 0)]["m"]).max() > 0

        # restore must be accepted by the server without error
        topo.master.load_optimizer_states(fname)
    finally:
        topo.stop()


if __name__ == "__main__":
    import sys

    sys.exit(pytest.main([__file__, "-x", "-q"]))
