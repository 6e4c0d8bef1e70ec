"""``correct`` has to be able to fail: the control of part (a) at a size
a test run can hold, and the three checks of part (b) on made-up rounds,
each beside a control that breaks what it guards."""

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import correct, manifest
from benchmark.data import pattern


@pytest.fixture(scope="module")
def tiny():
    cfg = manifest.load_config_file("gpt2-small")
    cfg = dict(cfg, **cfg["rehearsal"])
    ref = manifest.family_module("references", cfg["family"])
    mdl = manifest.family_module("models", cfg["family"])
    names, grad_step = mdl.build(cfg, 32)
    return (cfg, ref, names, grad_step, correct.reference_step(ref, cfg),
            correct.reference_step(ref, cfg, cfg["control_dtype"]))


@pytest.mark.parametrize("seed", [3, 2147483700, 4294967311])
def test_the_fp8_control_is_not_correct_and_the_program_is(tiny, seed):
    cfg, ref, names, grad_step, ref_step, control_step = tiny
    limit = cfg["limits"]["grad_rel_l2"]
    params = ref.init_params(cfg, seed)
    toks = jnp.asarray(pattern.batch(np.random.default_rng(seed), 2, 33,
                                     cfg["vocab_size"]))
    prog = correct.reference_errors(ref_step, params, names, grad_step,
                                    toks)
    ctrl = correct.control_errors(ref_step, control_step, params, names,
                                  toks)
    assert prog["grad_rel_l2"] <= limit
    assert ctrl["grad_rel_l2"] > limit
    # the same seed gives the same weights and inputs
    again = ref.init_params(cfg, seed)
    assert all(np.array_equal(params[n], again[n]) for n in names)
    # (b) reads the same number at the cell's batch, in shards of the
    # shape the reference is compiled for; the control fails there too
    batch = pattern.batch(np.random.default_rng(seed + 1), 4, 33,
                          cfg["vocab_size"])
    want = correct.batch_gradient(ref_step, params, names, batch, 2)
    whole = ref_step(params, jnp.asarray(batch))[1]
    flat = np.concatenate([np.asarray(whole[n]).ravel() for n in names])
    assert correct.rel_l2(want, flat) < 1e-5
    low = correct.batch_gradient(control_step, params, names, batch, 2)
    assert correct.rel_l2(low, want) > limit


def _round(n=1000, k=50, seed=0, pushers=2):
    rng = np.random.default_rng(seed)
    pushed = []
    for _w in range(pushers):
        idx = np.sort(rng.choice(n, k, replace=False))
        pushed.append({7: (rng.normal(size=k).astype(np.float32), idx)})
    dense = np.zeros(n, np.float32)
    for p in pushed:
        dense[p[7][1]] += p[7][0]
    nz = np.nonzero(dense)[0]
    applied = [{7: (dense[nz], nz)} for _ in range(pushers)]
    return pushed, applied


@pytest.mark.parametrize("pushers", [1, 2, 3, 5, 16])
def test_aggregate_exact_sum_passes_and_is_complete(pushers):
    pushed, applied = _round(pushers=pushers, k=300)
    out = correct.check_aggregate(pushed, applied)
    assert out["exact"] and out["complete"]
    assert out["pusher_share_min"] == 1.0


def test_aggregate_sum_in_another_order_passes():
    """Three or more terms: the servers sum in the order of arrival."""
    pushed, applied = _round(pushers=5, k=400, seed=3)
    dense = np.zeros(1000, np.float32)
    for p in reversed(pushed):
        dense[p[7][1]] += p[7][0]
    nz = applied[0][7][1]
    out = correct.check_aggregate(pushed, [{7: (dense[nz], nz)}] * 5)
    assert out["exact"] and out["complete"]


def test_aggregate_subset_passes_but_is_not_complete():
    pushed, applied = _round()
    v, i = applied[0][7]
    applied = [{7: (v[::2], i[::2])} for _ in range(2)]
    out = correct.check_aggregate(pushed, applied)
    assert out["exact"] and not out["complete"]
    assert 0.3 < out["pusher_share_min"] < 0.7


@pytest.mark.parametrize("pushers", [2, 4])
def test_aggregate_part_of_its_pushers_passes(pushers):
    pushed = [{1: (np.float32([1.5, 2.0]), np.int64([3, 9]))},
              {1: (np.float32([0.25, 4.0]), np.int64([3, 5]))}]
    pushed += [{1: (np.float32([8.0]), np.int64([3]))}] * (pushers - 2)
    applied = [{1: (np.float32([1.5, 4.0]), np.int64([3, 5]))}] * pushers
    out = correct.check_aggregate(pushed, applied)
    assert out["exact"] and out["part_of_its_pushers"] == 1


def test_a_silenced_party_reads_share_zero():
    """The control of the floor on ``pusher_share_min``: the aggregate
    holds one party's entries only, each of them exact."""
    pushed, _ = _round()
    out = correct.check_aggregate(pushed, [{7: pushed[0][7]}] * 2)
    assert out["exact"] and out["pusher_share_min"] == 0.0
    cell = manifest.load_cell("gpt2s-hips-bsc")["spec"]
    assert out["pusher_share_min"] < cell["limits"]["pusher_share_min"]


def test_aggregate_failures_are_seen():
    pushed, applied = _round()
    v, i = applied[0][7]
    wrong = v.copy()
    wrong[3] = np.nextafter(wrong[3], np.float32(10))
    assert not correct.check_aggregate(
        pushed, [{7: (wrong, i)}, {7: (wrong, i)}])["exact"]
    assert not correct.check_aggregate(
        pushed, [{7: (v, i)}, {7: (wrong, i)}])["exact"]    # workers differ
    free = np.setdiff1d(np.arange(1000), i)[:1]
    stranger = (np.append(v, np.float32(1.0)), np.append(i, free))
    out = correct.check_aggregate(pushed, [{7: stranger}] * 2)
    assert not out["exact"] and out["not_pushed_by_anyone"] == 1
    twice = (np.append(v, v[:1]), np.append(i, i[:1]))
    out = correct.check_aggregate(pushed, [{7: twice}] * 2)
    assert not out["exact"] and out["duplicates"] == 1
    empty = (np.zeros(0, np.float32), np.zeros(0, np.int64))
    assert not correct.check_aggregate(pushed, [{7: empty}] * 2)["exact"]
    # three terms, one ulp beyond the bound of any order of summation
    pushed, applied = _round(pushers=3, k=900, seed=1)
    v, i = applied[0][7]
    far = v.copy()
    far[:] = v * np.float32(1 + 2.0 ** -18)
    out = correct.check_aggregate(pushed, [{7: (far, i)}] * 3)
    assert out["value_not_a_sum"] > 0


SIZES, THRESHOLD = [400, 7, 1000], 0.01


def _select(seed=0, approximate=False):
    """One worker's first round as the trainer makes it: per key the
    top ``max(int(size * threshold), 1)`` of the gradient leave, and the
    accumulator is cleared there. ``approximate``: the largest key
    misses its largest entry and takes the next one instead."""
    rng = np.random.default_rng(seed)
    grad = rng.normal(size=sum(SIZES)).astype(np.float32)
    v, pushed, off = grad.copy(), {}, 0
    for key, size in enumerate(SIZES):
        k = max(int(size * THRESHOLD), 1)
        order = np.argsort(-np.abs(grad[off:off + size]))
        idx = order[1:k + 1] if approximate and size == 1000 else order[:k]
        pushed[key] = (grad[off + idx].copy(), idx)
        v[off + idx] = 0.0
        off += size
    return pushed, v, grad


def test_select_exact_topk_passes_and_an_approximate_one_does_not():
    pushed, v, grad = _select()
    out = correct.check_select(pushed, v, SIZES, THRESHOLD, grad=grad)
    assert (out["keys_bad_count"], out["keys_not_topk"],
            out["not_cleared"]) == (0, 0, 0)
    assert out["grad_rel_l2"] == 0.0
    pushed, v, grad = _select(approximate=True)
    assert correct.check_select(pushed, v, SIZES, THRESHOLD)[
        "keys_not_topk"] == 1


@pytest.mark.parametrize("fault", ["count", "twice", "not_cleared",
                                   "scaled"])
def test_select_failures_are_seen(fault):
    pushed, v, grad = _select(seed=5)
    vals, idx = pushed[2]
    if fault == "count":
        pushed[2] = (vals[:-1], idx[:-1])
    elif fault == "twice":
        pushed[2] = (vals, np.append(idx[:-1], idx[0]))
    elif fault == "not_cleared":
        v[sum(SIZES[:2]) + idx[0]] = vals[0]
    else:       # the wire carries half of what the accumulator held
        pushed = {k: (a / 2, i) for k, (a, i) in pushed.items()}
    out = correct.check_select(pushed, v, SIZES, THRESHOLD, grad=grad)
    assert (out["keys_bad_count"] or out["not_cleared"]
            or out["grad_rel_l2"] > 0.1)


def _apply(seed=0, lr=0.05):
    rng = np.random.default_rng(seed)
    flat0 = (0.02 * rng.normal(size=sum(SIZES))).astype(np.float32)
    applied, g, off = {}, np.zeros_like(flat0), 0
    for key, size in enumerate(SIZES):
        idx = np.sort(rng.choice(size, max(size // 50, 1), replace=False))
        vals = (1e-3 * rng.normal(size=len(idx))).astype(np.float32)
        applied[key] = (vals, idx)
        g[off + idx] = vals
        off += size
    return flat0, flat0 - np.float32(lr) * g, applied


def test_apply_in_float32_passes_and_in_bfloat16_does_not():
    flat0, flat1, applied = _apply()
    out = correct.check_apply(flat0, flat1, applied, SIZES, 0.05,
                              control=True)
    assert out["max_ulps"] == 0 and out["mismatched_untouched"] == 0
    assert out["touched"] == 8 + 1 + 20
    assert out["control_max_ulps"] > 1000 > correct.APPLY_MAX_ULPS


@pytest.mark.parametrize("fault", ["lr", "half_the_aggregate", "drift"])
def test_apply_failures_are_seen(fault):
    flat0, flat1, applied = _apply(seed=2)
    if fault == "lr":
        out = correct.check_apply(flat0, flat1, applied, SIZES, 0.1)
    elif fault == "half_the_aggregate":
        half = {k: (v / 2, i) for k, (v, i) in applied.items()}
        out = correct.check_apply(flat0, flat1, half, SIZES, 0.05)
    else:       # a parameter the aggregate does not reach moved
        free = np.setdiff1d(np.arange(SIZES[0]), applied[0][1])[0]
        flat1[free] = np.nextafter(flat1[free], np.float32(1))
        out = correct.check_apply(flat0, flat1, applied, SIZES, 0.05)
    assert (out["max_ulps"] > correct.APPLY_MAX_ULPS
            or out["mismatched_untouched"] == 1)
