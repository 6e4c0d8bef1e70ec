"""OLMoE through the program: the model against the benchmark's plain
float32 reference, the no-drop sparse dispatch against the dense
every-expert formulation, one rank's share against the whole, and one
two-party HiPS round through the device-resident trainer.

Tiny widths, seeded weights, CPU. The published widths are compared on
the chip (``benchmark/tests/chip_limits.py``, PERF.md section 2).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.models import olmoe as bench_model
from benchmark.references import olmoe as reference
from geomx_tpu import telemetry
from geomx_tpu.models.moe import dispatch_cap, sparse_dispatch
from geomx_tpu.models.olmoe import Olmoe, OlmoeBlock
from geomx_tpu.simulate import InProcessHiPS
from geomx_tpu.trainer_device import DeviceResidentTrainer

TINY = dict(
    family="olmoe", compute_dtype="float32", hidden_size=64,
    intermediate_size=32, num_attention_heads=4, num_hidden_layers=2,
    num_experts=8, num_experts_per_tok=2, vocab_size=128,
    rms_norm_eps=1e-5, rope_theta=10000, local_experts=[0, 4],
    router_aux_loss_coef=0.01, microbatch_sequences=1)
SEQ = 32
PARAM_SEED, TOKEN_SEED = 2147483659, 8


def _tokens(seed, batch=2):
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, TINY["vocab_size"], (batch, SEQ + 1)), jnp.int32)


def _leaf_errors(cfg, operand_dtype=None, system=True):
    """Relative error of the loss and relative L2 error of every
    gradient leaf against the float32 reference: of the program's model
    (``system``) or of the reference with rounded matmul operands."""
    params = reference.init_params(cfg, PARAM_SEED)
    toks = _tokens(TOKEN_SEED)
    loss_r, grads_r = jax.jit(
        lambda p, x: reference.loss_and_grads(p, x, cfg))(params, toks)
    if system:
        names, grad_step = bench_model.build(cfg, SEQ)
        loss, grads = jax.jit(grad_step)(
            bench_model.leaves_from(params, names), toks, None)
        grads = dict(zip(names, grads))
    else:
        loss, grads = jax.jit(lambda p, x: reference.loss_and_grads(
            p, x, cfg, operand_dtype))(params, toks)
    errs = {n: float(jnp.linalg.norm(grads[n] - g) / jnp.linalg.norm(g))
            for n, g in grads_r.items()}
    return abs(float(loss) - float(loss_r)) / float(loss_r), errs


# bfloat16 keeps 8 bits of significand: a rounded operand is off by up
# to 2^-9 relative, and a leaf's gradient passes a few such matmuls.
# Measured here: bfloat16 reads 0.004 to 0.013 a leaf, the same
# mathematics with float8_e4m3 operands 0.06 to 0.19. 0.03 sits between,
# so computing in the next precision down fails on every leaf.
# Top-k routing is discrete: where a token's k-th and (k+1)-th router
# probabilities nearly tie, a rounding upstream sends it to another
# expert and that layer's leaves jump by 0.1 and more at 64 tokens. The
# seeds above were chosen for a clear margin (the first test holds them
# to it); the chip's comparison has 8,192 tokens to average over.
LEAF_TOL = 0.03
ROUTING_MARGIN = 2e-3


def test_the_seeds_leave_routing_a_margin():
    params = reference.init_params(TINY, PARAM_SEED)
    names, _ = bench_model.build(TINY, SEQ)
    tree = {}
    for name in names:
        *path, leaf = name.split("/")
        node = tree
        for part in path:
            node = node.setdefault(part, {})
        node[leaf] = params[name]
    model = Olmoe(vocab=128, dim=64, depth=2, heads=4, num_experts=8,
                  experts_per_token=2, expert_width=32, local_experts=(0, 4))
    probs = model.apply({"params": tree}, _tokens(TOKEN_SEED)[:, :-1])[1]
    ranked = jnp.sort(probs, -1)[..., ::-1]
    gap = (ranked[..., 1] - ranked[..., 2]) / ranked[..., 1]
    assert float(gap.min()) > ROUTING_MARGIN


@pytest.mark.parametrize("dtype,loss_tol,leaf_tol", [
    ("float32", 1e-5, 1e-5), ("bfloat16", 1e-4, LEAF_TOL)])
def test_model_matches_the_float32_reference(dtype, loss_tol, leaf_tol):
    loss_err, errs = _leaf_errors(dict(TINY, compute_dtype=dtype))
    assert len(errs) == 27
    assert loss_err <= loss_tol
    over = {n: e for n, e in errs.items() if e > leaf_tol}
    assert not over, over


def test_float8_operands_fail_the_bfloat16_tolerance():
    _loss_err, errs = _leaf_errors(TINY, "float8_e4m3fn", system=False)
    under = {n: e for n, e in errs.items() if e <= LEAF_TOL}
    assert not under, under


# -- the dispatch -----------------------------------------------------------

E, D, W, N = 8, 16, 12, 24


def _ffn_weights(rng, experts=E):
    return tuple(jnp.asarray(rng.normal(0, 0.3, s), jnp.float32)
                 for s in ((experts, D, W), (experts, D, W),
                           (experts, W, D)))


def _sparse(h, chosen, gates, weights, local=(0, E), num_experts=None):
    lo, hi = local
    return sparse_dispatch(h, chosen, gates,
                           _gated(*(w[lo:hi] for w in weights)), local,
                           num_experts)


def _gated(w_gate, w_up, w_down):
    def experts(rows, group_sizes, _row_expert):
        a = jax.nn.silu(jax.lax.ragged_dot(rows, w_gate, group_sizes)) \
            * jax.lax.ragged_dot(rows, w_up, group_sizes)
        return jax.lax.ragged_dot(a, w_down, group_sizes)

    return experts


def _dense(h, chosen, gates, weights, local=(0, E)):
    """Every expert computes every row; the router's mask picks."""
    w_gate, w_up, w_down = weights
    experts = len(w_gate)
    act = jax.nn.silu(jnp.einsum("nd,edw->enw", h, w_gate)) \
        * jnp.einsum("nd,edw->enw", h, w_up)
    out = jnp.einsum("enw,ewd->end", act, w_down)
    mask = jnp.einsum("nk,nke->en", gates,
                      jax.nn.one_hot(chosen, experts, dtype=h.dtype))
    held = (jnp.arange(experts) >= local[0]) \
        & (jnp.arange(experts) < local[1])
    return jnp.einsum("en,end->nd", mask * held[:, None], out)


def _routing(rng, k, case):
    if case == "one_expert_takes_all":
        # every row's first choice is expert 3: 24 rows in one group
        rest = np.stack([rng.permutation(np.delete(np.arange(E), 3))[:k - 1]
                         for _ in range(N)]).reshape(N, k - 1)
        chosen = np.concatenate([np.full((N, 1), 3), rest], 1)
    else:
        pool = np.arange(E) if case == "random" else np.delete(
            np.arange(E), 5)        # expert 5 gets no row
        chosen = np.stack([rng.permutation(pool)[:k] for _ in range(N)])
    gates = rng.uniform(0.05, 1.0, (N, k))
    return jnp.asarray(chosen, jnp.int32), jnp.asarray(gates, jnp.float32)


# top-8 of 8 leaves no expert out: that pairing does not exist
@pytest.mark.parametrize("k,case", [
    (k, case) for k in (1, 2, 8)
    for case in ("random", "an_expert_gets_no_row", "one_expert_takes_all")
    if (k, case) != (E, "an_expert_gets_no_row")])
def test_sparse_dispatch_is_the_dense_formulation(k, case):
    rng = np.random.default_rng(k)
    weights = _ffn_weights(rng)
    h = jnp.asarray(rng.normal(size=(N, D)), jnp.float32)
    chosen, gates = _routing(rng, k, case)
    y, sizes = jax.jit(_sparse)(h, chosen, gates, weights)
    np.testing.assert_allclose(y, _dense(h, chosen, gates, weights),
                               rtol=2e-5, atol=2e-6)
    # nothing dropped: every (row, slot) pair is in some expert's group
    assert int(sizes.sum()) == N * k
    np.testing.assert_array_equal(
        sizes, np.bincount(np.asarray(chosen).ravel(), minlength=E))

    def loss(fn, h, gates, weights):
        out = fn(h, chosen, gates, weights)
        out = out[0] if isinstance(out, tuple) else out
        return jnp.sum(jnp.sin(out))

    got = jax.jit(jax.grad(lambda *a: loss(_sparse, *a), (0, 1, 2)))(
        h, gates, weights)
    want = jax.grad(lambda *a: loss(_dense, *a), (0, 1, 2))(
        h, gates, weights)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)


# -- the compact path: a router of 64, experts 2..4 held, 512 rows of
# top-4: 2,048 pairs, a cap of 512, so up to four tiles --------------------

WIDE, HELD, ROWS, TOP = 64, (2, 5), 512, 4


def _held_routing(rng, case):
    """[ROWS, TOP] choices whose held pairs (experts 2, 3, 4) number
    what ``case`` says; every other slot goes to an expert held
    elsewhere."""
    away = np.delete(np.arange(WIDE), np.arange(*HELD))
    chosen = np.stack([rng.permutation(away)[:TOP] for _ in range(ROWS)])
    if case == "fewer_than_the_cap":        # what a router sends: ~96
        chosen = np.stack([rng.permutation(WIDE)[:TOP]
                           for _ in range(ROWS)])
    elif case == "exactly_the_cap":         # 256 rows x experts 2 and 3
        chosen[:256, 1], chosen[:256, 3] = 2, 3
    elif case == "two_tiles":               # expert 3 takes all, + 100
        chosen[:, 0] = 3
        chosen[:100, 2] = 4
    elif case == "three_tiles":             # 512 x (2, 3) and 7 more
        chosen[:, 3], chosen[:, 1] = 2, 3
        chosen[5:12, 0] = 4
    else:
        assert case == "no_held_pair"
    gates = rng.uniform(0.05, 1.0, (ROWS, TOP))
    return jnp.asarray(chosen, jnp.int32), jnp.asarray(gates, jnp.float32)


HELD_PAIRS = {"fewer_than_the_cap": None, "exactly_the_cap": 512,
              "two_tiles": 612, "three_tiles": 1031, "no_held_pair": 0}


@pytest.mark.parametrize("case,remat,dtype", [
    *((case, False, "float32") for case in HELD_PAIRS),
    ("fewer_than_the_cap", True, "float32"),
    ("three_tiles", True, "float32"),
    ("fewer_than_the_cap", False, "bfloat16"),
    ("two_tiles", False, "bfloat16")])
def test_compact_dispatch_is_the_dense_formulation(case, remat, dtype):
    """With the router's width given and 3 of 64 experts held the
    dispatch gathers 512 rows a tile, not 2,048: the result and every
    gradient are the dense formulation's whatever the held count (no
    tile, one, two with an expert's rows across the boundary, three),
    kept or recomputed, and in the compute dtype of the cells."""
    assert dispatch_cap(ROWS, TOP, HELD[1] - HELD[0], WIDE) == 512
    rng = np.random.default_rng(len(case))
    dt = jnp.dtype(dtype)
    weights = tuple(w.astype(dt) for w in _ffn_weights(rng, WIDE))
    h = jnp.asarray(rng.normal(size=(ROWS, D)), dt)
    chosen, gates = _held_routing(rng, case)
    gates = gates.astype(dt)
    sparse = lambda h, gates, weights: _sparse(        # noqa: E731
        h, chosen, gates, weights, HELD, WIDE)
    if remat:
        sparse = jax.checkpoint(sparse)
    y, sizes = jax.jit(sparse)(h, gates, weights)
    np.testing.assert_array_equal(
        sizes, np.bincount(np.asarray(chosen).ravel(),
                           minlength=WIDE)[HELD[0]:HELD[1]])
    if HELD_PAIRS[case] is not None:
        assert int(sizes.sum()) == HELD_PAIRS[case]
    else:
        assert 0 < int(sizes.sum()) < 512

    def dense(h, gates, weights):
        return _dense(h.astype(jnp.float32), chosen,
                      gates.astype(jnp.float32),
                      tuple(w.astype(jnp.float32) for w in weights), HELD)

    # bfloat16: 2**-8 a rounding, a few of them between h and y
    rtol, atol = (2e-5, 2e-6) if dtype == "float32" else (4e-2, 4e-2)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               dense(h, gates, weights), rtol, atol)

    def loss(fn, *a):
        out = fn(*a)
        out = out[0] if isinstance(out, tuple) else out
        return jnp.sum(jnp.sin(out.astype(jnp.float32)))

    got = jax.jit(jax.grad(lambda *a: loss(sparse, *a), (0, 1, 2)))(
        h, gates, weights)
    want = jax.grad(lambda *a: loss(dense, *a), (0, 1, 2))(
        h, gates, weights)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        if dtype == "float32":
            np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)
        else:
            assert np.linalg.norm(a - b) <= 3e-2 * np.linalg.norm(b)
    if case == "no_held_pair":
        assert not np.any(np.asarray(y)) and not any(
            np.any(np.asarray(g)) for g in jax.tree_util.tree_leaves(got))


def test_the_cap_follows_from_the_shapes():
    # 4,096 rows a pass: Qwen3-Next top-10, 8 of 512 held; Laguna top-8,
    # 8 of 256; OLMoE top-8, 16 of 64: about twice the even count, in
    # tiles of 512
    assert dispatch_cap(4096, 10, 8, 512) == 1536
    assert dispatch_cap(4096, 8, 8, 256) == 2048
    assert dispatch_cap(4096, 8, 16, 64) == 16384
    # the router's width not given, or every expert held: all the pairs
    assert dispatch_cap(4096, 10, 8) == 40960
    assert dispatch_cap(4096, 8, 64, 64) == 32768
    # never over the pairs there are, never under a tile
    assert dispatch_cap(24, 2, 4, 8) == 48
    assert dispatch_cap(4096, 8, 1, 4096) == 512


def _largest_arrays(fn, *args):
    """(rows, elements) maxima over every array of two or more
    dimensions that ``fn``'s jaxpr makes, nested jaxprs included."""
    seen = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            for v in eqn.outvars:
                shape = getattr(v.aval, "shape", ())
                if len(shape) >= 2 and shape[-1] > 1:
                    seen.append((int(np.prod(shape[:-1])),
                                 int(np.prod(shape))))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return max(r for r, _ in seen), max(n for _, n in seen)


def test_the_compact_path_builds_no_array_of_all_the_pairs():
    """Forward, recomputed and backward: no array of ROWS * TOP rows by
    more than one column, and none larger than the rows by the wider of
    D and W, whatever the number of tiles (a loop that kept its tiles'
    terms for the way back would stack them)."""
    rng = np.random.default_rng(5)
    weights = _ffn_weights(rng, HELD[1] - HELD[0])
    h = jnp.asarray(rng.normal(size=(ROWS, D)), jnp.float32)
    chosen, gates = _held_routing(rng, "fewer_than_the_cap")

    def grads(num_experts, remat):
        def loss(h, gates, weights):
            return jnp.sum(jnp.sin(sparse_dispatch(
                h, chosen, gates, _gated(*weights), HELD, num_experts)[0]))
        return jax.grad(remat(loss), (0, 1, 2))

    for remat in (jax.checkpoint, lambda f: f):
        rows, size = _largest_arrays(grads(WIDE, remat), h, gates, weights)
        assert rows < ROWS * TOP and size <= ROWS * max(D, W), (rows, size)
    # the walk sees what it should: without the router's width the
    # dispatch gathers every pair
    rows, size = _largest_arrays(grads(None, jax.checkpoint),
                                 h, gates, weights)
    assert rows == ROWS * TOP and size == ROWS * TOP * D, (rows, size)


def test_a_held_range_computes_its_own_experts_only():
    rng = np.random.default_rng(11)
    weights = _ffn_weights(rng)
    h = jnp.asarray(rng.normal(size=(N, D)), jnp.float32)
    chosen, gates = _routing(rng, 2, "random")
    y, sizes = _sparse(h, chosen, gates, weights, local=(2, 6))
    np.testing.assert_allclose(
        y, _dense(h, chosen, gates, weights, local=(2, 6)),
        rtol=2e-5, atol=2e-6)
    np.testing.assert_array_equal(
        sizes, np.bincount(np.asarray(chosen).ravel(), minlength=E)[2:6])


def test_the_ranks_shares_sum_to_the_whole_layer():
    """Expert parallel 4: rank r holds experts 2r and 2r+1 of 8. Each
    rank's block output is the attention residual plus ITS experts'
    terms, so the four, less three residuals, are the full layer."""
    def block(local):
        return OlmoeBlock(dim=32, heads=4, num_experts=8,
                          experts_per_token=2, expert_width=16,
                          local_experts=local)

    x = jnp.asarray(np.random.default_rng(3).normal(size=(2, 16, 32)),
                    jnp.float32)
    full = block((0, 8)).init(jax.random.PRNGKey(0), x)
    whole, _p, _c, rows = block((0, 8)).apply(full, x)
    assert int(rows) == 2 * 16 * 2

    def share(lo, hi, zero_down=False):
        p = dict(full["params"])
        for n in ("w_gate", "w_up", "w_down"):
            p[n] = p[n][lo:hi]
        if zero_down:
            p["w_down"] = jnp.zeros_like(p["w_down"])
        return block((lo, hi)).apply({"params": p}, x)

    residual = share(0, 2, zero_down=True)[0]
    parts = [share(lo, lo + 2) for lo in (0, 2, 4, 6)]
    np.testing.assert_allclose(
        sum(p[0] for p in parts) - 3 * residual, whole,
        rtol=1e-5, atol=1e-6)
    assert sum(int(p[3]) for p in parts) == int(rows)


def test_a_block_on_the_kernel_is_the_dense_block(monkeypatch):
    """``OlmoeBlock`` with ``transformer.runs_kernel`` forced onto the
    Pallas kernel (its test-only argument; interpreted here) against the
    same block on the dense [T, T] product: the output and every
    parameter's gradient."""
    from functools import partial

    from geomx_tpu.models import transformer

    block = OlmoeBlock(dim=32, heads=4, num_experts=8, experts_per_token=2,
                       expert_width=16, local_experts=(0, 8))
    x = jnp.asarray(np.random.default_rng(3).normal(size=(2, 21, 32)),
                    jnp.float32)
    variables = block.init(jax.random.PRNGKey(0), x)

    def loss(variables):
        out = block.apply(variables, x)[0]
        return jnp.sum(jnp.sin(out)), out

    # (a fresh function a trace: a cached trace asks the rule nothing)
    assert "pallas_call" not in str(jax.make_jaxpr(
        lambda v: loss(v))(variables))
    (_l, want), grads_want = jax.value_and_grad(loss, has_aux=True)(
        variables)
    monkeypatch.setattr(transformer, "runs_kernel", partial(
        transformer.runs_kernel, forced=True))
    assert "pallas_call" in str(jax.make_jaxpr(
        lambda v: loss(v))(variables))
    (_l, got), grads_got = jax.value_and_grad(loss, has_aux=True)(variables)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(grads_got),
                    jax.tree_util.tree_leaves(grads_want)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)


# -- one round through the system ---------------------------------------------

@pytest.mark.time_limit(300)
def test_two_party_round_through_the_device_trainer():
    names, grad_step = bench_model.build(TINY, SEQ)
    params = reference.init_params(TINY, 5)
    leaves = [np.array(x) for x in bench_model.leaves_from(params, names)]
    was_on = telemetry.enabled()
    telemetry.enable(True)
    before = telemetry.snapshot()["counters"]
    topo = InProcessHiPS(num_parties=2, workers_per_party=1).start()
    out = {}
    try:
        def master_init(kv):
            for i, leaf in enumerate(leaves):
                kv.init(i, leaf)
            kv.wait()

        def worker(kv):
            w = topo.workers.index(kv)
            tr = DeviceResidentTrainer(
                list(leaves), kv, grad_step, threshold=0.05,
                learning_rate=0.05, momentum=0.9)
            losses = [tr.step(_tokens(100 * w + r, batch=4), None)
                      for r in range(2)]
            out[w] = (losses, np.asarray(tr._flat))

        topo.run_workers(worker, include_master=master_init, timeout=240)
    finally:
        topo.stop()
        telemetry.enable(was_on)
    after = telemetry.snapshot()["counters"]
    assert np.isfinite(out[0][0] + out[1][0]).all()
    np.testing.assert_array_equal(out[0][1].view(np.uint32),
                                  out[1][1].view(np.uint32))
    assert not np.array_equal(out[0][1],
                              np.concatenate([l.ravel() for l in leaves]))
    # 2 workers x 2 rounds x 4 sequences x 32 tokens x 2 layers x top-2
    total = after["moe.rows_total"] - before.get("moe.rows_total", 0)
    local = after["moe.rows_local"] - before.get("moe.rows_local", 0)
    assert total == 2 * 2 * 4 * SEQ * 2 * 2
    assert 0 < local < total
