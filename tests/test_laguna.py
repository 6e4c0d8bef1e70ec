"""Laguna through the program: the model against the benchmark's plain
float32 reference, the blocked window attention against the masked
``[T, T]`` product, grouped queries against repeated key/value heads,
YaRN's frequencies against hand-computed values, the dispatch at its
extremes, the ranks' shares against the uncut layer, and one two-party
HiPS round through the device-resident trainer.

Tiny widths, seeded weights, CPU. The published widths are compared on
the chip (``benchmark/tests/chip_limits.py``, PERF.md section 2).
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.models import laguna as bench_model
from benchmark.references import laguna as reference
from geomx_tpu import telemetry
from geomx_tpu.models.laguna import LagunaBlock, rotary_frequencies
from geomx_tpu.models.moe import sparse_dispatch
from geomx_tpu.models.transformer import (dense_attention,
                                          grouped_attention, score_entries,
                                          window_attention)
from geomx_tpu.simulate import InProcessHiPS
from geomx_tpu.trainer_device import DeviceResidentTrainer

# the published rotary settings (poolside/Laguna-XS.2 config.json)
ROPE = {
    "full_attention": {
        "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
        "original_max_position_embeddings": 4096, "beta_slow": 1,
        "beta_fast": 64, "attention_factor": 1.4158883083359672,
        "partial_rotary_factor": 0.5},
    "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                          "partial_rotary_factor": 1}}
# a rank in the middle of a layout: key/value heads 1..2 of 4, their
# query groups (3 a head in full layers, 4 in sliding ones), experts
# 4..7 of 16
TINY = dict(
    family="laguna", compute_dtype="float32", hidden_size=64, head_dim=16,
    intermediate_size=96, moe_intermediate_size=32,
    shared_expert_intermediate_size=32, num_experts=16,
    num_experts_per_tok=2, moe_routed_scaling_factor=2.5, vocab_size=128,
    rms_norm_eps=1e-6, sliding_window=8, rope_parameters=ROPE,
    num_hidden_layers=5,
    layer_types=["full_attention"] + ["sliding_attention"] * 3
    + ["full_attention"],
    mlp_layer_types=["dense"] + ["sparse"] * 4,
    query_heads=[[3, 9], [4, 12], [4, 12], [4, 12], [3, 9]],
    key_value_heads=[1, 3], local_experts=[4, 8], microbatch_sequences=1)
SEQ = 37        # not a multiple of the window
PARAM_SEED, TOKEN_SEED = 2147483700, 3


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


# bfloat16 keeps 8 bits of significand; a leaf's gradient passes a few
# matmuls with rounded operands. Measured here (the seeds above): the
# program in bfloat16 reads 0.0115 at most a leaf, the same mathematics
# with float8_e4m3 operands 0.0517 at least; 0.03 sits between, so
# computing in the next precision down fails on every leaf. Top-k
# routing is discrete: a near-tie of the k-th and (k+1)-th router score
# flips a token's expert on a rounding upstream and that layer's leaves
# jump to 0.05-0.36 (four of six seed pairs tried, 74 tokens through
# four routers each); the seeds were chosen clear of that. The chip's
# comparison has 8,192 tokens to average over.
LEAF_TOL = 0.03


@pytest.mark.parametrize("dtype,loss_tol,leaf_tol", [
    ("float32", 1e-5, 1e-5), ("bfloat16", 1e-3, LEAF_TOL)])
def test_model_matches_the_float32_reference(dtype, loss_tol, leaf_tol):
    loss_err, errs = _leaf_errors(dict(TINY, compute_dtype=dtype))
    assert len(errs) == 69
    assert loss_err <= loss_tol
    over = {n: e for n, e in errs.items() if e > leaf_tol}
    assert not over, over


def test_float8_operands_fail_the_bfloat16_tolerance():
    _loss_err, errs = _leaf_errors(TINY, "float8_e4m3fn", system=False)
    under = {n: e for n, e in errs.items() if e <= LEAF_TOL}
    assert not under, under


# -- attention ----------------------------------------------------------------

def _qkv(t, kv=2, group=3, d=8, seed=0):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.normal(size=(2, t, kv, group, d)), jnp.float32),
            jnp.asarray(rng.normal(size=(2, t, kv, d)), jnp.float32),
            jnp.asarray(rng.normal(size=(2, t, kv, d)), jnp.float32))


def _masked_product(q, k, v, window=None):
    """The [T, T] product over every query head, the mask written out."""
    t = q.shape[1]
    behind = np.arange(t)[:, None] - np.arange(t)[None]
    mask = behind >= 0
    if window is not None:
        mask &= behind < window
    s = jnp.einsum("bqkgd,bjkd->bkgqj", q, k) / math.sqrt(q.shape[-1])
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    return jnp.einsum("bkgqj,bjkd->bqkgd", p, v), int(mask.sum())


@pytest.mark.parametrize("t,window", [
    (5, 8), (8, 8), (16, 8), (21, 8), (7, 1), (9, 3)],
    ids=["under", "equal", "two_blocks", "not_a_multiple", "window_1",
         "odd"])
def test_blocked_window_attention_is_the_masked_product(t, window):
    q, k, v = _qkv(t, seed=t)
    want, live = _masked_product(q, k, v, window)
    got = jax.jit(lambda *a: window_attention(*a, window))(q, k, v)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)

    def loss(fn, *a):
        return jnp.sum(jnp.sin(fn(*a)))

    for a, b in zip(
            jax.grad(lambda *a: loss(
                lambda *x: window_attention(*x, window), *a), (0, 1, 2))(
                    q, k, v),
            jax.grad(lambda *a: loss(
                lambda *x: _masked_product(*x, window)[0], *a), (0, 1, 2))(
                    q, k, v)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)
    entries = score_entries(t, window)
    assert entries[0] == live
    # blocked by the window: two blocks of columns a query, whatever T
    block = min(window, t)
    assert entries[1] == -(-t // block) * block * 2 * block


def test_grouped_queries_are_repeated_key_value_heads():
    q, k, v = _qkv(12, kv=2, group=3, seed=3)
    b, t, kv, g, d = q.shape
    want = dense_attention(q.reshape(b, t, kv * g, d),
                           jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2))
    got = grouped_attention(q, k, v)
    np.testing.assert_allclose(got.reshape(b, t, kv * g, d), want,
                               rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(got, _masked_product(q, k, v)[0],
                               rtol=2e-5, atol=2e-6)
    assert score_entries(12) == (78, 144)


def test_yarn_frequencies_at_the_published_settings():
    inv, factor = rotary_frequencies(ROPE["full_attention"], 128)
    # 64 of 128 dims turn: 32 pairs, f_i = 500000^(-i/32). The pair that
    # turns r times over 4,096 positions is 64 ln(4096 / (2 pi r)) /
    # (2 ln 500000): 5.66 for r = 64, 15.80 for r = 1, so pairs 0..5
    # keep their frequency, pairs 16..31 have it divided by 64, and
    # pair i between is f_i * (1 - (i - 5)/11 * 63/64)
    assert inv.shape == (32,) and inv.dtype == np.float32
    f = [500000.0 ** (-i / 32) for i in range(32)]
    np.testing.assert_allclose(inv[:6], f[:6], rtol=1e-6)
    np.testing.assert_allclose(inv[16:], np.array(f[16:]) / 64, rtol=1e-6)
    np.testing.assert_allclose(inv[10], f[10] * (1 - 5 / 11 * 63 / 64),
                               rtol=1e-6)
    # 500000^(-10/32) = 0.016560, times 0.552557
    assert inv[10] == pytest.approx(0.0091506, rel=1e-4)
    assert inv[31] == pytest.approx(1 / (64 * 500000 ** (31 / 32)),
                                    rel=1e-6)
    # 0.1 ln 64 + 1, as the config states it
    assert factor == 1.4158883083359672
    assert factor == pytest.approx(0.1 * math.log(64) + 1, rel=1e-12)
    plain, one = rotary_frequencies(ROPE["sliding_attention"], 128)
    np.testing.assert_allclose(
        plain, [10000.0 ** (-i / 64) for i in range(64)], rtol=1e-6)
    assert one == 1.0
    # the reference computes its own table from the formula
    cos, sin = reference.rotary_table(ROPE["full_attention"], 128, 3)
    np.testing.assert_allclose(cos[1, :32], factor * np.cos(inv), rtol=1e-5)
    np.testing.assert_allclose(sin[2, 32:], factor * np.sin(2 * inv),
                               rtol=1e-5)


# -- the dispatch at its extremes ---------------------------------------------

E, D, W, N, LOCAL = 16, 16, 12, 24, (4, 8)


def _ffn_weights(rng):
    return tuple(jnp.asarray(rng.normal(0, 0.3, s), jnp.float32)
                 for s in ((E, D, W), (E, D, W), (E, W, D)))


def _sparse(h, chosen, weights, stacks):
    w_gate, w_up, w_down = (w[LOCAL[0]:LOCAL[1]] for w in stacks)

    def experts(rows, group_sizes, _row_expert):
        a = jax.nn.silu(jax.lax.ragged_dot(rows, w_gate, group_sizes)) \
            * jax.lax.ragged_dot(rows, w_up, group_sizes)
        return jax.lax.ragged_dot(a, w_down, group_sizes)

    return sparse_dispatch(h, chosen, weights, experts, LOCAL)


def _every_expert(h, chosen, weights, stacks):
    """Every expert computes every row; the router's mask picks."""
    w_gate, w_up, w_down = stacks
    act = jax.nn.silu(jnp.einsum("nd,edw->enw", h, w_gate)) \
        * jnp.einsum("nd,edw->enw", h, w_up)
    out = jnp.einsum("enw,ewd->end", act, w_down)
    mask = jnp.einsum("nk,nke->en", weights,
                      jax.nn.one_hot(chosen, E, dtype=h.dtype))
    held = (jnp.arange(E) >= LOCAL[0]) & (jnp.arange(E) < LOCAL[1])
    return jnp.einsum("en,end->nd", mask * held[:, None], out)


@pytest.mark.parametrize("case,rows_here", [
    ("every_token_to_one_held_expert", N), ("no_token_to_a_held_expert", 0)])
def test_dispatch_at_its_extremes_drops_nothing(case, rows_here):
    rng = np.random.default_rng(5)
    stacks = _ffn_weights(rng)
    h = jnp.asarray(rng.normal(size=(N, D)), jnp.float32)
    elsewhere = np.array([e for e in range(E)
                          if not LOCAL[0] <= e < LOCAL[1]])
    chosen = np.stack([rng.permutation(elsewhere)[:2] for _ in range(N)])
    if rows_here:
        chosen[:, 0] = 6        # held, and the other slot is not
    scores = rng.uniform(0.05, 1.0, (N, 2))
    # the weights Laguna's router hands over: normalised, times 2.5
    weights = jnp.asarray(2.5 * scores / scores.sum(-1, keepdims=True),
                          jnp.float32)
    chosen = jnp.asarray(chosen, jnp.int32)
    y, sizes = jax.jit(_sparse)(h, chosen, weights, stacks)
    np.testing.assert_allclose(y, _every_expert(h, chosen, weights, stacks),
                               rtol=2e-5, atol=2e-6)
    assert int(sizes.sum()) == rows_here
    np.testing.assert_array_equal(
        sizes, np.bincount(np.asarray(chosen).ravel(),
                           minlength=E)[LOCAL[0]:LOCAL[1]])
    if not rows_here:
        assert not np.asarray(y).any()

    def loss(fn, h, weights, stacks):
        out = fn(h, chosen, weights, stacks)
        return jnp.sum(jnp.sin(out[0] if isinstance(out, tuple) else out))

    got = jax.jit(jax.grad(lambda *a: loss(_sparse, *a), (0, 1, 2)))(
        h, weights, stacks)
    want = jax.grad(lambda *a: loss(_every_expert, *a), (0, 1, 2))(
        h, weights, stacks)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert np.isfinite(a).all()
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)


# -- the shares add up --------------------------------------------------------

# one layer uncut: 8 key/value heads with 2 query heads each, 8 experts
WHOLE = dict(TINY, hidden_size=32, head_dim=8, num_experts=8,
             num_hidden_layers=1, key_value_heads=[0, 8],
             query_heads=[[0, 16]], local_experts=[0, 8])


def _block(kind, sparse, q_heads, kv_heads, local):
    return LagunaBlock(
        dim=32, head_dim=8, kind=kind, query_heads=q_heads,
        key_value_heads=kv_heads, window=8, rope=ROPE[kind], sparse=sparse,
        dense_width=96, num_experts=8, experts_per_token=2, expert_width=32,
        shared_width=32, local_experts=local, routed_scale=2.5)


def _layer_params(cfg, seed=3):
    params = reference.init_params(cfg, seed)
    return {n[len("block0/"):]: p for n, p in params.items()
            if n.startswith("block0/")}


def _tree(flat):
    tree = {}
    for name, value in flat.items():
        *path, leaf = name.split("/")
        node = tree
        for part in path:
            node = node.setdefault(part, {})
        node[leaf] = value
    return {"params": tree}


def _reference_layer(cfg, flat, x):
    return jnp.stack([
        reference.layer({"block0/" + n: p for n, p in flat.items()},
                        "block0/", seq, 0, cfg) for seq in x])


@pytest.mark.parametrize("kind", ["full_attention", "sliding_attention"])
def test_eight_head_shares_sum_to_the_attention_branch(kind):
    """Tensor parallel 8: rank r holds key/value head r and its two
    query heads. With the FFN's down projection zero a dense block
    returns h' = x + the rank's part of (o * g) Wo; the eight parts are
    the uncut reference's branch."""
    cfg = dict(WHOLE, layer_types=[kind], mlp_layer_types=["dense"])
    flat = _layer_params(cfg)
    flat["ffn_down/kernel"] = jnp.zeros_like(flat["ffn_down/kernel"])
    x = jnp.asarray(np.random.default_rng(4).normal(size=(2, 21, 32)),
                    jnp.float32)
    whole = _reference_layer(cfg, flat, x)

    def share(r):
        mine = dict(flat)
        for n in ("q", "gate"):
            mine[n + "/kernel"] = flat[n + "/kernel"][:, 16 * r:16 * r + 16]
        for n in ("k", "v"):
            mine[n + "/kernel"] = flat[n + "/kernel"][:, 8 * r:8 * r + 8]
        mine["o/kernel"] = flat["o/kernel"][16 * r:16 * r + 16]
        return _block(kind, False, (2 * r, 2 * r + 2), (r, r + 1),
                      (0, 8)).apply(_tree(mine), x)[0]

    parts = sum(share(r) - x for r in range(8))
    assert float(jnp.abs(parts).max()) > 1e-3
    np.testing.assert_allclose(x + parts, whole, rtol=1e-5, atol=1e-6)
    with pytest.raises(ValueError, match="not the groups"):
        _block(kind, False, (0, 2), (1, 2), (0, 8)).apply(
            _tree(flat), x)


def _on_the_kernel(monkeypatch):
    """Force ``transformer.runs_kernel`` to the kernel (its test-only
    argument): here the kernels run interpreted."""
    from functools import partial

    from geomx_tpu.models import transformer

    monkeypatch.setattr(transformer, "runs_kernel", partial(
        transformer.runs_kernel, forced=True))


def test_a_full_block_on_the_kernel_is_the_dense_block(monkeypatch):
    """The full layer with its core as the Pallas kernel against the
    same block on the dense [T, T] product: the block's output and every
    parameter's gradient."""
    flat = _layer_params(dict(WHOLE, layer_types=["full_attention"],
                              mlp_layer_types=["dense"]))
    x = jnp.asarray(np.random.default_rng(4).normal(size=(2, 21, 32)),
                    jnp.float32)
    block = _block("full_attention", False, (0, 16), (0, 8), (0, 8))

    def loss(variables):
        out = block.apply(variables, x)[0]
        return jnp.sum(jnp.sin(out)), out

    (_l, want), grads_want = jax.value_and_grad(loss, has_aux=True)(
        _tree(flat))
    _on_the_kernel(monkeypatch)
    (_l, got), grads_got = jax.value_and_grad(loss, has_aux=True)(
        _tree(flat))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(grads_got),
                    jax.tree_util.tree_leaves(grads_want)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)
    # the count follows the program: a full layer's head computes one
    # block of 40 x 40 where the dense product has 37 x 37; the sliding
    # layers and the live entries are what they were
    model = bench_model.model_of(TINY)
    (_r, live, dense), (_r, live_k, on_kernel) = (
        model.counts(1, SEQ), model.counts(1, SEQ, True))
    assert live_k == live
    assert on_kernel - dense == 2 * 6 * (40 * 40 - SEQ * SEQ)
    # from the rule's floor on the window the sliding layers count their
    # band's live tiles: at 1,100 positions and a window of 512 a head's
    # 1 + 2 + 2 tiles of 512 x 512 for 3 blocks of 512 x 1,024, beside
    # a full head's 1 + 1 + 2 tiles of 512 x 1,024 for 1100 x 1100
    wide = bench_model.model_of(dict(TINY, sliding_window=512))
    (_r, live, dense), (_r, live_k, on_kernel) = (
        wide.counts(1, 1100), wide.counts(1, 1100, True))
    assert live_k == live == 2 * 6 * 605_550 + 3 * 8 * 432_384
    assert dense == 2 * 6 * 1100 * 1100 + 3 * 8 * 3 * 512 * 1024
    assert on_kernel == 2 * 6 * 4 * 512 * 1024 + 3 * 8 * 5 * 512 * 512


@pytest.mark.parametrize("core", ["window", "dense", "kernel",
                                  "window_kernel"])
def test_the_checkpoint_is_where_something_quadratic_is_kept(core,
                                                             monkeypatch):
    """``gated_attention`` computes a dense core again on the way back
    (window and full alike) and keeps the kernels' own residuals (window
    and full alike): read off the jaxpr."""
    from geomx_tpu.models.transformer import gated_attention

    if core.endswith("kernel"):
        _on_the_kernel(monkeypatch)
    q = jnp.zeros((1, 24, 1, 2, 8), jnp.float32)
    k = v = jnp.zeros((1, 24, 1, 8), jnp.float32)
    gate = jnp.zeros((1, 24, 16), jnp.float32)
    inv_freq, factor = rotary_frequencies(ROPE["sliding_attention"], 8)
    text = str(jax.make_jaxpr(lambda q, k, v: gated_attention(
        q, k, v, gate, inv_freq, factor,
        window=8 if core.startswith("window") else None))(q, k, v))
    assert ("remat" in text) == (not core.endswith("kernel"))
    assert ("pallas_call" in text) == core.endswith("kernel")


def test_four_expert_shares_and_one_shared_expert_sum_to_the_layer():
    """Expert parallel 4: rank r holds experts 2r and 2r+1 of 8, every
    rank the shared expert. A rank's block output is h' + shared(m) +
    ITS experts' terms, so the four, less three times what all compute
    alike, are the uncut reference's layer."""
    cfg = dict(WHOLE, layer_types=["sliding_attention"],
               mlp_layer_types=["sparse"])
    flat = _layer_params(cfg)
    x = jnp.asarray(np.random.default_rng(6).normal(size=(2, 21, 32)),
                    jnp.float32)
    whole = _reference_layer(cfg, flat, x)

    def share(lo, hi, zero_down=False):
        mine = dict(flat)
        for n in ("w_gate", "w_up", "w_down"):
            mine[n] = flat[n][lo:hi]
        if zero_down:
            mine["w_down"] = jnp.zeros_like(mine["w_down"])
        return _block("sliding_attention", True, (0, 16), (0, 8),
                      (lo, hi)).apply(_tree(mine), x)

    alike = share(0, 2, zero_down=True)[0]
    parts = [share(lo, lo + 2) for lo in (0, 2, 4, 6)]
    np.testing.assert_allclose(sum(p[0] for p in parts) - 3 * alike, whole,
                               rtol=1e-5, atol=1e-6)
    # every routed row is some rank's
    assert sum(int(p[1]) for p in parts) == 2 * 21 * 2
    assert float(jnp.abs(whole - alike).max()) > 1e-3


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
            tr.warmup(_tokens(7, batch=4), None)
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

    def booked(name):
        return after[name] - before.get(name, 0)

    # 2 workers x 2 rounds x 4 sequences
    sequences = 2 * 2 * 4
    # x 37 tokens x 4 expert layers x top-2
    assert booked("moe.rows_total") == sequences * SEQ * 4 * 2
    assert 0 < booked("moe.rows_local") < booked("moe.rows_total")
    # held query heads: 6 in the two full layers, 8 in the three sliding
    # ones; a sliding head keeps 36 + 29 * 8 entries of 5 blocks x 8 x 16
    live = 2 * 6 * (SEQ * (SEQ + 1) // 2) + 3 * 8 * (36 + 29 * 8)
    computed = 2 * 6 * SEQ * SEQ + 3 * 8 * (5 * 8 * 16)
    assert booked("attn.score_entries_live") == sequences * live
    assert booked("attn.score_entries_computed") == sequences * computed
    assert reference.live_score_entries(TINY, SEQ) == live


# -- the program itself --------------------------------------------------------

# sha256 of the StableHLO of the benchmark's grad_step at TINY, bfloat16,
# two sequences, as the tree of PR 33 lowers it: ``sparse_dispatch``
# gathers the held (token, slot) pairs up to a cap before any row moves
# (PR 33 meant to alter Laguna's program; PRs 31 and 32 left it byte for
# byte what it was). A change that means to alter it records the new
# value. PR 39 (the full layers' core behind ``causal_attention``: the
# dense product here, where Pallas is interpreted) left every operation
# what it was and moved one private function's number (``_take_507`` ->
# ``_take_506``): recorded anew. PR 57 meant to alter it: the routed
# experts lost the ``jax.checkpoint`` around ``sparse_dispatch`` (it
# ran the index work, the gather, the grouped matmuls and the
# scatter-add once more before the way back, which ``_sum_of_tiles``
# recomputes a tile by itself), so a sparse layer's second forward is
# out of the text: recorded anew, and the test below counts the groups.
FUSED_STEP_STABLEHLO = \
    "8faaf26aa5bf030f1a5d35e813d9332eae6eba70b46c3906fb81d22eab6c31de"
# the same of the GPT-2 family's grad_step at gpt2-small's rehearsal
# widths, 37 tokens, taken on the tree of PR 32: a family without
# experts compiles what it compiled before the dispatch changed
GPT2_STEP_STABLEHLO = \
    "657e9adddff64d1f1838639620ac1eabfac06877c59c6c5cea4243988416a4f2"


def _stablehlo_sha256(model, family_reference, cfg):
    import hashlib

    names, grad_step = model.build(cfg, SEQ)
    shapes = family_reference.param_shapes(cfg)
    text = jax.jit(grad_step).lower(
        [jax.ShapeDtypeStruct(shapes[n], jnp.float32) for n in names],
        jax.ShapeDtypeStruct((2, SEQ + 1), jnp.int32), None).as_text()
    return hashlib.sha256(text.encode()).hexdigest()


def test_grad_step_lowers_to_the_recorded_program():
    assert _stablehlo_sha256(
        bench_model, reference,
        dict(TINY, compute_dtype="bfloat16")) == FUSED_STEP_STABLEHLO


@pytest.mark.parametrize("seq,in_loops", [(SEQ, False), (300, True)],
                         ids=["one_tile_by_shape", "loop_over_tiles"])
def test_grad_step_holds_one_forward_group_of_grouped_matmuls_a_layer(
        seq, in_loops):
    """A sparse layer's grouped matmuls (gate, up, down: one group) in
    the whole of ``grad_step``: one group forward and its six
    transposed products where the cap is all the pairs; where it is
    not, the forward loop over the tiles holds the group and the loop
    of the way back holds it again beside the six
    (``moe._sum_of_tiles``; 600 pairs a pass, 512 a tile). No third
    group: until PR 57 a checkpoint around the dispatch ran the forward
    once more."""
    names, grad_step = bench_model.build(TINY, seq)
    shapes = reference.param_shapes(TINY)
    jaxpr = jax.make_jaxpr(lambda p, x: grad_step(p, x, None))(
        [jax.ShapeDtypeStruct(shapes[n], jnp.float32) for n in names],
        jax.ShapeDtypeStruct((2, seq + 1), jnp.int32))

    def equations(jaxpr):
        """Every equation, those of inner jaxprs included."""
        for eqn in jaxpr.eqns:
            yield eqn
            for value in eqn.params.values():
                for inner in value if isinstance(value, (list, tuple)) \
                        else [value]:
                    inner = getattr(inner, "jaxpr", inner)
                    if hasattr(inner, "eqns"):
                        yield from equations(inner)

    def grouped(eqns):
        return sum(e.primitive.name.startswith("ragged_dot") for e in eqns)

    sparse = sum(kind == "sparse" for kind in TINY["mlp_layer_types"])
    eqns = list(equations(jaxpr.jaxpr))
    loops = sorted(filter(None, (
        grouped(equations(e.params["body_jaxpr"].jaxpr)) for e in eqns
        if e.primitive.name == "while")))
    assert loops == ([3] * sparse + [9] * sparse if in_loops else [])
    assert grouped(eqns) == (12 if in_loops else 9) * sparse


def test_gpt2_grad_step_lowers_to_the_parents_program():
    from benchmark import manifest
    from benchmark.models import transformer
    from benchmark.references import transformer as transformer_reference

    cfg = manifest.load_cell("gpt2s-hips-bsc")["config"]
    assert _stablehlo_sha256(
        transformer, transformer_reference,
        dict(cfg, **cfg["rehearsal"])) == GPT2_STEP_STABLEHLO
