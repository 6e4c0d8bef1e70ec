"""Mellum 2 through the program: the model against the benchmark's plain
float32 reference, the ranks' shares against the uncut layer, YaRN over
the whole head and the layer order by hand, the softmax router against a
written-out loop, what the model computes again on the way back, the
ungated attention branch against ``gated_attention`` at an open gate,
and one two-party HiPS round through the device-resident trainer.

Tiny widths, seeded weights, CPU. The published widths are compared on
the chip (``benchmark/tests/chip_limits.py``, PERF.md section 2).
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.models import mellum as bench_model
from benchmark.references import mellum as reference
from geomx_tpu import telemetry
from geomx_tpu.models.mellum import MellumBlock
from geomx_tpu.models.transformer import (gated_attention,
                                          rotary_attention,
                                          rotary_frequencies, score_entries)
from geomx_tpu.simulate import InProcessHiPS
from geomx_tpu.trainer_device import DeviceResidentTrainer

# the published rotary settings (JetBrains/Mellum2-12B-A2.5B-Instruct
# config.json): neither block names a partial_rotary_factor
ROPE = {
    "full_attention": {
        "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
        "original_max_position_embeddings": 8192, "beta_fast": 32,
        "beta_slow": 1, "attention_factor": 1.2772588722239782},
    "sliding_attention": {"rope_type": "default", "rope_theta": 500000}}
SLIDING, FULL = "sliding_attention", "full_attention"
# a rank in the middle of a layout: key/value heads 1..2 of 4 with their
# query groups of 4, experts 4..7 of 16
TINY = dict(
    family="mellum", compute_dtype="float32", hidden_size=64, head_dim=16,
    moe_intermediate_size=32, num_experts=16, num_experts_per_tok=2,
    vocab_size=128, rms_norm_eps=1e-6, sliding_window=8,
    rope_parameters=ROPE, num_hidden_layers=4,
    layer_types=[SLIDING] * 3 + [FULL], query_heads=[4, 12],
    key_value_heads=[1, 3], local_experts=[4, 8], microbatch_sequences=1)
SEQ = 37        # not a multiple of the window
PARAM_SEED, TOKEN_SEED = 2147483700, 7


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
# program in bfloat16 reads 0.0120 at most a leaf, the same mathematics
# with float8_e4m3 operands 0.0659 at least; 0.03 sits between, so
# computing in the next precision down fails on every leaf. Top-k
# routing is discrete: a near-tie of the k-th and (k+1)-th router
# probability flips a token's expert on a rounding upstream and that
# layer's leaves jump to 0.16-0.39 (seven of eight token seeds tried,
# 74 tokens through four softmax routers of 16 each); the seeds were
# chosen clear of that. The chip's comparison has 16,384 tokens to
# average over.
LEAF_TOL = 0.03


@pytest.mark.parametrize("dtype,loss_tol,leaf_tol", [
    ("float32", 1e-5, 1e-5), ("bfloat16", 1e-3, LEAF_TOL)])
def test_model_matches_the_float32_reference(dtype, loss_tol, leaf_tol):
    loss_err, errs = _leaf_errors(dict(TINY, compute_dtype=dtype))
    assert len(errs) == 43
    assert loss_err <= loss_tol
    over = {n: e for n, e in errs.items() if e > leaf_tol}
    assert not over, over


def test_float8_operands_fail_the_bfloat16_tolerance():
    _loss_err, errs = _leaf_errors(TINY, "float8_e4m3fn", system=False)
    under = {n: e for n, e in errs.items() if e <= LEAF_TOL}
    assert not under, under


def test_the_reference_in_query_blocks_is_the_whole_product(monkeypatch):
    """``reference.attention`` a block of queries at a time (three
    blocks of 16 over 37 positions, the last padded) against the [T, T]
    product with its mask written out, window and full."""
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.normal(size=(SEQ, 4, 8)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(SEQ, 2, 8)), jnp.float32)
            for _ in range(2))
    behind = np.arange(SEQ)[:, None] - np.arange(SEQ)[None]
    monkeypatch.setattr(reference, "QUERY_BLOCK", 16)
    for window in (None, 8):
        mask = (behind >= 0) & (behind < (window or SEQ))
        s = jnp.einsum("qhd,khd->hqk", q, jnp.repeat(k, 2, 1)) / math.sqrt(8)
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1)
        want = jnp.einsum("hqk,khd->qhd", p, jnp.repeat(v, 2, 1))
        np.testing.assert_allclose(
            reference.attention(q, k, v, window), want.reshape(SEQ, 32),
            rtol=2e-5, atol=2e-6)


# -- positions and order by hand ----------------------------------------------

def test_yarn_over_the_whole_head_at_the_published_settings():
    inv, factor = rotary_frequencies(ROPE[FULL], 128)
    # all 128 dims turn: 64 pairs, f_i = 500000^(-i/64). The pair that
    # turns r times over 8,192 positions is 128 ln(8192 / (2 pi r)) /
    # (2 ln 500000): 18.08 for r = 32, 34.98 for r = 1, so pairs 0..18
    # keep their frequency, pairs 35..63 have it divided by 16, and pair
    # i between is f_i * (1 - (i - 18)/17 * 15/16)
    assert inv.shape == (64,) and inv.dtype == np.float32
    c = [128 * math.log(8192 / (2 * math.pi * r)) / (2 * math.log(500000))
         for r in (32, 1)]
    assert (math.floor(c[0]), math.ceil(c[1])) == (18, 35)
    f = [500000.0 ** (-i / 64) for i in range(64)]
    np.testing.assert_allclose(inv[:19], f[:19], rtol=1e-6)
    np.testing.assert_allclose(inv[35:], np.array(f[35:]) / 16, rtol=1e-6)
    np.testing.assert_allclose(inv[26], f[26] * (1 - 8 / 17 * 15 / 16),
                               rtol=1e-6)
    # 0.1 ln 16 + 1, as the config states it
    assert factor == 1.2772588722239782
    assert factor == pytest.approx(0.1 * math.log(16) + 1, rel=1e-12)
    plain, one = rotary_frequencies(ROPE[SLIDING], 128)
    np.testing.assert_allclose(plain, f, rtol=1e-6)
    assert one == 1.0
    # the reference computes its own table from the formula
    cos, sin = reference.rotary_table(ROPE[FULL], 128, 3)
    assert cos.shape == (3, 128)
    np.testing.assert_allclose(cos[1, :64], factor * np.cos(inv), rtol=1e-5)
    np.testing.assert_allclose(sin[2, 64:], factor * np.sin(2 * inv),
                               rtol=1e-5)


def test_three_sliding_layers_then_the_full_one():
    """The order of the kinds, read off the program: blocks 0..2 compute
    the window's blocked scores (five blocks of 8 x 16 at 37 positions),
    block 3 the [T, T] product; and what ``counts`` says by shape."""
    model = bench_model.model_of(TINY)
    assert [model.layer_types[i] for i in range(4)] == [SLIDING] * 3 + [FULL]
    rows, live, computed = model.counts(1, SEQ)
    assert rows == SEQ * 4 * 2
    # 8 held query heads a layer; a sliding head keeps 36 + 29 * 8
    # entries of 5 blocks x 8 x 16, a full head 37 * 38 / 2 of 37 * 37
    assert score_entries(SEQ, 8) == (268, 640)
    assert score_entries(SEQ) == (703, 1369)
    assert live == 8 * (3 * 268 + 703) == reference.live_score_entries(
        TINY, SEQ)
    assert computed == 8 * (3 * 640 + 1369)
    x = jnp.zeros((1, SEQ, 64), jnp.float32)
    for kind, shape in ((SLIDING, "5,2,4,8,16"), (FULL, "2,4,37,37")):
        block = _block(kind, (4, 12), (1, 3), (4, 8), dim=64, head_dim=16,
                       num_experts=16)
        text = str(jax.make_jaxpr(lambda v, x: block.apply(v, x))(
            jax.eval_shape(block.init, jax.random.PRNGKey(0), x), x))
        assert f"f32[1,{shape}]" in text, kind


def test_softmax_router_normalises_what_it_chose():
    """The router's weights against a loop written out: softmax over all
    experts, the k largest, divided by their sum; in the block they
    weigh the held experts' outputs."""
    rng = np.random.default_rng(8)
    m = rng.normal(size=(11, 32)).astype(np.float32)
    kernel = rng.normal(0, 0.5, (32, 8)).astype(np.float32)
    chosen, weights = reference.router_weights(
        jnp.asarray(m), jnp.asarray(kernel), 3)
    for t in range(11):
        logits = m[t].astype(np.float64) @ kernel.astype(np.float64)
        p = np.exp(logits - logits.max())
        p /= p.sum()
        top = sorted(range(8), key=lambda e: -p[e])[:3]
        assert list(np.asarray(chosen[t])) == top
        np.testing.assert_allclose(
            weights[t], [p[e] / sum(p[e] for e in top) for e in top],
            rtol=1e-5)
    np.testing.assert_allclose(weights.sum(-1), 1.0, rtol=1e-6)


# -- the shares add up --------------------------------------------------------

# one layer uncut: 4 key/value heads with 2 query heads each, 8 experts
WHOLE = dict(TINY, hidden_size=32, head_dim=8, num_experts=8,
             num_hidden_layers=1, key_value_heads=[0, 4],
             query_heads=[0, 8], local_experts=[0, 8])


def _block(kind, q_heads, kv_heads, local, dim=32, head_dim=8,
           num_experts=8):
    return MellumBlock(
        dim=dim, head_dim=head_dim, kind=kind, query_heads=q_heads,
        key_value_heads=kv_heads, window=8, rope=ROPE[kind],
        num_experts=num_experts, experts_per_token=2, expert_width=32,
        local_experts=local)


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


@pytest.mark.parametrize("kind", [SLIDING, FULL])
def test_four_head_shares_by_eight_expert_shares_sum_to_the_layer(kind):
    """Tensor parallel 4 x expert parallel 8: head rank r holds
    key/value head r with its two query heads, expert rank e holds
    expert e. With the experts' down projection zero a block returns
    h' = x + the rank's part of o Wo, and the four parts are the uncut
    branch; on that h' the eight expert ranks' terms are the uncut
    layer's routed sum. A head rank's h' is only its part, so the expert
    shares are taken on the whole h' (what the all-reduce would hand
    them), through the same block with every head held."""
    cfg = dict(WHOLE, layer_types=[kind])
    flat = _layer_params(cfg)
    x = jnp.asarray(np.random.default_rng(4).normal(size=(2, 21, 32)),
                    jnp.float32)
    whole = jnp.stack([reference.layer(flat, "", seq, kind, cfg)
                       for seq in x])
    no_experts = dict(flat, w_down=jnp.zeros_like(flat["w_down"]))

    def head_share(r):
        mine = dict(no_experts)
        mine["q/kernel"] = flat["q/kernel"][:, 16 * r:16 * r + 16]
        for n in ("k", "v"):
            mine[n + "/kernel"] = flat[n + "/kernel"][:, 8 * r:8 * r + 8]
        mine["o/kernel"] = flat["o/kernel"][16 * r:16 * r + 16]
        return _block(kind, (2 * r, 2 * r + 2), (r, r + 1),
                      (0, 8)).apply(_tree(mine), x)[0]

    branch = sum(head_share(r) - x for r in range(4))
    assert float(jnp.abs(branch).max()) > 1e-3
    after_attention = _block(kind, (0, 8), (0, 4), (0, 8)).apply(
        _tree(no_experts), x)[0]
    np.testing.assert_allclose(x + branch, after_attention, rtol=1e-5,
                               atol=1e-6)

    def expert_share(e):
        mine = dict(flat)
        for n in ("w_gate", "w_up", "w_down"):
            mine[n] = flat[n][e:e + 1]
        out, rows = _block(kind, (0, 8), (0, 4), (e, e + 1)).apply(
            _tree(mine), x)
        return out - after_attention, int(rows)

    parts = [expert_share(e) for e in range(8)]
    routed = sum(p[0] for p in parts)
    assert float(jnp.abs(routed).max()) > 1e-3
    np.testing.assert_allclose(x + branch + routed, whole, rtol=1e-5,
                               atol=1e-6)
    # every routed row is some rank's
    assert sum(p[1] for p in parts) == 2 * 21 * 2
    with pytest.raises(ValueError, match="not the groups"):
        _block(kind, (0, 2), (1, 2), (0, 8)).apply(_tree(flat), x)


# -- the memory plan and the shared branch ------------------------------------

def test_the_model_recomputes_its_attention_cores_and_nothing_else():
    """The memory plan: a block keeps what its matmuls produced; the one
    rematerialised region a layer is the dense attention core of
    ``rotary_attention`` (the kernel, where it runs, has none). No
    ``jax.checkpoint`` wraps a block or the dispatch: XLA read the fused
    step's peak in its flat-sized tail, where a whole-block
    rematerialisation buys nothing (PERF.md section 4)."""
    names, grad_step = bench_model.build(TINY, SEQ)
    leaves = bench_model.leaves_from(
        reference.init_params(TINY, PARAM_SEED), names)
    # one sequence a pass: the accumulation loop's body is traced once
    text = str(jax.make_jaxpr(lambda l, t: grad_step(l, t, None))(
        leaves, _tokens(TOKEN_SEED)))
    assert text.count("remat") == len(TINY["layer_types"]) == 4


@pytest.mark.parametrize("window", [None, 8], ids=["full", "window"])
def test_the_ungated_branch_is_gated_attention_at_an_open_gate(window):
    """``gated_attention`` is ``rotary_attention`` times its gate: at a
    gate whose sigmoid is 1 in float32 the two are equal bit for bit,
    and at any gate the gated one is the ungated one times sigmoid."""
    rng = np.random.default_rng(9)
    q = jnp.asarray(rng.normal(size=(2, 21, 2, 3, 8)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(2, 21, 2, 8)), jnp.float32)
            for _ in range(2))
    inv_freq, factor = rotary_frequencies(ROPE[FULL], 8)
    plain = rotary_attention(q, k, v, inv_freq, factor, window)
    assert plain.shape == q.shape
    wide_open = jnp.full((2, 21, 48), 30.0, jnp.float32)
    assert float(jax.nn.sigmoid(wide_open).min()) == 1.0
    np.testing.assert_array_equal(
        gated_attention(q, k, v, wide_open, inv_freq, factor, window),
        plain.reshape(2, 21, 48))
    gate = jnp.asarray(rng.normal(size=(2, 21, 48)), jnp.float32)
    np.testing.assert_allclose(
        gated_attention(q, k, v, gate, inv_freq, factor, window),
        plain.reshape(2, 21, 48) * jax.nn.sigmoid(gate), rtol=1e-6)
    # the same recomputation rule: a dense core is computed again
    assert "remat" in str(jax.make_jaxpr(lambda q: rotary_attention(
        q, k, v, inv_freq, factor, window))(q))


@pytest.mark.parametrize("kind", [SLIDING, FULL])
def test_a_block_on_the_kernels_is_the_block_off_them(kind, monkeypatch):
    """A sliding and the full layer with the core as the Pallas kernels
    (the rule forced, the kernels interpreted: the window rule at a
    window of 8 over 21 positions) against the same block on the
    blocked / the dense product: the block's output and every
    parameter's gradient; the kernel form is not rematerialised."""
    from functools import partial

    from geomx_tpu.models import transformer

    flat = _layer_params(dict(TINY, hidden_size=32, head_dim=8,
                              num_experts=8, layer_types=[kind],
                              num_hidden_layers=1, query_heads=[0, 8],
                              key_value_heads=[0, 2], local_experts=[0, 8]))
    x = jnp.asarray(np.random.default_rng(4).normal(size=(2, 21, 32)),
                    jnp.float32)
    block = _block(kind, (0, 8), (0, 2), (0, 8))

    def loss(variables):
        out = block.apply(variables, x)[0]
        return jnp.sum(jnp.sin(out)), out

    (_l, want), grads_want = jax.value_and_grad(loss, has_aux=True)(
        _tree(flat))
    monkeypatch.setattr(transformer, "runs_kernel", partial(
        transformer.runs_kernel, forced=True))
    (_l, got), grads_got = jax.value_and_grad(loss, has_aux=True)(
        _tree(flat))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(grads_got),
                    jax.tree_util.tree_leaves(grads_want)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)
    text = str(jax.make_jaxpr(lambda v: block.apply(v, x))(_tree(flat)))
    assert "pallas_call" in text and "remat" not in text


def test_counts_on_the_kernels_against_a_count_by_hand():
    """``counts(1, t, True)``: the full layer's live causal tiles and,
    from the rule's floor on the window, the sliding layers' live tiles
    of the band; ``counts(1, t)`` is what it was, and under the floor
    the sliding layers stay on the blocked product."""
    from geomx_tpu.models.transformer import KERNEL_MIN_WINDOW

    t = 1100
    model = bench_model.model_of(dict(TINY, sliding_window=512))
    assert KERNEL_MIN_WINDOW <= 512
    # a sliding head keeps 512 * 513 / 2 + 588 * 512 entries; the
    # blocked product has 3 blocks of 512 x 1,024, the kernels 1 + 2 + 2
    # tiles of 512 x 512 (q-blocks of 512, 512 and 76 rows); a full head
    # keeps 1100 * 1101 / 2 of 1100 * 1100, the kernels 1 + 1 + 2 tiles
    # of 512 x 1,024
    rows, live, computed = model.counts(1, t)
    assert live == 8 * (3 * 432_384 + 605_550)
    assert computed == 8 * (3 * 3 * 512 * 1024 + 1100 * 1100)
    assert model.counts(1, t, True) == (
        rows, live, 8 * (3 * 5 * 512 * 512 + 4 * 512 * 1024))
    # the tiny model's window of 8 is under the floor
    tiny = bench_model.model_of(TINY)
    assert tiny.counts(1, SEQ, True)[2] - tiny.counts(1, SEQ)[2] \
        == 8 * (40 * 40 - SEQ * SEQ)
    assert model.counts(2, t, True)[2] == 2 * model.counts(1, t, True)[2]


# -- one round through the system ---------------------------------------------

@pytest.mark.time_limit(300)
def test_two_party_round_books_the_four_counters():
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
    assert booked("attn.score_entries_live") == \
        sequences * 8 * (3 * 268 + 703)
    assert booked("attn.score_entries_computed") == \
        sequences * 8 * (3 * 640 + 1369)
