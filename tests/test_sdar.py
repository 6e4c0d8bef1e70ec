"""SDAR's block-diffusion training through the program: the model and
its masked-token loss against the benchmark's plain float32 reference,
what the block mask MEANS (which tokens a noised block's logits can
see, and that they are the logits of the pass generation makes), rotary
positions by position id, the loss's weights by hand, the ranks' shares
against the uncut layer, the noise generator, and one two-party HiPS
round through the device-resident trainer.

Tiny widths, seeded weights, CPU. The published widths are compared on
the chip (``benchmark/tests/chip_limits_sdar.py``, PERF.md section 2).
"""

import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.data import block_noise
from benchmark.models import sdar as bench_model
from benchmark.references import sdar as reference
from geomx_tpu import telemetry
from geomx_tpu.models import moe
from geomx_tpu.models.sdar import SdarBlock
from geomx_tpu.models.transformer import (block_diffusion_attention,
                                          block_diffusion_mask,
                                          block_score_entries, rotary,
                                          rotary_frequencies)
from geomx_tpu.simulate import InProcessHiPS
from geomx_tpu.trainer_device import DeviceResidentTrainer

BLOCK = 4
# a rank in the middle of a layout: key/value heads 1..2 of 4 with their
# query groups of 4, experts 4..7 of 16
TINY = dict(
    family="sdar", compute_dtype="float32", hidden_size=64, head_dim=16,
    moe_intermediate_size=32, num_experts=16, num_experts_per_tok=2,
    vocab_size=128, rms_norm_eps=1e-6, rope_theta=1e6, block_length=BLOCK,
    num_hidden_layers=4, query_heads=[4, 12], key_value_heads=[1, 3],
    local_experts=[4, 8], microbatch_sequences=1)
SEQ = 36
PARAM_SEED, DATA_SEED = 2147483700, 7
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _batch(seed, batch=2, seq=SEQ):
    return jnp.asarray(block_noise.batch(
        np.random.default_rng(seed), batch, seq + 1, TINY["vocab_size"]))


def _leaf_errors(cfg, operand_dtype=None, system=True):
    """Relative error of the loss and relative L2 error of every
    gradient leaf against the float32 reference: of the program's model
    (``system``) or of the reference with rounded matmul operands."""
    params = reference.init_params(cfg, PARAM_SEED)
    batch = _batch(DATA_SEED)
    loss_r, grads_r = jax.jit(
        lambda p, x: reference.loss_and_grads(p, x, cfg))(params, batch)
    if system:
        names, grad_step = bench_model.build(cfg, SEQ)
        loss, grads = jax.jit(grad_step)(
            bench_model.leaves_from(params, names), batch, None)
        grads = dict(zip(names, grads))
    else:
        loss, grads = jax.jit(lambda p, x: reference.loss_and_grads(
            p, x, cfg, operand_dtype))(params, batch)
    errs = {n: float(jnp.linalg.norm(grads[n] - g) / jnp.linalg.norm(g))
            for n, g in grads_r.items()}
    return abs(float(loss) - float(loss_r)) / float(loss_r), errs


# bfloat16 keeps 8 bits of significand; a leaf's gradient passes a few
# matmuls with rounded operands. 0.03 sits between what the program in
# bfloat16 reads a leaf and what the same mathematics reads with
# float8_e4m3 operands (the seeds above; the readings are in the test's
# failure message when they move). Top-k routing is discrete: a near-tie
# of the k-th and (k+1)-th router probability flips a position's expert
# on a rounding upstream; the seeds were chosen clear of that.
LEAF_TOL = 0.03


@pytest.mark.parametrize("dtype,loss_tol,leaf_tol", [
    ("float32", 1e-5, 1e-5), ("bfloat16", 1e-3, LEAF_TOL)])
def test_model_matches_the_float32_reference(dtype, loss_tol, leaf_tol):
    loss_err, errs = _leaf_errors(dict(TINY, compute_dtype=dtype))
    assert len(errs) == 51
    assert loss_err <= loss_tol
    over = {n: e for n, e in errs.items() if e > leaf_tol}
    assert not over, over


def test_float8_operands_fail_the_bfloat16_tolerance():
    _loss_err, errs = _leaf_errors(TINY, "float8_e4m3fn", system=False)
    under = {n: e for n, e in errs.items() if e <= LEAF_TOL}
    assert not under, under


def test_the_reference_in_query_slabs_is_the_whole_product(monkeypatch):
    """``reference.attention`` a slab of queries at a time (five slabs of
    16 over 72 positions, the last padded) against the [2T, 2T] product
    under the program's mask, which is written another way."""
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.normal(size=(2 * SEQ, 4, 8)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(2 * SEQ, 2, 8)), jnp.float32)
            for _ in range(2))
    monkeypatch.setattr(reference, "QUERY_BLOCK", 16)
    mask = block_diffusion_mask(SEQ, BLOCK)
    np.testing.assert_array_equal(
        reference.live_keys(jnp.arange(2 * SEQ), SEQ, BLOCK), mask)
    s = jnp.einsum("qhd,khd->hqk", q, jnp.repeat(k, 2, 1)) / math.sqrt(8)
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1)
    want = jnp.einsum("hqk,khd->qhd", p, jnp.repeat(v, 2, 1))
    np.testing.assert_allclose(
        reference.attention(q, k, v, SEQ, BLOCK), want.reshape(2 * SEQ, 32),
        rtol=2e-5, atol=2e-6)


# -- the mask -----------------------------------------------------------------

def test_the_mask_is_the_four_rules_and_every_row_has_a_key():
    t = 10      # the last block is short
    mask = block_diffusion_mask(t, BLOCK)
    assert mask.shape == (2 * t, 2 * t) and mask.dtype == bool
    for i in range(2 * t):
        for j in range(2 * t):
            bi, bj = i % t // BLOCK, j % t // BLOCK
            want = (bj <= bi if j < t else False) if i < t else (
                bj < bi if j < t else bj == bi)
            assert mask[i, j] == want, (i, j)
    assert mask.any(1).all()
    assert block_score_entries(t, BLOCK) == (int(mask.sum()), 4 * t * t)
    # whole blocks: T(T+B) live entries
    assert block_score_entries(SEQ, BLOCK)[0] == SEQ * (SEQ + BLOCK)
    assert block_score_entries(4096, 4) == (16_793_600, 67_108_864)


def test_dense_block_diffusion_attention_against_numpy():
    rng = np.random.default_rng(3)
    q = rng.normal(size=(2, 2 * SEQ, 2, 3, 8)).astype(np.float32)
    k, v = (rng.normal(size=(2, 2 * SEQ, 2, 8)).astype(np.float32)
            for _ in range(2))
    got = block_diffusion_attention(*(jnp.asarray(x) for x in (q, k, v)),
                                    BLOCK)
    mask = block_diffusion_mask(SEQ, BLOCK)
    s = np.einsum("bqkgd,bjkd->bkgqj", q.astype(np.float64),
                  k.astype(np.float64)) / math.sqrt(8)
    s = np.where(mask, s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    np.testing.assert_allclose(
        got, np.einsum("bkgqj,bjkd->bqkgd", p, v.astype(np.float64)),
        rtol=2e-5, atol=2e-6)
    # the dense core is computed again on the way back
    assert "remat" in str(jax.make_jaxpr(
        lambda q: block_diffusion_attention(q, k, v, BLOCK))(q))


def _model_and_variables(seed=11):
    cfg = dict(TINY, num_hidden_layers=2)
    model = bench_model.model_of(cfg)
    params = reference.init_params(cfg, seed)
    names, _ = bench_model.build(cfg, SEQ)
    flat = dict(zip(names, bench_model.leaves_from(params, names)))
    return cfg, model, _tree(flat), flat


def _tree(flat):
    tree = {}
    for name, value in flat.items():
        *path, leaf = name.split("/")
        node = tree
        for part in path:
            node = node.setdefault(part, {})
        node[leaf] = value
    return {"params": tree}


def _ids(seed, t=SEQ):
    rng = np.random.default_rng(seed)
    return rng.integers(0, 127, (1, t)), rng.integers(0, 128, (1, t))


def test_what_a_noised_blocks_logits_can_see():
    """Block 4 of 9 (positions 16..19): its logits stay put when clean
    tokens of its own or later blocks change and when noised tokens of
    any other block change; they move with an earlier clean block and
    with its own noised tokens."""
    _cfg, model, variables, _ = _model_and_variables()
    clean, noised = _ids(5)
    mine = slice(16, 20)

    def logits(clean, noised):
        return np.asarray(model.apply(variables, jnp.asarray(
            np.concatenate([clean, noised], 1)))[0])[0, mine]

    base = logits(clean, noised)
    assert base.shape == (4, 128)

    def changed(which, at):
        c, n = clean.copy(), noised.copy()
        (c if which == "clean" else n)[0, at] += 1
        return logits(c, n)

    for which, at in (("clean", mine), ("clean", slice(20, SEQ)),
                      ("noised", slice(0, 16)), ("noised", slice(20, SEQ))):
        np.testing.assert_array_equal(changed(which, at), base,
                                      err_msg=f"{which} {at}")
    for which, at in (("clean", slice(0, 4)), ("clean", slice(12, 16)),
                      ("noised", slice(17, 18))):
        assert np.abs(changed(which, at) - base).max() > 1e-4, (which, at)


def _stack(flat, cfg, z):
    """The model's blocks on hidden states ``z`` [B, 2T, D]: every row
    of both halves, which ``Sdar`` itself does not hand out."""
    for i in range(cfg["num_hidden_layers"]):
        mine = {n[len(f"block{i}/"):]: p for n, p in flat.items()
                if n.startswith(f"block{i}/")}
        z = SdarBlock(
            cfg["hidden_size"], cfg["head_dim"], tuple(cfg["query_heads"]),
            tuple(cfg["key_value_heads"]), cfg["block_length"],
            cfg["rope_theta"], cfg["num_experts"],
            cfg["num_experts_per_tok"], cfg["moe_intermediate_size"],
            tuple(cfg["local_experts"])).apply(_tree(mine), z)[0]
    return z


def test_the_clean_half_never_sees_the_noised_half():
    cfg, _model, _variables, flat = _model_and_variables()
    rng = np.random.default_rng(6)
    z = rng.normal(size=(1, 2 * SEQ, 64)).astype(np.float32)
    other = z.copy()
    other[:, SEQ:] = rng.normal(size=(1, SEQ, 64))
    a, b = (np.asarray(_stack(flat, cfg, jnp.asarray(x)))
            for x in (z, other))
    np.testing.assert_array_equal(a[:, :SEQ], b[:, :SEQ])
    assert np.abs(a[:, SEQ:] - b[:, SEQ:]).max() > 1e-3


def test_a_noised_block_is_the_pass_generation_makes():
    """Block 4's noised rows out of the 2T pass are the rows of a plain
    pass over [the clean blocks before it ; the noised block] under a
    block-causal mask: the form generation uses (denoise a block on a
    cache of clean blocks). The clean half of a pass IS such a plain
    pass, so the shorter sequence goes in as the clean copy of a pass of
    its own length."""
    cfg, _model, _variables, flat = _model_and_variables()
    rng = np.random.default_rng(8)
    z = rng.normal(size=(1, 2 * SEQ, 64)).astype(np.float32)
    whole = np.asarray(_stack(flat, cfg, jnp.asarray(z)))
    short = np.concatenate([z[:, :16], z[:, SEQ + 16:SEQ + 20]], 1)
    plain = np.asarray(_stack(flat, cfg, jnp.asarray(
        np.concatenate([short, rng.normal(size=short.shape)], 1),
        jnp.float32)))
    np.testing.assert_allclose(plain[:, 16:20], whole[:, SEQ + 16:SEQ + 20],
                               rtol=1e-5, atol=1e-6)


# -- positions and the loss by hand -------------------------------------------

def test_rotary_by_position_id():
    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.normal(size=(2, 24, 3, 16)), jnp.float32)
    inv_freq, factor = rotary_frequencies(
        {"rope_type": "default", "rope_theta": 1e6}, 16)
    # the index is the default
    np.testing.assert_array_equal(
        rotary(x, inv_freq, factor, positions=jnp.arange(24)),
        rotary(x, inv_freq, factor))
    # two copies side by side turn as each alone
    both = rotary(x, inv_freq, factor, positions=jnp.tile(jnp.arange(12), 2))
    for half in (slice(0, 12), slice(12, 24)):
        np.testing.assert_allclose(both[:, half],
                                   rotary(x[:, half], inv_freq, factor),
                                   rtol=1e-6, atol=1e-7)
    # the reference's table from the formula, at the same ids
    cos, sin = reference.rotary_table(1e6, 16, jnp.arange(24) % 12)
    np.testing.assert_allclose(cos[13, :8], np.cos(inv_freq), rtol=1e-6)
    np.testing.assert_allclose(sin[14, 8:], np.sin(2 * inv_freq), rtol=1e-6)


class _Fixed:
    """A model whose logits are given: the loss alone is under test."""
    vocab = 16

    def __init__(self, logits):
        self.logits = logits

    def apply(self, _variables, ids):
        assert ids.shape[1] == 2 * self.logits.shape[1]
        self.ids = ids
        return self.logits, jnp.int32(3)

    def counts(self, batch, t, kernel):
        return (10 * batch, 20 * t, 30)


def test_the_loss_weighs_masked_positions_by_one_over_p():
    rng = np.random.default_rng(10)
    s, t = 2, 8
    logits = rng.normal(size=(s, t, 16)).astype(np.float32)
    x0 = rng.integers(0, 15, (s, t + 1))
    m = rng.integers(0, 2, (s, t + 1))
    n = np.repeat(rng.integers(1, 1001, (s, 3)), BLOCK, 1)[:, :t + 1]
    m[0, 0], m[0, 1] = 1, 0

    def loss(x0, m, n):
        model = _Fixed(jnp.asarray(logits))
        out, counts = moe.masked_diffusion_loss(
            model, None, jnp.asarray(np.stack([x0, m, n], 1), jnp.int32))
        return float(out), np.asarray(counts), np.asarray(model.ids)

    got, counts, ids = loss(x0, m, n)
    logp = logits - np.log(np.exp(logits).sum(-1, keepdims=True))
    terms = np.zeros((s, t))
    for i in range(s):
        for j in range(t):
            terms[i, j] = m[i, j] * (1000.0 / n[i, j]) * -logp[i, j, x0[i, j]]
    assert got == pytest.approx(terms.sum() / (s * t), rel=1e-5)
    # the model saw [x0 ; MASK where masked], the last column dropped
    np.testing.assert_array_equal(ids[:, :t], x0[:, :t])
    np.testing.assert_array_equal(
        ids[:, t:], np.where(m[:, :t] > 0, 15, x0[:, :t]))
    np.testing.assert_array_equal(
        counts, [3, 10 * s, 20 * t, 30, m[:, :t].sum(), s * t])
    # an unmasked position adds nothing, whatever its token and level
    x1, n1 = x0.copy(), n.copy()
    x1[0, 1], n1[0, 1] = (x0[0, 1] + 1) % 15, 1
    assert loss(x1, m, n1)[0] == pytest.approx(got, rel=1e-6)
    # doubling n halves a masked position's term
    n2 = n.copy()
    n2[0, 0] = 2 * n[0, 0]
    assert loss(x0, m, n2)[0] == pytest.approx(
        got - terms[0, 0] / 2 / (s * t), rel=1e-5)


# -- the shares add up --------------------------------------------------------

# one layer uncut: 4 key/value heads with 2 query heads each, 16 experts
WHOLE = dict(TINY, hidden_size=32, head_dim=8, num_experts=16,
             num_hidden_layers=1, key_value_heads=[0, 4],
             query_heads=[0, 8], local_experts=[0, 16])


def _block(q_heads, kv_heads, local):
    return SdarBlock(
        dim=32, head_dim=8, query_heads=q_heads, key_value_heads=kv_heads,
        block_length=BLOCK, rope_theta=1e6, num_experts=16,
        experts_per_token=2, expert_width=32, local_experts=local)


def test_four_head_shares_by_sixteen_expert_shares_sum_to_the_layer():
    """Tensor parallel 4 x expert parallel 16: head rank r holds
    key/value head r with its two query heads, expert rank e holds
    expert e. With the experts' down projection zero a block returns
    h' = z + the rank's part of o Wo, and the four parts are the uncut
    branch; on that h' the sixteen expert ranks' terms are the uncut
    layer's routed sum. The whole is the REFERENCE's layer."""
    params = reference.init_params(WHOLE, 3)
    flat = {n[len("block0/"):]: p for n, p in params.items()
            if n.startswith("block0/")}
    z = jnp.asarray(np.random.default_rng(4).normal(size=(2, 40, 32)),
                    jnp.float32)
    whole = jnp.stack([reference.layer(flat, "", seq, WHOLE) for seq in z])
    no_experts = dict(flat, w_down=jnp.zeros_like(flat["w_down"]))

    def head_share(r):
        mine = dict(no_experts)
        mine["q/kernel"] = flat["q/kernel"][:, 16 * r:16 * r + 16]
        for n in ("k", "v"):
            mine[n + "/kernel"] = flat[n + "/kernel"][:, 8 * r:8 * r + 8]
        mine["o/kernel"] = flat["o/kernel"][16 * r:16 * r + 16]
        return _block((2 * r, 2 * r + 2), (r, r + 1),
                      (0, 16)).apply(_tree(mine), z)[0]

    branch = sum(head_share(r) - z for r in range(4))
    assert float(jnp.abs(branch).max()) > 1e-3
    after_attention = _block((0, 8), (0, 4), (0, 16)).apply(
        _tree(no_experts), z)[0]
    np.testing.assert_allclose(z + branch, after_attention, rtol=1e-5,
                               atol=1e-6)

    def expert_share(e):
        mine = dict(flat)
        for n in ("w_gate", "w_up", "w_down"):
            mine[n] = flat[n][e:e + 1]
        out, rows = _block((0, 8), (0, 4), (e, e + 1)).apply(_tree(mine), z)
        return out - after_attention, int(rows)

    parts = [expert_share(e) for e in range(16)]
    routed = sum(p[0] for p in parts)
    assert float(jnp.abs(routed).max()) > 1e-3
    np.testing.assert_allclose(z + branch + routed, whole, rtol=1e-5,
                               atol=1e-6)
    # every routed row is some rank's
    assert sum(p[1] for p in parts) == 2 * 40 * 2
    with pytest.raises(ValueError, match="not the groups"):
        _block((0, 2), (1, 2), (0, 16)).apply(_tree(flat), z)


# -- the configuration's file, the generator, the scopes ----------------------

def _published():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "sdar-30b-a3b-ep16.json")) as f:
        return json.load(f)


def test_sizes_in_the_configuration_are_the_references_shapes():
    cfg = _published()
    shapes = reference.param_shapes(cfg)
    sizes = cfg["sizes"]
    assert len(shapes) == sizes["keys"] == 51
    assert reference.num_params(cfg) == sizes["parameters"] == 248_728_576
    layer = sum(math.prod(s) for n, s in shapes.items()
                if n.startswith("block0/"))
    assert layer == sizes["a_layer"]["total"] == 42_733_824
    assert math.prod(shapes["embed/embedding"]) == sizes["embedding"] \
        == math.prod(shapes["head/kernel"]) == sizes["head"] == 38_895_616
    assert sizes["trainer_state_bytes_two_trainers"] == 32 * 248_728_576
    # the generator's block is the configuration's
    assert cfg["block_length"] == block_noise.BLOCK
    assert reference.NOISE_STEPS == moe.NOISE_STEPS == block_noise.STEPS
    # what the catalog publishes is kept but for the five reduced keys
    for key, value in cfg["published"].items():
        if key not in cfg["reduced"]:
            assert cfg[key] == value, key
    assert sorted(cfg["reduced"]) == sorted(
        k for k, v in cfg["published"].items() if cfg[k] != v)
    # a counted token costs two positions: the count per token at the
    # cell's length, by hand for one layer's projections
    assert reference.live_score_entries(cfg, 4096) == 8 * 4 * 16_793_600


def test_the_generator_is_seeded_and_draws_what_it_says():
    a = block_noise.batch(np.random.default_rng(1), 8, 4097, 18992)
    b = block_noise.batch(np.random.default_rng(1), 8, 4097, 18992)
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(
        a, block_noise.batch(np.random.default_rng(2), 8, 4097, 18992))
    assert a.shape == (8, 3, 4097) and a.dtype == np.int32
    x0, m, n = a[:, 0], a[:, 1], a[:, 2]
    assert x0.min() >= 0 and x0.max() < 18991      # MASK is never drawn
    np.testing.assert_array_equal(x0[:, 1:], (3 * x0[:, :-1] + 7) % 18991)
    assert set(np.unique(m)) == {0, 1}
    assert n.min() >= 1 and n.max() <= 1000
    blocks = n[:, :4096].reshape(8, 1024, 4)
    assert (blocks == blocks[:, :, :1]).all()       # constant a block
    assert len(np.unique(blocks[:, :, 0])) > 900    # and not a sequence
    # Bernoulli(n / 1000) a position: the masked share is the mean p
    assert m.mean() == pytest.approx(n.mean() / 1000, abs=0.01)
    assert m.mean() == pytest.approx(0.5, abs=0.02)
    low, high = n < 100, n > 900
    assert m[low].mean() < 0.1 and m[high].mean() > 0.9


def test_the_scopes_are_in_the_lowered_grad_step():
    names, grad_step = bench_model.build(TINY, SEQ)
    leaves = bench_model.leaves_from(
        reference.init_params(TINY, PARAM_SEED), names)
    text = jax.jit(grad_step.counted[1]).lower(
        leaves, _batch(DATA_SEED), None).as_text(debug_info=True)
    for scope in ("attention_blockdiff", "blockdiff_core", "router",
                  "dispatch", "combine", "expert_matmuls", "head"):
        assert f"/{scope}/" in text, scope
    assert grad_step.counted[0] == (
        "moe.rows_local", "moe.rows_total", "attn.score_entries_live",
        "attn.score_entries_computed", "diffusion.positions_masked",
        "diffusion.positions")


# -- one round through the system ---------------------------------------------

@pytest.mark.time_limit(300)
def test_two_party_round_books_the_six_counters_and_pushes_51_keys():
    names, grad_step = bench_model.build(TINY, SEQ)
    params = reference.init_params(TINY, 5)
    leaves = [np.array(x) for x in bench_model.leaves_from(params, names)]
    was_on = telemetry.enabled()
    telemetry.enable(True)
    before = telemetry.snapshot()["counters"]
    topo = InProcessHiPS(num_parties=2, workers_per_party=1).start()
    out, masked, pushed = {}, {}, {}
    try:
        def master_init(kv):
            for i, leaf in enumerate(leaves):
                kv.init(i, leaf)
            kv.wait()

        def worker(kv):
            w = topo.workers.index(kv)
            push = kv.push_pull_bsc_batch_async

            def recording(keys, *a, **kw):
                pushed.setdefault(w, set()).update(keys)
                return push(keys, *a, **kw)

            kv.push_pull_bsc_batch_async = recording
            tr = DeviceResidentTrainer(
                list(leaves), kv, grad_step, threshold=0.05,
                learning_rate=0.05, momentum=0.9)
            tr.warmup(_batch(7, batch=4), None)
            batches = [_batch(100 * w + r, batch=4) for r in range(2)]
            masked[w] = sum(int(b[:, 1, :-1].sum()) for b in batches)
            losses = [tr.step(b, None) for b in batches]
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
    assert pushed[0] == pushed[1] == set(range(51))

    def booked(name):
        return after[name] - before.get(name, 0)

    # 2 workers x 2 rounds x 4 sequences
    sequences = 2 * 2 * 4
    # x 2 copies x 36 tokens x 4 layers x top-2
    assert booked("moe.rows_total") == sequences * 2 * SEQ * 4 * 2
    assert 0 < booked("moe.rows_local") < booked("moe.rows_total")
    # 8 held query heads x 4 layers x T(T+B) of (2T)^2
    assert booked("attn.score_entries_live") == \
        sequences * 8 * 4 * SEQ * (SEQ + BLOCK)
    assert booked("attn.score_entries_computed") == \
        sequences * 8 * 4 * 4 * SEQ * SEQ
    assert booked("diffusion.positions") == sequences * SEQ
    assert booked("diffusion.positions_masked") == masked[0] + masked[1]
