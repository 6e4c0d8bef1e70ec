"""Ouro's looped training through the program: the model and its
expected-exit loss against the benchmark's plain float32 reference,
the loop tied to the model (a shared weight's gradient is the sum over
four untied copies of the stack), one pass as the plain stack, the exit
distribution and its entropy by hand, the lowered program holding each
block once whatever the number of passes, and one two-party HiPS round
through the device-resident trainer.

Tiny widths, seeded weights, CPU. The published widths are compared on
the chip (``benchmark/tests/chip_limits.py``, PERF.md section 2).
"""

import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.data import pattern
from benchmark.models import ouro as bench_model
from benchmark.references import ouro as reference
from geomx_tpu import telemetry
from geomx_tpu.models import ouro
from geomx_tpu.models.transformer import (rotary, rotary_frequencies,
                                          score_entries)
from geomx_tpu.simulate import InProcessHiPS
from geomx_tpu.trainer_device import DeviceResidentTrainer

STEPS = 4
# the cell's depth, so that the keys are the cell's 49
TINY = dict(
    family="ouro", compute_dtype="float32", hidden_size=64, head_dim=16,
    num_attention_heads=4, intermediate_size=96, vocab_size=128,
    rms_norm_eps=1e-6, rope_theta=1e6, num_hidden_layers=4,
    total_ut_steps=STEPS, microbatch_sequences=1)
SEQ = 36
PARAM_SEED, DATA_SEED = 2147483700, 7
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _batch(seed, batch=2, seq=SEQ):
    return jnp.asarray(pattern.batch(
        np.random.default_rng(seed), batch, seq + 1, TINY["vocab_size"]))


def _system(cfg, params, batch):
    names, grad_step = bench_model.build(cfg, SEQ)
    loss, grads = jax.jit(grad_step)(
        bench_model.leaves_from(params, names), batch, None)
    return loss, dict(zip(names, grads))


def _leaf_errors(cfg, operand_dtype=None, system=True):
    """Relative error of the loss and relative L2 error of every
    gradient leaf against the float32 reference: of the program's model
    (``system``) or of the reference with rounded matmul operands."""
    params = reference.init_params(cfg, PARAM_SEED)
    batch = _batch(DATA_SEED)
    loss_r, grads_r = jax.jit(
        lambda p, x: reference.loss_and_grads(p, x, cfg))(params, batch)
    if system:
        loss, grads = _system(cfg, params, batch)
    else:
        loss, grads = jax.jit(lambda p, x: reference.loss_and_grads(
            p, x, cfg, operand_dtype))(params, batch)
    errs = {n: float(jnp.linalg.norm(grads[n] - g) / jnp.linalg.norm(g))
            for n, g in grads_r.items()}
    return abs(float(loss) - float(loss_r)) / float(loss_r), errs


# bfloat16 keeps 8 bits of significand; a leaf's gradient passes a few
# matmuls with rounded operands, four times over. 0.05 sits between what
# the program in bfloat16 reads a leaf (0.024 at most, the seeds above)
# and what the same mathematics reads with float8_e4m3 operands (0.112
# at least; the readings are in the test's failure message when they
# move). The gate's bias is ONE number, the sum of its cotangent over
# every position and pass: the roundings average out in it (0.016 under
# float8), so it separates nothing and the control is not held to it.
LEAF_TOL = 0.05
ONE_NUMBER = "exit_gate/bias"


@pytest.mark.parametrize("dtype,loss_tol,leaf_tol", [
    ("float32", 1e-5, 1e-5), ("bfloat16", 1e-3, LEAF_TOL)])
def test_model_matches_the_float32_reference(dtype, loss_tol, leaf_tol):
    loss_err, errs = _leaf_errors(dict(TINY, compute_dtype=dtype))
    assert len(errs) == 49
    assert loss_err <= loss_tol
    over = {n: e for n, e in errs.items() if e > leaf_tol}
    assert not over, over


def test_float8_operands_fail_the_bfloat16_tolerance():
    _loss_err, errs = _leaf_errors(TINY, "float8_e4m3fn", system=False)
    under = {n: e for n, e in errs.items()
             if e <= LEAF_TOL and n != ONE_NUMBER}
    assert not under, under


def test_the_reference_in_query_slabs_is_the_whole_product(monkeypatch):
    """``reference.attention`` a slab of queries at a time (three slabs
    of 16 over 36 positions, the last padded) against the [T, T]
    product."""
    rng = np.random.default_rng(2)
    q, k, v = (jnp.asarray(rng.normal(size=(SEQ, 4, 8)), jnp.float32)
               for _ in range(3))
    monkeypatch.setattr(reference, "QUERY_BLOCK", 16)
    s = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(8)
    p = jax.nn.softmax(jnp.where(np.tril(np.ones((SEQ, SEQ), bool)), s,
                                 -jnp.inf), -1)
    np.testing.assert_allclose(
        reference.attention(q, k, v),
        jnp.einsum("hqk,khd->qhd", p, v).reshape(SEQ, 32),
        rtol=2e-5, atol=2e-6)


def test_the_references_rotary_table_is_the_programs_rotation():
    x = jnp.asarray(np.random.default_rng(9).normal(size=(1, 24, 3, 16)),
                    jnp.float32)
    inv_freq, factor = rotary_frequencies(
        {"rope_type": "default", "rope_theta": 1e6}, 16)
    cos, sin = reference.rotary_table(1e6, 16, jnp.arange(24))
    np.testing.assert_allclose(
        reference._rope(x[0], cos, sin), rotary(x, inv_freq, factor)[0],
        rtol=1e-5, atol=1e-6)


# -- the loop tied to the model -----------------------------------------------

def test_a_shared_weights_gradient_is_the_sum_over_four_untied_stacks():
    """The reference written out four times over UNTIED copies (pass t
    reads its stack, final norm, head and gate from copy t, the
    embedding from the first): at equal copies, the gradient the
    program gives a shared weight is the sum of the four copies'
    gradients, leaf by leaf, and no copy's share is nothing."""
    params = reference.init_params(TINY, PARAM_SEED)
    batch = _batch(DATA_SEED, batch=1)
    copies = [dict(params) for _ in range(STEPS)]
    loss_u, grads_u = jax.jit(jax.value_and_grad(
        lambda c: reference.passes_loss(c, batch[0], TINY)))(copies)
    loss, grads = _system(TINY, params, batch)
    assert float(loss) == pytest.approx(float(loss_u), rel=1e-6)
    for name, g in grads.items():
        parts = [c[name] for c in grads_u]
        np.testing.assert_allclose(g, sum(parts), rtol=2e-4, atol=1e-7,
                                   err_msg=name)
        share = [float(jnp.linalg.norm(p)) for p in parts]
        if name == "embed/embedding":
            assert share[0] > 0 and share[1:] == [0.0] * 3
        elif name.startswith("exit_gate/"):
            # the last pass takes what is left: its gate is read by nothing
            assert min(share[:-1]) > 0 and share[-1] == 0.0
        else:
            assert min(share) > 0.0, (name, share)


def test_one_pass_is_the_plain_stack():
    """``total_ut_steps`` 1: p_1 = 1, the entropy term is 0 and the loss
    is the mean cross-entropy of one pass of the stack, as the model's
    own logits give it and as the reference's layers give it."""
    cfg = dict(TINY, total_ut_steps=1)
    params = reference.init_params(cfg, PARAM_SEED)
    batch = _batch(DATA_SEED)
    loss, _ = _system(cfg, params, batch)
    model = bench_model.model_of(cfg)
    names, _ = bench_model.build(cfg, SEQ)
    variables = _tree(dict(zip(names,
                               bench_model.leaves_from(params, names))))
    logits, gate = model.apply(variables, batch[:, :-1])
    assert logits.shape == (1, 2, SEQ, 128) and gate.shape == (1, 2, SEQ)
    p, log_p = ouro.exit_distribution(gate)
    np.testing.assert_array_equal(p, 1.0)
    np.testing.assert_array_equal(log_p, 0.0)
    nll = -jnp.take_along_axis(jax.nn.log_softmax(logits[0]),
                               batch[:, 1:, None], -1)
    assert float(loss) == pytest.approx(float(nll.mean()), rel=1e-6)
    # the plain stack, layer by layer, from the reference
    h = params["embed/embedding"][batch[0, :-1]]
    for i in range(cfg["num_hidden_layers"]):
        h = reference.layer(params, f"block{i}/", h, cfg)
    h = reference._rms_norm(h, params["norm/scale"], 1e-6)
    np.testing.assert_allclose(logits[0, 0], h @ params["head/kernel"],
                               rtol=1e-4, atol=1e-5)


def _tree(flat):
    tree = {}
    for name, value in flat.items():
        *path, leaf = name.split("/")
        node = tree
        for part in path:
            node = node.setdefault(part, {})
        node[leaf] = value
    return {"params": tree}


# -- the exit distribution and the loss by hand -------------------------------

def test_the_exit_distribution_sums_to_one_and_its_entropy_is_numpys():
    z = np.random.default_rng(3).normal(scale=3.0, size=(STEPS, 2, 9))
    z[:, 0, 0] = [40.0, -40.0, 0.0, 5.0]       # a gate at 1 and one at 0
    p, log_p = ouro.exit_distribution(jnp.asarray(z, jnp.float32))
    g = 1.0 / (1.0 + np.exp(-z))
    want = np.stack([g[0], g[1] * (1 - g[0]),
                     g[2] * (1 - g[0]) * (1 - g[1]),
                     (1 - g[0]) * (1 - g[1]) * (1 - g[2])])
    np.testing.assert_allclose(p, want, rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(np.asarray(p).sum(0), 1.0, rtol=1e-6)
    assert np.isfinite(log_p).all()
    entropy = -np.sum(np.where(want > 0, want * np.log(
        np.maximum(want, 1e-300)), 0.0), 0)
    np.testing.assert_allclose(-jnp.sum(p * log_p, 0), entropy,
                               rtol=1e-4, atol=1e-6)
    # the reference writes it in probabilities
    ref_p = reference.exit_probabilities(list(jnp.asarray(g, jnp.float32)))
    np.testing.assert_allclose(np.stack(ref_p), want, rtol=1e-5, atol=1e-7)


class _Fixed:
    """A model whose exits are given: the loss alone is under test."""

    def __init__(self, nll, gate):
        self.nll, self.gate = nll, gate

    def apply(self, _variables, tokens, targets):
        self.seen = (tokens, targets)
        return self.nll, self.gate

    def counts(self, batch, t, kernel):
        return (10 * batch, 20 * t, 30)


def test_the_loss_is_the_expected_nll_less_beta_times_the_entropy():
    rng = np.random.default_rng(10)
    s, t = 2, 8
    nll = rng.uniform(1, 6, (STEPS, s, t)).astype(np.float32)
    z = rng.normal(size=(STEPS, s, t)).astype(np.float32)
    toks = rng.integers(0, 16, (s, t + 1))
    model = _Fixed(jnp.asarray(nll), jnp.asarray(z))
    loss, counts = ouro.looped_exit_loss(model, None, jnp.asarray(toks))
    g = 1.0 / (1.0 + np.exp(-z.astype(np.float64)))
    total = 0.0
    for i in range(s):
        for j in range(t):
            left, p = 1.0, []
            for step in range(STEPS - 1):
                p.append(left * g[step, i, j])
                left *= 1.0 - g[step, i, j]
            p.append(left)
            total += sum(p[k] * nll[k, i, j] for k in range(STEPS)) \
                + 0.05 * sum(q * math.log(q) for q in p)
    assert float(loss) == pytest.approx(total / (s * t), rel=1e-5)
    # the model saw the tokens and, shifted by one, their targets
    np.testing.assert_array_equal(model.seen[0], toks[:, :-1])
    np.testing.assert_array_equal(model.seen[1], toks[:, 1:])
    counts = np.asarray(counts)
    assert counts.shape == (4 + 2 * STEPS,)
    np.testing.assert_array_equal(counts[:4], [s * t, 10 * s, 20 * t, 30])
    assert counts[4:8].sum() == pytest.approx(s * t, rel=1e-5)
    np.testing.assert_allclose(counts[8:], nll.sum((1, 2)), rtol=1e-5)
    # beta 0 leaves the expectation alone
    plain, _ = ouro.looped_exit_loss(model, None, jnp.asarray(toks),
                                     beta=0.0)
    assert float(plain) > float(loss)


# -- the program: one copy of the blocks, the scopes --------------------------

def _lowered(cfg):
    names, grad_step = bench_model.build(cfg, SEQ)
    leaves = bench_model.leaves_from(
        reference.init_params(cfg, PARAM_SEED), names)
    return jax.jit(grad_step.counted[1]).lower(leaves, _batch(DATA_SEED),
                                               None), grad_step


def test_the_lowered_grad_step_holds_each_block_once_whatever_the_passes():
    """The R passes are one loop: the program of four passes has the
    matrix products of the program of one (seven a block, the head and
    the gate, forward, recomputed and back) and is no longer."""
    four, _ = _lowered(TINY)
    one, _ = _lowered(dict(TINY, total_ut_steps=1))
    two, _ = _lowered(dict(TINY, total_ut_steps=2))
    products = [low.as_text().count("stablehlo.dot_general")
                for low in (one, two, four)]
    assert products[0] == products[1] == products[2] > 0
    sizes = [len(low.as_text()) for low in (one, two, four)]
    assert max(sizes) - min(sizes) < 0.01 * sizes[0], sizes
    # and grows with the depth: the count does see a block
    deeper, _ = _lowered(dict(TINY, num_hidden_layers=5))
    assert deeper.as_text().count("stablehlo.dot_general") > products[2]


def test_the_scopes_and_the_counters_are_in_the_grad_step():
    lowered, grad_step = _lowered(TINY)
    text = lowered.as_text(debug_info=True)
    for scope in ("ouro_loop", "attention_full", "dense_ffn", "causal_core",
                  "ouro_exit"):
        assert f"/{scope}/" in text, scope
    # the core's scope lies inside the block's, the exit beside the blocks
    assert "/attention_full/causal_core/" in text
    assert "/ouro_exit/" in text and "/dense_ffn/ouro_exit/" not in text
    # every block and the exit are computed again on the way back
    assert "/checkpoint/" in text
    assert grad_step.counted[0] == (
        "ouro.positions", "ouro.layer_applications",
        "attn.score_entries_live", "attn.score_entries_computed",
        "ouro.exit_mass_t1", "ouro.exit_mass_t2", "ouro.exit_mass_t3",
        "ouro.exit_mass_t4", "ouro.nll_sum_t1", "ouro.nll_sum_t2",
        "ouro.nll_sum_t3", "ouro.nll_sum_t4")


# -- the configuration's file -------------------------------------------------

def _published():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "ouro-2.6b-vp8.json")) as f:
        return json.load(f)


def test_sizes_in_the_configuration_are_the_references_shapes():
    cfg = _published()
    shapes = reference.param_shapes(cfg)
    sizes = cfg["sizes"]
    assert len(shapes) == sizes["keys"] == 49
    assert reference.num_params(cfg) == sizes["parameters"] == 230_723_585
    layer = sum(math.prod(s) for n, s in shapes.items()
                if n.startswith("block0/"))
    assert layer == sizes["a_layer"]["total"] == 51_388_416
    assert math.prod(shapes["embed/embedding"]) == sizes["embedding"] \
        == math.prod(shapes["head/kernel"]) == sizes["head"] == 12_582_912
    assert sizes["trainer_state_bytes_two_trainers"] == 32 * 230_723_585
    # every published width and the number of passes are kept; the
    # reduced keys are the depth, its list of layer types, the vocabulary
    assert cfg["total_ut_steps"] == cfg["published"]["total_ut_steps"] == 4
    for key, value in cfg["published"].items():
        if key not in cfg["reduced"]:
            assert cfg[key] == value, key
    assert sorted(cfg["reduced"]) == sorted(
        k for k, v in cfg["published"].items() if cfg[k] != v) \
        == ["layer_types", "num_hidden_layers", "vocab_size"]
    assert cfg["layer_types"] == ["full_attention"] * 4
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert reference.ENTROPY_BETA == ouro.ENTROPY_BETA \
        == cfg["assumed"]["entropy_beta"]


def test_the_required_operations_by_hand():
    cfg = _published()
    # sixteen layer applications: four projections of 2048 x 2048, three of
    # 2048 x 5632, 4 x 128 an entry over (T + 1) / 2 keys and 16 heads;
    # four exits of 2048 x 6144 and the gate
    a_layer = 2 * (4 * 2048 * 2048 + 3 * 2048 * 5632) + 4 * 128 * 16 * 2048.5
    forward = 16 * a_layer + 4 * (2 * 2048 * 6144 + 2 * 2048)
    assert reference.forward_flops_per_token(cfg, 4096) == forward
    assert reference.train_flops_per_token(cfg, 4096) == 3 * forward
    assert 6.03e9 < 3 * forward < 6.05e9
    assert reference.live_score_entries(cfg, 4096) \
        == 16 * 16 * score_entries(4096)[0]
    model = bench_model.model_of(cfg)
    assert model.counts(2, 4096) == (
        32, 2 * reference.live_score_entries(cfg, 4096),
        32 * 16 * 4096 * 4096)


# -- one round through the system ---------------------------------------------

@pytest.mark.time_limit(300)
def test_two_party_round_books_the_counters_and_pushes_49_keys():
    names, grad_step = bench_model.build(TINY, SEQ)
    params = reference.init_params(TINY, 5)
    leaves = [np.array(x) for x in bench_model.leaves_from(params, names)]
    was_on = telemetry.enabled()
    telemetry.enable(True)
    before = telemetry.snapshot()["counters"]
    topo = InProcessHiPS(num_parties=2, workers_per_party=1).start()
    out, pushed = {}, {}
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
            losses = [tr.step(_batch(100 * w + r, batch=4), None)
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
    assert pushed[0] == pushed[1] == set(range(49))

    def booked(name):
        return after[name] - before.get(name, 0)

    # 2 workers x 2 rounds x 4 sequences
    sequences = 2 * 2 * 4
    assert booked("ouro.positions") == sequences * SEQ
    # x 4 layers x 4 passes
    assert booked("ouro.layer_applications") == sequences * 4 * STEPS
    # x 4 heads x T(T+1)/2 of T^2
    assert booked("attn.score_entries_live") == \
        sequences * 4 * STEPS * 4 * SEQ * (SEQ + 1) // 2
    assert booked("attn.score_entries_computed") == \
        sequences * 4 * STEPS * 4 * SEQ * SEQ
    # the exits' masses are a distribution over the positions
    masses = [booked(f"ouro.exit_mass_t{t}") for t in range(1, STEPS + 1)]
    assert min(masses) > 0
    assert sum(masses) == pytest.approx(sequences * SEQ, rel=1e-5)
    # a gate near 0.5 at the start: the last exit holds about an eighth
    assert masses[-1] / sum(masses) == pytest.approx(0.125, abs=0.03)
    for t in range(1, STEPS + 1):
        # random weights: about log(vocab) a position at every exit
        assert booked(f"ouro.nll_sum_t{t}") / (sequences * SEQ) \
            == pytest.approx(math.log(128), rel=0.2)
