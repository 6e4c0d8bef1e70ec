"""Kanana 2 through the program: the model against the benchmark's plain
float32 reference, the interleaved rotary pairing against complex
numbers, the router's choice by score plus bias with weights from the
score alone, the ranks' shares against the uncut layer (the latent and
the shared expert counted once), the kernel path against the dense one,
the configuration's arithmetic, the scopes the benchmark reads, and one
two-party HiPS round through the device-resident trainer.

Tiny widths, seeded weights, CPU. The published widths are compared on
the chip (``benchmark/tests/chip_limits.py``, PERF.md section 2).
"""

import json
import math
import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.models import kanana as bench_model
from benchmark.references import kanana as reference
from geomx_tpu import telemetry
from geomx_tpu.models import transformer
from geomx_tpu.models.kanana import KananaBlock
from geomx_tpu.simulate import InProcessHiPS
from geomx_tpu.trainer_device import DeviceResidentTrainer

BIAS = "e_score_correction_bias"
# a rank in the middle of a layout: heads 2..4 of 8, experts 4..7 of 16;
# a value head (12) that is neither the non-rotary (16) nor the whole
# query/key head (24)
TINY = dict(
    family="kanana", compute_dtype="float32", hidden_size=64,
    intermediate_size=96, moe_intermediate_size=32, n_shared_experts=2,
    n_routed_experts=16, num_experts_per_tok=3, kv_lora_rank=24,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=12,
    rope_theta=1000000, routed_scaling_factor=2.448,
    first_k_dense_replace=1, vocab_size=128, rms_norm_eps=1e-6,
    num_hidden_layers=3, query_heads=[2, 5], local_experts=[4, 8],
    microbatch_sequences=1, e_score_correction_bias={"seed": 7, "std": 0.1})
SEQ = 37
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
# program in bfloat16 reads 0.0096 at most a leaf, the same mathematics
# with float8_e4m3 operands 0.05 at least; 0.03 sits between, so
# computing in the next precision down fails on every leaf. Top-k
# routing is discrete (a near-tie flips a token's expert on a rounding
# upstream); the seeds were chosen clear of that, the chip's comparison
# has 16,384 tokens to average over.
LEAF_TOL = 0.03


@pytest.mark.parametrize("dtype,loss_tol,leaf_tol", [
    ("float32", 1e-5, 1e-5), ("bfloat16", 1e-3, LEAF_TOL)])
def test_model_matches_the_float32_reference(dtype, loss_tol, leaf_tol):
    loss_err, errs = _leaf_errors(dict(TINY, compute_dtype=dtype))
    # 7 keys of attention a layer, 3 of the dense FFN, 7 of an expert
    # layer, embedding, norm, head; the bias is none of them
    assert len(errs) == 3 * 7 + 3 + 2 * 7 + 3 == 41
    assert not any(BIAS in n for n in errs)
    assert loss_err <= loss_tol
    over = {n: e for n, e in errs.items() if e > leaf_tol}
    assert not over, over


def test_float8_operands_fail_the_bfloat16_tolerance():
    _loss_err, errs = _leaf_errors(TINY, "float8_e4m3fn", system=False)
    under = {n: e for n, e in errs.items() if e <= LEAF_TOL}
    assert not under, under


# -- rotary positions ---------------------------------------------------------

def test_interleaved_rotary_is_the_complex_product():
    """HF ``apply_rotary_pos_emb_interleave``: the pair (x_2i, x_2i+1)
    at position p times exp(i p theta^(-2i/d)), the real parts first on
    the way out; the program's and the reference's against numpy's
    complex numbers."""
    d, t, theta = 8, 21, 1e6
    x = np.random.default_rng(3).normal(size=(2, t, 3, d)).astype(np.float32)
    freq = theta ** (-np.arange(0, d, 2) / d)
    z = (x[..., 0::2] + 1j * x[..., 1::2]) * np.exp(
        1j * np.arange(t)[None, :, None, None] * freq)
    want = np.concatenate([z.real, z.imag], -1)
    got = transformer.rotary(jnp.asarray(x), freq.astype(np.float32), 1.0,
                             interleaved=True)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        reference._turned(jnp.asarray(x[0]), t, theta), want[0],
        rtol=1e-5, atol=1e-5)
    # the half-split pairing is another rotation of the same vector
    half = transformer.rotary(jnp.asarray(x), freq.astype(np.float32), 1.0)
    assert float(jnp.abs(half - got).max()) > 0.1


# -- the router ---------------------------------------------------------------

def _block(sparse, heads, local, whole=TINY):
    return KananaBlock(
        dim=whole["hidden_size"], nope_dim=whole["qk_nope_head_dim"],
        rope_dim=whole["qk_rope_head_dim"], value_dim=whole["v_head_dim"],
        latent_rank=whole["kv_lora_rank"], heads=heads,
        rope_theta=whole["rope_theta"], sparse=sparse,
        dense_width=whole["intermediate_size"],
        num_experts=whole["n_routed_experts"],
        experts_per_token=whole["num_experts_per_tok"],
        expert_width=whole["moe_intermediate_size"],
        shared_width=whole["moe_intermediate_size"]
        * whole["n_shared_experts"],
        local_experts=local, routed_scale=whole["routed_scaling_factor"])


def _layer_params(cfg, layer, seed=3):
    params = reference.init_params(cfg, seed)
    return {n[len(f"block{layer}/"):]: p for n, p in params.items()
            if n.startswith(f"block{layer}/")}


def _tree(flat, bias=None):
    tree = {}
    for name, value in flat.items():
        *path, leaf = name.split("/")
        node = tree
        for part in path:
            node = node.setdefault(part, {})
        node[leaf] = value
    out = {"params": tree}
    if bias is not None:
        out["buffers"] = {BIAS: jnp.asarray(bias)}
    return out


def _reference_layer(cfg, flat, x, sparse, bias=None):
    return jnp.stack([reference.layer(flat, "", seq, sparse, bias, cfg)
                      for seq in x])


# every head and every expert on one rank
WHOLE = dict(TINY, query_heads=[0, 8], local_experts=[0, 16])


def test_the_bias_chooses_and_the_scores_weigh(monkeypatch):
    """choice = top-k(s + b), weights = scale * s[choice] / sum: by hand
    in numpy, in the reference and in the block. A model that weighs by
    s + b, or that ignores b, is another function."""
    rng = np.random.default_rng(11)
    flat = _layer_params(WHOLE, 1)
    # a router wide enough apart that float32 ties decide nothing
    flat["router/kernel"] = jnp.asarray(
        rng.normal(0, 0.3, flat["router/kernel"].shape), jnp.float32)
    bias = reference.correction_bias(WHOLE)[f"block1/{BIAS}"]
    m = rng.normal(size=(50, 64)).astype(np.float32)
    s = 1.0 / (1.0 + np.exp(-(m.astype(np.float64)
                              @ np.asarray(flat["router/kernel"],
                                           np.float64))))
    choice = np.argsort(-(s + bias), axis=-1)[:, :3]
    picked = np.take_along_axis(s, choice, -1)
    want = 2.448 * picked / picked.sum(-1, keepdims=True)
    chosen, weights = reference.router_weights(
        jnp.asarray(m), flat["router/kernel"], jnp.asarray(bias), 3, 2.448)
    np.testing.assert_array_equal(np.sort(chosen, -1), np.sort(choice, -1))
    np.testing.assert_allclose(np.sort(weights, -1), np.sort(want, -1),
                               rtol=1e-5)
    # the bias moves the choice of a good share of the rows
    plain = np.argsort(-s, axis=-1)[:, :3]
    moved = (np.sort(plain, -1) != np.sort(choice, -1)).any(-1).mean()
    assert 0.2 < moved < 1.0

    x = jnp.asarray(rng.normal(size=(2, 21, 64)), jnp.float32)
    block = _block(True, (0, 8), (0, 16))
    got = block.apply(_tree(flat, bias), x)[0]
    np.testing.assert_allclose(
        got, _reference_layer(WHOLE, flat, x, True, jnp.asarray(bias)),
        rtol=1e-5, atol=1e-5)
    # a model that ignores b
    ignored = block.apply(_tree(flat, np.zeros_like(bias)), x)[0]
    assert float(jnp.abs(ignored - got).max()) > 1e-3
    np.testing.assert_allclose(
        ignored, _reference_layer(WHOLE, flat, x, True, 0.0),
        rtol=1e-5, atol=1e-5)

    # a model that weighs by s + b
    def weighs_by_the_sum(m, kernel, bias, k, scale):
        scores = jax.nn.sigmoid(m @ kernel) + bias
        top, chosen = jax.lax.top_k(scores, k)
        return chosen, scale * top / top.sum(-1, keepdims=True)

    monkeypatch.setattr(reference, "router_weights", weighs_by_the_sum)
    other = _reference_layer(WHOLE, flat, x, True, jnp.asarray(bias))
    # (small expert outputs at these weights; the comparison above holds
    # the block to the reference at 1e-5)
    assert float(jnp.abs(other - got).max()) > 3e-4
    _, summed = weighs_by_the_sum(jnp.asarray(m), flat["router/kernel"],
                                  jnp.asarray(bias), 3, 2.448)
    assert float(jnp.abs(jnp.sort(summed, -1)
                         - np.sort(want, -1)).max()) > 1e-2
    # zeros from init; a block without its buffers does not run
    made = block.init(jax.random.PRNGKey(0), x)
    assert not np.asarray(made["buffers"][BIAS]).any()
    with pytest.raises(Exception, match="buffers|" + BIAS):
        block.apply(_tree(flat), x)


# -- the shares ---------------------------------------------------------------

def test_eight_head_shares_sum_to_the_attention_branch():
    """Tensor parallel 8: rank r holds head r: its columns of Wq and
    Wkv_b, its rows of Wo; Wkv_a and the latent's norm whole on every
    rank. With the FFN's down projection zero a dense block returns
    x + the rank's part of o Wo; the eight parts are the uncut
    reference's branch (the latent is computed eight times and counted
    in no sum: it is an input of the parts, not a term)."""
    flat = _layer_params(WHOLE, 0)
    flat["ffn_down/kernel"] = jnp.zeros_like(flat["ffn_down/kernel"])
    x = jnp.asarray(np.random.default_rng(4).normal(size=(2, 21, 64)),
                    jnp.float32)
    whole = jnp.stack([reference.attention_branch(flat, "", seq, WHOLE)
                       for seq in x])
    dqk, dkv, dv = 16 + 8, 16 + 12, 12

    def share(r):
        mine = dict(flat)
        mine["q/kernel"] = flat["q/kernel"][:, dqk * r:dqk * (r + 1)]
        mine["kv_b/kernel"] = flat["kv_b/kernel"][:, dkv * r:dkv * (r + 1)]
        mine["o/kernel"] = flat["o/kernel"][dv * r:dv * (r + 1)]
        return _block(False, (r, r + 1), (0, 16)).apply(_tree(mine), x)[0]

    parts = sum(share(r) - x for r in range(8))
    assert float(jnp.abs(parts).max()) > 1e-3
    np.testing.assert_allclose(parts, whole, rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(
        _block(False, (0, 8), (0, 16)).apply(_tree(flat), x)[0] - x, whole,
        rtol=1e-4, atol=1e-6)


def test_sixteen_expert_shares_and_one_shared_expert_sum_to_the_layer():
    """Expert parallel 16: rank r holds expert r of 16, every rank the
    shared expert. A rank's block output is h' + shared(m) + ITS
    expert's terms, so the sixteen, less fifteen times what all compute
    alike, are the uncut reference's layer."""
    flat = _layer_params(WHOLE, 1)
    bias = reference.correction_bias(WHOLE)[f"block1/{BIAS}"]
    x = jnp.asarray(np.random.default_rng(6).normal(size=(2, 21, 64)),
                    jnp.float32)
    whole = _reference_layer(WHOLE, flat, x, True, jnp.asarray(bias))

    def share(lo, hi, zero_down=False):
        mine = dict(flat)
        for n in ("w_gate", "w_up", "w_down"):
            mine[n] = flat[n][lo:hi]
        if zero_down:
            mine["w_down"] = jnp.zeros_like(mine["w_down"])
        return _block(True, (0, 8), (lo, hi)).apply(_tree(mine, bias), x)

    alike = share(0, 1, zero_down=True)[0]
    parts = [share(e, e + 1) for e in range(16)]
    np.testing.assert_allclose(sum(p[0] for p in parts) - 15 * alike, whole,
                               rtol=1e-4, atol=1e-5)
    # every routed row is some rank's
    assert sum(int(p[1]) for p in parts) == 2 * 21 * 3
    assert float(jnp.abs(whole - alike).max()) > 1e-3


# -- the kernel path ----------------------------------------------------------

def _on_the_kernel(monkeypatch):
    """Force ``transformer.runs_kernel`` to the kernel (its test-only
    argument): here the kernels run interpreted."""
    monkeypatch.setattr(transformer, "runs_kernel", partial(
        transformer.runs_kernel, forced=True))


def test_a_block_on_the_kernel_is_the_dense_block(monkeypatch):
    """The latent core as the Pallas kernels (a query/key head of 24
    beside a value head of 12) against the same block on the dense
    [T, T] product: the block's output and every parameter's gradient;
    the dense core is computed again on the way back, the kernel is
    not."""
    flat = _layer_params(WHOLE, 0)
    x = jnp.asarray(np.random.default_rng(4).normal(size=(2, 21, 64)),
                    jnp.float32)
    block = _block(False, (0, 8), (0, 16))

    def loss(variables):
        out = block.apply(variables, x)[0]
        return jnp.sum(jnp.sin(out)), out

    def text():     # a fresh function a call: a cached trace asks nothing
        return str(jax.make_jaxpr(lambda v: loss(v)[0])(_tree(flat)))

    (_l, want), grads_want = jax.value_and_grad(loss, has_aux=True)(
        _tree(flat))
    assert "remat" in text() and "pallas_call" not in text()
    _on_the_kernel(monkeypatch)
    (_l, got), grads_got = jax.value_and_grad(loss, has_aux=True)(
        _tree(flat))
    assert "pallas_call" in text() and "remat" not in text()
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(grads_got),
                    jax.tree_util.tree_leaves(grads_want)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)
    # the count follows the program: a head computes one block of
    # 40 x 40 where the dense product has 37 x 37
    model = bench_model.model_of(TINY)
    (rows, live, dense), (_r, live_k, on_kernel) = (
        model.counts(1, SEQ), model.counts(1, SEQ, True))
    assert rows == SEQ * 2 * 3 and live_k == live == 3 * 3 * 703
    assert (dense, on_kernel) == (3 * 3 * SEQ * SEQ, 3 * 3 * 40 * 40)
    assert reference.live_score_entries(TINY, SEQ) == live


# -- the configuration --------------------------------------------------------

def _config_file():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "kanana-2-30b-ep16.json")) as f:
        return json.load(f)


def test_the_configurations_sizes_are_the_parameter_shapes():
    """``sizes`` in the configuration's file is arithmetic a reader can
    check by hand; this holds it to ``param_shapes``."""
    cfg = _config_file()
    shapes = reference.param_shapes(cfg)
    sizes = cfg["sizes"]

    def count(*parts):
        return sum(math.prod(s) for n, s in shapes.items()
                   if any(n.startswith(p) or n == p for p in parts))

    att = sizes["a_layers_attention"]
    assert att["q_2048x768"] == math.prod(shapes["block0/q/kernel"])
    assert att["kv_a_2048x576_whole"] == math.prod(
        shapes["block0/kv_a/kernel"])
    assert att["latent_norm"] == math.prod(shapes["block0/kv_norm/scale"])
    assert att["kv_b_512x1024"] == math.prod(shapes["block0/kv_b/kernel"])
    assert att["o_512x2048"] == math.prod(shapes["block0/o/kernel"])
    assert att["total"] == count("block0/q/", "block0/kv_", "block0/o/") \
        == 4_325_888
    assert sizes["dense_layer"]["total"] == count("block0/") == 42_078_720
    assert sizes["expert_layer"]["total"] == count("block1/") == 51_778_048
    assert sizes["expert_layer"]["experts_held_8x3x2048x768"] == count(
        "block1/w_") == 37_748_736
    assert sizes["embedding"] == sizes["head"] == count("embed/") \
        == count("head/") == 32_833_536
    assert sizes["parameters"] == reference.num_params(cfg) \
        == sizes["dense_layer"]["total"] \
        + 4 * sizes["expert_layer"]["total"] + 2 * sizes["embedding"] \
        + sizes["final_norm"] == 314_860_032
    assert sizes["keys"] == len(shapes) == 69
    assert sizes["trainer_state_bytes_two_trainers"] == 32 * 314_860_032
    # every key the source has is there as published, or in `reduced`
    for key, value in cfg["published"].items():
        assert (cfg[key] == value) != (key in cfg["reduced"]), key
    # the floors: four layers behind the dense one, 8 experts, an eighth
    # of the vocabulary
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] >= 4
    assert cfg["local_experts"] == [0, cfg["num_local_experts"]] == [0, 8]
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]


SCOPES = ("attention_latent", "latent_core", "router", "shared_expert",
          "dense_ffn", "dispatch", "combine")


def test_the_lowered_grad_step_carries_the_scopes_and_no_bias_leaf():
    """The benchmark's ``kanana.*`` metrics read device time by these
    named scopes; the correction bias is a constant of the program, no
    argument of it."""
    names, grad_step = bench_model.build(TINY, SEQ)
    shapes = reference.param_shapes(TINY)
    assert sorted(names) == sorted(shapes) and len(names) == 41
    assert not any(BIAS in n for n in names)
    text = jax.jit(grad_step).lower(
        [jax.ShapeDtypeStruct(shapes[n], jnp.float32) for n in names],
        jax.ShapeDtypeStruct((2, SEQ + 1), jnp.int32), None).as_text(
            debug_info=True)
    for scope in SCOPES:
        assert f"/{scope}/" in text, scope
    assert "attention_latent/latent_core/" in text
    assert text.count("tensor<16xf32>") > 0     # the bias, a constant


# -- one round through the system ---------------------------------------------

@pytest.mark.time_limit(300)
def test_two_party_round_books_the_four_counters_and_pushes_no_bias():
    names, grad_step = bench_model.build(TINY, SEQ)
    params = reference.init_params(TINY, 5)
    leaves = [np.array(x) for x in bench_model.leaves_from(params, names)]
    was_on = telemetry.enabled()
    telemetry.enable(True)
    before = telemetry.snapshot()["counters"]
    topo = InProcessHiPS(num_parties=2, workers_per_party=1).start()
    out, pushed = {}, set()
    try:
        def master_init(kv):
            for i, leaf in enumerate(leaves):
                kv.init(i, leaf)
            kv.wait()

        def worker(kv):
            w = topo.workers.index(kv)
            send = kv.push_pull_bsc_batch_async

            def recorded(keys, *a, **k):
                pushed.update(keys)
                return send(keys, *a, **k)

            kv.push_pull_bsc_batch_async = recorded
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
    flat0 = np.concatenate([l.ravel() for l in leaves])
    assert not np.array_equal(out[0][1], flat0)
    # the keys are the 41 trained leaves: 16-element biases are none
    assert pushed == set(range(41))
    assert len(flat0) == reference.num_params(TINY)

    def booked(name):
        return after[name] - before.get(name, 0)

    # 2 workers x 2 rounds x 4 sequences
    sequences = 2 * 2 * 4
    # x 37 tokens x 2 expert layers x top-3
    assert booked("moe.rows_total") == sequences * SEQ * 2 * 3
    assert 0 < booked("moe.rows_local") < booked("moe.rows_total")
    # 3 layers x 3 held heads
    assert booked("attn.score_entries_live") == sequences * 9 * 703
    assert booked("attn.score_entries_computed") == sequences * 9 * 1369
