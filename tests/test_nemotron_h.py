"""Nemotron-H through the program: the model against the benchmark's
plain float32 reference (the published first segment and each kind of
layer alone), the chunked selective scan against the token recurrence,
what a position may read (no later token in the Mamba mixer, no position
at all in attention), the ranks' shares against the uncut layer (the
shared expert counted once), the two pieces ``models/moe.py`` gained,
the configuration's arithmetic, the scopes the benchmark reads, and one
two-party HiPS round through the device-resident trainer.

Tiny widths, seeded weights, CPU. The published widths are compared on
the chip (``benchmark/tests/chip_limits.py``, PERF.md section 2).
"""

import json
import math
import os

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.models import nemotron_h as bench_model
from benchmark.references import nemotron_h as reference
from geomx_tpu import telemetry
from geomx_tpu.models import moe
from geomx_tpu.models.nemotron_h import NemotronHBlock, _filled, relu2
from geomx_tpu.models.transformer import HIGHEST
from geomx_tpu.ops.ssd import chunks_of, ssd_chunked, ssd_recurrent
from geomx_tpu.simulate import InProcessHiPS
from geomx_tpu.trainer_device import DeviceResidentTrainer

BIAS = "e_score_correction_bias"
# a rank in the middle of a layout: Mamba heads 4..7 of 16 with groups
# 2..3 of 8, query heads 4..7 of 16 with key/value head 0 of 2 (which
# serves 0..7 of them: more than are held), experts 4..7 of 16
TINY = dict(
    family="nemotron_h", compute_dtype="float32", hidden_size=48,
    hybrid_override_pattern="MEMEM*", mamba_head_dim=8, ssm_state_size=16,
    conv_kernel=4, chunk_size=16, mamba_heads=[4, 8], mamba_groups=[2, 4],
    head_dim=16, query_heads=[4, 8], key_value_heads=[0, 1],
    n_routed_experts=16, num_experts_per_tok=3, moe_intermediate_size=24,
    moe_shared_expert_intermediate_size=40, local_experts=[4, 8],
    routed_scaling_factor=2.5, layer_norm_epsilon=1e-5, vocab_size=96,
    microbatch_sequences=1, time_step_min=0.001, time_step_max=0.1,
    time_step_floor=1e-4, e_score_correction_bias={"seed": 7, "std": 0.1})
SEQ = 37        # no multiple of the chunk: the scan pads
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
    # the number ``correct`` (a) compares: the whole gradient's
    errs["*"] = math.sqrt(
        sum(float(jnp.sum((grads[n] - g) ** 2)) for n, g in grads_r.items())
        / sum(float(jnp.sum(g ** 2)) for g in grads_r.values()))
    return abs(float(loss) - float(loss_r)) / float(loss_r), errs


# bfloat16 keeps 8 bits of significand; a leaf's gradient passes a few
# matmuls with rounded operands. Measured here (the seeds above): the
# program in bfloat16 reads 0.040 at most a leaf (``D`` of the last
# Mamba layer: 4 numbers) and 0.0083 over the whole gradient, the
# same mathematics with float8_e4m3 operands 0.06 and more on every leaf
# but the first layer's ``D`` and conv bias (8 and 96 numbers that no
# rounded product lies behind but the out projection's) and 0.097
# over the whole gradient: a limit on the whole gradient between the two
# fails the next precision down, as the chip's comparison does at the
# published widths.
LEAF_TOL = 0.06
WHOLE_TOL = 0.03
KEYS = {"M": 10, "E": 6, "*": 5}


@pytest.mark.parametrize("pattern,dtype,loss_tol,leaf_tol", [
    ("MEMEM*", "float32", 1e-5, 2e-5), ("MEMEM*", "bfloat16", 1e-3, LEAF_TOL),
    ("M", "float32", 1e-5, 2e-5), ("E", "float32", 1e-5, 2e-5),
    ("*", "float32", 1e-5, 2e-5)])
def test_model_matches_the_float32_reference(pattern, dtype, loss_tol,
                                             leaf_tol):
    cfg = dict(TINY, hybrid_override_pattern=pattern, compute_dtype=dtype)
    loss_err, errs = _leaf_errors(cfg)
    assert errs.pop("*") <= min(leaf_tol, WHOLE_TOL)
    # a layer's keys, embedding, final norm, head; the bias is none
    assert len(errs) == sum(KEYS[kind] for kind in pattern) + 3
    assert not any(BIAS in n for n in errs)
    assert loss_err <= loss_tol
    over = {n: e for n, e in errs.items() if e > leaf_tol}
    assert not over, over


def test_a_filled_expert_width_changes_no_result():
    """The routed experts' width is filled with zeros to whole column
    tiles inside the step where that is an eighth more at most: the
    published 1,856 to 2,048, the tiny 24 not at all; at 480 (filled to
    512) loss and every leaf's gradient are the reference's, whose
    experts are 480 wide, and the leaves keep that width."""
    assert [_filled(w) for w in (24, 480, 1856, 2048, 2049)] == [
        0, 32, 192, 0, 0]
    cfg = dict(TINY, hybrid_override_pattern="E", moe_intermediate_size=480)
    loss_err, errs = _leaf_errors(cfg)
    assert loss_err <= 1e-5 and max(errs.values()) <= 2e-5, errs
    names, _ = bench_model.build(cfg, SEQ)
    assert reference.param_shapes(cfg)["block0/w_up"] == (4, 48, 480)
    assert "block0/w_up" in names


def test_float8_operands_fail_the_bfloat16_tolerance():
    _loss_err, errs = _leaf_errors(TINY, "float8_e4m3fn", system=False)
    assert errs.pop("*") > 2 * WHOLE_TOL
    under = {n: e for n, e in errs.items() if e <= LEAF_TOL}
    assert set(under) <= {"block0/D", "block0/conv_bias"}, under


def test_logits_match_the_float32_reference():
    params = reference.init_params(TINY, PARAM_SEED)
    toks = _tokens(TOKEN_SEED)[:, :-1]
    names, _ = bench_model.build(TINY, SEQ)
    logits, rows = bench_model.model_of(TINY).apply(
        dict(_tree({n: params[n] for n in names}),
             buffers=bench_model.buffers_of(TINY)), toks)
    want = jnp.stack([reference.sequence_logits(params, seq, TINY)
                      for seq in toks])
    np.testing.assert_allclose(logits, want, rtol=1e-4, atol=1e-5)
    assert 0 < int(rows) < 2 * SEQ * 2 * 3


# -- the scan -----------------------------------------------------------------

def _scan_inputs(t, h, g, seed=0, bt=2, p=8, n=16):
    rng = np.random.default_rng(seed)

    def normal(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    return (normal(bt, t, h, p), jax.nn.softplus(normal(bt, t, h)),
            -jnp.asarray(rng.uniform(0.5, 8, (h,)), jnp.float32),
            normal(bt, t, g, n), normal(bt, t, g, n), normal(h))


@pytest.mark.parametrize("t,chunk,h,g", [
    (64, 16, 4, 2),     # whole chunks, two heads a group
    (75, 16, 4, 1),     # padded: 75 is 4 chunks and 11 tokens
    (40, 128, 6, 6),    # shorter than one chunk, a group a head
    (37, 1, 2, 1)])     # a chunk a token: the chain alone
def test_the_chunked_scan_is_the_token_recurrence(t, chunk, h, g):
    args = _scan_inputs(t, h, g)
    want = ssd_recurrent(*args)
    got = ssd_chunked(*args, chunk=chunk)
    assert got.shape == want.shape == (2, t, h, 8)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)

    def grads(fn):
        return jax.grad(lambda *a: jnp.sum(jnp.sin(fn(*a))),
                        argnums=tuple(range(6)))(*args)

    for a, b in zip(grads(lambda *a: ssd_chunked(*a, chunk=chunk)),
                    grads(ssd_recurrent)):
        assert float(jnp.linalg.norm(a - b)) <= 1e-4 * float(
            jnp.linalg.norm(b))
    assert chunks_of(t, chunk) == -(-t // chunk)


def test_a_strong_decay_inverts_nothing():
    """dt A of -50 a token: a form that divides by a decay, or takes
    exp of a masked entry's positive exponent, reads inf or nan."""
    x, dt, a, b, c, d = _scan_inputs(48, 2, 1)
    dt = dt + 6.0
    got = ssd_chunked(x, dt, a * 8, b, c, d, chunk=16)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, ssd_recurrent(x, dt, a * 8, b, c, d),
                               rtol=1e-4, atol=1e-4)
    g = jax.grad(lambda dt: jnp.sum(ssd_chunked(
        x, dt, a * 8, b, c, d, chunk=16)))(dt)
    assert np.isfinite(g).all()


# -- one block ----------------------------------------------------------------

def _block(kind, whole=TINY, **held):
    cfg = dict(whole, **held)
    return NemotronHBlock(
        dim=cfg["hidden_size"], kind=kind,
        mamba_head_dim=cfg["mamba_head_dim"],
        state_size=cfg["ssm_state_size"], conv_kernel=cfg["conv_kernel"],
        chunk=cfg["chunk_size"], mamba_heads=tuple(cfg["mamba_heads"]),
        mamba_groups=tuple(cfg["mamba_groups"]), head_dim=cfg["head_dim"],
        query_heads=tuple(cfg["query_heads"]),
        kv_heads=tuple(cfg["key_value_heads"]),
        num_experts=cfg["n_routed_experts"],
        experts_per_token=cfg["num_experts_per_tok"],
        expert_width=cfg["moe_intermediate_size"],
        shared_width=cfg["moe_shared_expert_intermediate_size"],
        local_experts=tuple(cfg["local_experts"]),
        routed_scale=cfg["routed_scaling_factor"])


def _layer_params(cfg, kind, seed=3):
    """One layer of ``kind`` from the reference's generator, without its
    prefix."""
    params = reference.init_params(
        dict(cfg, hybrid_override_pattern=kind), seed)
    return {n[len("block0/"):]: p for n, p in params.items()
            if n.startswith("block0/")}


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


def _reference_layer(cfg, flat, x, kind, bias=None):
    return jnp.stack([reference.layer(flat, "", seq, kind, bias, cfg)
                      for seq in x])


def _x(seed, t=21):
    return jnp.asarray(np.random.default_rng(seed).normal(
        size=(2, t, TINY["hidden_size"])), jnp.float32)


def test_the_mamba_mixer_reads_no_later_token():
    """Tokens after position 9 changed: the conv (4 taps back, none
    forward) and the scan leave positions 0..9 as they were, and
    position 10 moves."""
    flat = _layer_params(TINY, "M")
    block, x = _block("M"), _x(5)
    other = x.at[:, 10:].set(_x(6)[:, 10:])
    a, b = (block.apply(_tree(flat), v)[0] for v in (x, other))
    np.testing.assert_array_equal(a[:, :10], b[:, :10])
    assert float(jnp.abs(a[:, 10] - b[:, 10]).max()) > 1e-3
    # the conv alone: output t is taps . x[t-3..t] + its bias
    from geomx_tpu.models.qwen3_next import causal_conv
    taps = flat["conv"]
    seq = jnp.asarray(np.random.default_rng(2).normal(
        size=(2, 21, taps.shape[1])), jnp.float32)
    got = causal_conv(seq, taps)
    want = sum(taps[j] * seq[:, 7 - 3 + j] for j in range(4))
    np.testing.assert_allclose(got[:, 7], want, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(got[:, 0], taps[3] * seq[:, 0], rtol=1e-6)


def test_attention_has_no_positional_term():
    """The earlier tokens in another order: a position's output is a
    sum over the SET of keys before it, so the last position reads the
    same; with any positional term it would not."""
    flat = _layer_params(TINY, "*")
    block, x = _block("*"), _x(8)
    order = np.random.default_rng(9).permutation(20)
    shuffled = jnp.concatenate([x[:, order], x[:, 20:]], axis=1)
    a, b = (block.apply(_tree(flat), v)[0] for v in (x, shuffled))
    np.testing.assert_allclose(a[:, 20], b[:, 20], rtol=1e-5, atol=1e-6)
    # and it is causal: position 5 does not read what moved behind it
    assert float(jnp.abs(a[:, 5] - b[:, 5]).max()) > 1e-4


# -- the shares ---------------------------------------------------------------

# every head and every expert on one rank
WHOLE = dict(TINY, mamba_heads=[0, 16], mamba_groups=[0, 8],
             query_heads=[0, 16], key_value_heads=[0, 2],
             local_experts=[0, 16])


def _columns(matrix, lo, hi, width):
    return matrix[..., lo * width:hi * width]


def test_eight_head_shares_sum_to_the_mamba_mixer():
    """Tensor parallel 8 over 16 heads in 8 groups: rank r holds heads
    2r, 2r + 1 and their group r: its columns of W_in (z, x, B, C), of
    W_dt and of the conv, its heads' A, D, dt_bias, its part of the
    gated norm's scale, its rows of W_out. A group's statistics are its
    own, so the eight parts ARE the uncut reference's mixer: nothing is
    approximated by the cut."""
    flat, x = _layer_params(WHOLE, "M"), _x(4)
    whole = _reference_layer(WHOLE, flat, x, "M") - x
    p, n, h, g = 8, 16, 16, 8
    inner = h * p

    def share(r):
        heads, mine = slice(2 * r, 2 * r + 2), dict(flat)
        # z | x | B | C of in_proj, and x | B | C of the conv
        z, xs, bs, cs = (flat["in_proj/kernel"][:, a:b] for a, b in (
            (0, inner), (inner, 2 * inner), (2 * inner, 2 * inner + g * n),
            (2 * inner + g * n, 2 * inner + 2 * g * n)))
        mine["in_proj/kernel"] = jnp.concatenate([
            _columns(z, 2 * r, 2 * r + 2, p),
            _columns(xs, 2 * r, 2 * r + 2, p), _columns(bs, r, r + 1, n),
            _columns(cs, r, r + 1, n)], axis=1)
        for name in ("conv", "conv_bias"):
            full = flat[name]
            mine[name] = jnp.concatenate([
                _columns(full[..., :inner], 2 * r, 2 * r + 2, p),
                _columns(full[..., inner:inner + g * n], r, r + 1, n),
                _columns(full[..., inner + g * n:], r, r + 1, n)], axis=-1)
        mine["dt_proj"] = flat["dt_proj"][heads]
        for name in ("A_log", "D", "dt_bias"):
            mine[name] = flat[name][heads]
        mine["gated_norm"] = flat["gated_norm"][2 * r * p:(2 * r + 2) * p]
        mine["out_proj/kernel"] = flat["out_proj/kernel"][
            2 * r * p:(2 * r + 2) * p]
        return _block("M", mamba_heads=[2 * r, 2 * r + 2],
                      mamba_groups=[r, r + 1]).apply(_tree(mine), x)[0]

    parts = sum(share(r) - x for r in range(8))
    assert float(jnp.abs(parts).max()) > 1e-3
    np.testing.assert_allclose(parts, whole, rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(
        _block("M", WHOLE).apply(_tree(flat), x)[0] - x, whole,
        rtol=1e-4, atol=1e-6)


def test_eight_query_head_shares_sum_to_the_attention_mixer():
    """Tensor parallel 8 over 16 query heads on 2 key/value heads: rank
    r holds queries 2r, 2r + 1 and the ONE key/value head they read
    (r // 4: four ranks hold each): its columns of W_q, that head's of
    W_k and W_v, its rows of W_o. The key/value head is computed four
    times and counted in no sum: it is an input of the parts."""
    flat, x = _layer_params(WHOLE, "*"), _x(4)
    whole = _reference_layer(WHOLE, flat, x, "*") - x
    hd = 16

    def share(r):
        mine, kv = dict(flat), r // 4
        mine["q_proj/kernel"] = _columns(flat["q_proj/kernel"], 2 * r,
                                         2 * r + 2, hd)
        for name in ("k_proj/kernel", "v_proj/kernel"):
            mine[name] = _columns(flat[name], kv, kv + 1, hd)
        mine["o_proj/kernel"] = flat["o_proj/kernel"][
            2 * r * hd:(2 * r + 2) * hd]
        return _block("*", query_heads=[2 * r, 2 * r + 2],
                      key_value_heads=[kv, kv + 1]).apply(_tree(mine), x)[0]

    parts = sum(share(r) - x for r in range(8))
    assert float(jnp.abs(parts).max()) > 1e-3
    np.testing.assert_allclose(parts, whole, rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(
        _block("*", WHOLE).apply(_tree(flat), x)[0] - x, whole,
        rtol=1e-4, atol=1e-6)


def test_sixteen_expert_shares_and_one_shared_expert_sum_to_the_layer():
    """Expert parallel 16: rank r holds expert r of 16, every rank the
    shared expert. A rank's block output is x + shared(a) + ITS expert's
    terms, so the sixteen, less fifteen times what all compute alike,
    are the uncut reference's layer."""
    flat, x = _layer_params(WHOLE, "E"), _x(6)
    bias = reference.correction_bias(
        dict(WHOLE, hybrid_override_pattern="E"))[f"block0/{BIAS}"]
    whole = _reference_layer(WHOLE, flat, x, "E", jnp.asarray(bias))

    def share(lo, hi, zero_down=False):
        mine = dict(flat)
        for name in ("w_up", "w_down"):
            mine[name] = flat[name][lo:hi]
        if zero_down:
            mine["w_down"] = jnp.zeros_like(mine["w_down"])
        return _block("E", local_experts=[lo, hi]).apply(
            _tree(mine, bias), x)

    alike = share(0, 1, zero_down=True)[0]
    parts = [share(e, e + 1) for e in range(16)]
    np.testing.assert_allclose(sum(p[0] for p in parts) - 15 * alike, whole,
                               rtol=1e-4, atol=1e-5)
    # every routed row is some rank's
    assert sum(int(p[1]) for p in parts) == 2 * 21 * 3
    assert float(jnp.abs(whole - alike).max()) > 1e-3
    # the bias chooses: without it another function
    ignored = _block("E", WHOLE).apply(
        _tree(flat, np.zeros_like(bias)), x)[0]
    assert float(jnp.abs(ignored - whole).max()) > 1e-3


def test_heads_not_shared_evenly_are_refused():
    flat, x = _layer_params(TINY, "M"), _x(1)
    with pytest.raises(ValueError, match="not shared evenly"):
        _block("M", mamba_heads=[0, 3], mamba_groups=[0, 2]).apply(
            _tree(flat), x)


# -- what models/moe.py gained ------------------------------------------------

def test_plain_experts_are_a_loop_over_experts():
    rng = np.random.default_rng(2)
    w_up = jnp.asarray(rng.normal(size=(3, 12, 20)), jnp.float32)
    w_down = jnp.asarray(rng.normal(size=(3, 20, 12)), jnp.float32)
    rows = jnp.asarray(rng.normal(size=(11, 12)), jnp.float32)
    sizes = jnp.asarray([4, 0, 5], jnp.int32)   # 2 rows past the groups
    got = moe.plain_experts(w_up, w_down, relu2)(rows, sizes, None)
    at = 0
    for e, size in enumerate([4, 0, 5]):
        mine = rows[at:at + size]
        want = jnp.square(jnp.maximum(mine @ w_up[e], 0)) @ w_down[e]
        np.testing.assert_allclose(got[at:at + size], want, rtol=1e-5,
                                   atol=1e-5)
        at += size
    # through the dispatch: the weighted sum over a row's held experts
    idx = jnp.asarray(rng.integers(0, 3, (11, 2)), jnp.int32)
    gates = jnp.asarray(rng.uniform(size=(11, 2)), jnp.float32)
    out, group_sizes = moe.sparse_dispatch(
        rows, idx, gates, moe.plain_experts(w_up, w_down, relu2), (0, 3), 3)
    want = sum(gates[:, s, None] * jnp.stack([
        jnp.square(jnp.maximum(r @ w_up[e], 0)) @ w_down[e]
        for r, e in zip(rows, idx[:, s])]) for s in range(2))
    np.testing.assert_allclose(out, want, rtol=1e-4, atol=1e-4)
    assert int(group_sizes.sum()) == 22


class _InlineRouter(nn.Module):
    """The router as ``KananaBlock.__call__`` wrote it inline until PR
    65, to the letter."""
    num_experts: int
    experts_per_token: int
    routed_scale: float

    @nn.compact
    def __call__(self, m):
        with jax.named_scope("router"):
            scores = nn.sigmoid(nn.Dense(
                self.num_experts, use_bias=False, dtype=jnp.float32,
                precision=HIGHEST, name="router")(m))
            bias = self.variable(
                "buffers", "e_score_correction_bias", jnp.zeros,
                (self.num_experts,), jnp.float32).value
            _, chosen = jax.lax.top_k(scores + bias, self.experts_per_token)
            chosen_s = jnp.take_along_axis(scores, chosen, -1)
            weights = self.routed_scale * chosen_s / (
                jnp.sum(chosen_s, -1, keepdims=True) + 1e-20)
        return chosen, weights


class _SharedRouter(nn.Module):
    num_experts: int
    experts_per_token: int
    routed_scale: float

    @nn.compact
    def __call__(self, m):
        return moe.biased_sigmoid_router(
            self, m, self.num_experts, self.experts_per_token,
            self.routed_scale)


def test_the_shared_router_is_bit_equal_to_kananas_inline_one():
    rng = np.random.default_rng(3)
    m = jnp.asarray(rng.normal(size=(2, 50, 48)), jnp.float32)
    variables = {
        "params": {"router": {"kernel": jnp.asarray(
            rng.normal(0, 0.3, (48, 16)), jnp.float32)}},
        "buffers": {BIAS: jnp.asarray(rng.normal(0, 0.1, 16), jnp.float32)}}
    inline, shared = (cls(16, 3, 2.448) for cls in (_InlineRouter,
                                                   _SharedRouter))
    # the same names from init: parameter router/kernel, buffer zeros
    made = [jax.tree_util.tree_map(
        jnp.shape, r.init(jax.random.PRNGKey(0), m)) for r in (inline, shared)]
    assert made[0] == made[1]
    for fn in (lambda r: r.apply(variables, m),
               lambda r: jax.jit(r.apply)(variables, m)):
        (c0, w0), (c1, w1) = fn(inline), fn(shared)
        np.testing.assert_array_equal(c0, c1)
        np.testing.assert_array_equal(np.asarray(w0).view(np.uint32),
                                      np.asarray(w1).view(np.uint32))
    grads = [jax.grad(lambda v, r=r: jnp.sum(jnp.sin(
        r.apply(v, m)[1])))(variables) for r in (inline, shared)]
    np.testing.assert_array_equal(
        np.asarray(grads[0]["params"]["router"]["kernel"]).view(np.uint32),
        np.asarray(grads[1]["params"]["router"]["kernel"]).view(np.uint32))


# -- the configuration --------------------------------------------------------

def _config_file():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "nemotron-3-nano-30b-ep16.json")) as f:
        return json.load(f)


def test_the_configurations_sizes_are_the_parameter_shapes():
    """``sizes`` in the configuration's file is arithmetic a reader can
    check by hand; this holds it to ``param_shapes``."""
    cfg = _config_file()
    shapes = reference.param_shapes(cfg)
    sizes = cfg["sizes"]

    def count(*parts):
        return sum(math.prod(s) for n, s in shapes.items()
                   if any(n.startswith(p) for p in parts))

    m, e, a = (sizes[k] for k in ("mamba_layer", "expert_layer",
                                  "attention_layer"))
    assert m["in_proj_2688x1280"] + m["dt_proj_8x2688"] == 2688 * 1288
    assert m["total"] == count("block0/") == count("block2/") \
        == count("block4/") == 4_845_464
    assert e["experts_held_8x2x2688x1856"] == count("block1/w_") \
        == 79_822_848
    assert e["shared_expert_2x2688x3712"] == count("block1/shared_") \
        == 19_955_712
    assert e["total"] == count("block1/") == count("block3/") \
        == 100_125_312
    assert a["total"] == count("block5/") == 3_443_328
    assert sizes["embedding"] == sizes["head"] == count("embed/") \
        == count("head/") == 44_040_192
    assert sizes["parameters"] == reference.num_params(cfg) \
        == 3 * m["total"] + 2 * e["total"] + a["total"] \
        + 2 * sizes["embedding"] + sizes["final_norm"] == 306_313_416
    assert sizes["keys"] == len(shapes) == 50
    assert sizes["trainer_state_bytes_two_trainers"] == 32 * 306_313_416
    # every key the source has is there as published, or in `reduced`
    for key, value in cfg["published"].items():
        assert (cfg[key] == value) != (key in cfg["reduced"]), key
    # the first segment of the published pattern, whole
    assert cfg["published"]["hybrid_override_pattern"].startswith(
        cfg["hybrid_override_pattern"])
    assert len(cfg["hybrid_override_pattern"]) \
        == cfg["num_hidden_layers"] == 6
    # the floors: 8 experts, an eighth of the vocabulary; whole groups
    assert cfg["local_experts"] == [0, cfg["num_local_experts"]] == [0, 8]
    assert cfg["vocab_rows"] * 8 == cfg["published"]["vocab_size"]
    assert cfg["vocab_size"] <= cfg["vocab_rows"]
    assert cfg["mamba_heads"] == [0, cfg["mamba_num_heads"]]
    assert cfg["mamba_groups"] == [0, cfg["n_groups"]]
    assert cfg["query_heads"] == [0, cfg["num_attention_heads"]]
    assert cfg["key_value_heads"] == [0, cfg["num_key_value_heads"]]


SCOPES = ("mamba_mixer", "causal_conv", "ssd_scan", "attention", "router",
          "shared_expert", "dispatch", "expert_matmuls", "combine")


def test_the_lowered_grad_step_carries_the_scopes_and_no_bias_leaf():
    """The benchmark's ``nemotron.*`` metrics read device time by these
    named scopes; the correction bias is a constant of the program, no
    argument of it."""
    names, grad_step = bench_model.build(TINY, SEQ)
    shapes = reference.param_shapes(TINY)
    assert sorted(names) == sorted(shapes) and len(names) == 50
    assert not any(BIAS in n for n in names)
    text = jax.jit(grad_step).lower(
        [jax.ShapeDtypeStruct(shapes[n], jnp.float32) for n in names],
        jax.ShapeDtypeStruct((2, SEQ + 1), jnp.int32), None).as_text(
            debug_info=True)
    for scope in SCOPES:
        assert f"/{scope}/" in text, scope
    assert "mamba_mixer/causal_conv/" in text
    assert "mamba_mixer/ssd_scan/" in text
    assert text.count("tensor<16xf32>") > 0     # the bias, a constant


# -- one round through the system ---------------------------------------------

@pytest.mark.time_limit(300)
def test_two_party_round_books_the_six_counters_and_pushes_no_bias():
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
    # the keys are the 50 trained leaves: 16-element biases are none
    assert pushed == set(range(50))
    assert len(flat0) == reference.num_params(TINY)

    def booked(name):
        return after[name] - before.get(name, 0)

    # 2 workers x 2 rounds x 4 sequences
    sequences = 2 * 2 * 4
    # x 37 tokens x 2 expert layers x top-3
    assert booked("moe.rows_total") == sequences * SEQ * 2 * 3
    assert 0 < booked("moe.rows_local") < booked("moe.rows_total")
    # 1 attention layer x 4 held query heads
    assert booked("attn.score_entries_live") == sequences * 4 * 703
    assert booked("attn.score_entries_computed") == sequences * 4 * 1369
    # 3 Mamba layers x 4 held heads; 37 tokens are 3 chunks of 16
    assert booked("ssd.head_tokens") == sequences * 3 * 4 * SEQ
    assert booked("ssd.chunks") == sequences * 3 * 3
    assert reference.live_score_entries(TINY, SEQ) == 4 * 703
