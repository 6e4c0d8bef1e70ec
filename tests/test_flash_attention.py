"""FlashAttention Pallas kernels vs the dense reference.

Runs in Pallas interpret mode on the CPU mesh (conftest pins
JAX_PLATFORMS=cpu); the same code path compiles for TPU. Checks
forward values and all three gradients against
``models.transformer.dense_attention`` (reference for the math:
FlashAttention-2; the GeoMX reference has no attention op, SURVEY §5.7).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from geomx_tpu.models.transformer import dense_attention
from geomx_tpu.ops.flash_attention import flash_attention


def _rand(shape, key, dtype=jnp.float32):
    return jax.random.normal(jax.random.PRNGKey(key), shape, dtype=dtype)


def _check(B, T, H, D, causal, block=32, dtype=jnp.float32,
           tol=2e-5):
    q = _rand((B, T, H, D), 0, dtype)
    k = _rand((B, T, H, D), 1, dtype)
    v = _rand((B, T, H, D), 2, dtype)

    def f_flash(q, k, v):
        return flash_attention(q, k, v, causal=causal, block_q=block,
                               block_k=block)

    def f_dense(q, k, v):
        return dense_attention(q, k, v, causal=causal)

    out_f = f_flash(q, k, v)
    out_d = f_dense(q, k, v)
    np.testing.assert_allclose(np.asarray(out_f, np.float32),
                               np.asarray(out_d, np.float32),
                               atol=tol, rtol=tol)

    cot = _rand(out_d.shape, 3, out_d.dtype)
    gf = jax.vjp(f_flash, q, k, v)[1](cot)
    gd = jax.vjp(f_dense, q, k, v)[1](cot)
    for name, a, b in zip("qkv", gf, gd):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            atol=10 * tol, rtol=10 * tol,
            err_msg=f"d{name} mismatch (causal={causal}, T={T})")


def test_forward_backward_causal():
    _check(B=2, T=64, H=2, D=16, causal=True)


def test_forward_backward_full():
    _check(B=2, T=64, H=2, D=16, causal=False)


def test_ragged_seq_len_pads_correctly():
    # T=50 is not a multiple of the 32-block: exercises padding+masking
    _check(B=1, T=50, H=2, D=8, causal=True)
    _check(B=1, T=50, H=2, D=8, causal=False)


def test_multi_block_causal_boundary():
    # several k-blocks per q-block, exercising the causal skip logic
    _check(B=1, T=96, H=1, D=8, causal=True, block=16)


def test_bfloat16_inputs():
    _check(B=1, T=32, H=2, D=16, causal=True, dtype=jnp.bfloat16,
           tol=2e-2)


def test_jit_and_grad_compose():
    q = _rand((1, 32, 2, 8), 0)
    k = _rand((1, 32, 2, 8), 1)
    v = _rand((1, 32, 2, 8), 2)

    @jax.jit
    def loss(q, k, v):
        return flash_attention(q, k, v, block_q=16, block_k=16).sum()

    g = jax.grad(loss)(q, k, v)
    assert g.shape == q.shape and bool(jnp.all(jnp.isfinite(g)))


def test_matches_transformer_plug_in():
    """flash_attention slots into the Transformer attn_fn hook."""
    from geomx_tpu.models.transformer import Transformer

    tok = jax.random.randint(jax.random.PRNGKey(0), (2, 24), 0, 64)
    m_dense = Transformer(vocab=64, dim=32, depth=1, heads=2, max_len=64)
    m_flash = Transformer(vocab=64, dim=32, depth=1, heads=2, max_len=64,
                          attn_fn=lambda q, k, v: flash_attention(
                              q, k, v, block_q=8, block_k=8))
    p = m_dense.init(jax.random.PRNGKey(1), tok)
    np.testing.assert_allclose(
        np.asarray(m_flash.apply(p, tok)),
        np.asarray(m_dense.apply(p, tok)), atol=1e-4, rtol=1e-4)


def test_shard_mapped_flash_on_mesh():
    """make_attention(mesh=...) runs the kernel per dp/tp shard (the
    Pallas call has no SPMD rule; shard_map supplies the partitioning)."""
    from geomx_tpu.models.transformer import make_attention
    from geomx_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(jax.devices(), tp=2, sp=1)  # dp=4 x tp=2 on 8 cpus
    attn = make_attention("flash", mesh=mesh, block_q=8, block_k=8)
    q = _rand((4, 16, 2, 8), 0)
    k = _rand((4, 16, 2, 8), 1)
    v = _rand((4, 16, 2, 8), 2)
    out = jax.jit(attn)(q, k, v)
    ref = dense_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_make_attention_rejects_sp_sharding():
    from geomx_tpu.models.transformer import make_attention
    from geomx_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(jax.devices(), tp=1, sp=2)
    with pytest.raises(ValueError, match="ring"):
        make_attention("flash", mesh=mesh)


def test_cross_attention_unequal_lengths():
    """Tq != Tk, non-causal (cross-attention)."""
    q = _rand((1, 24, 2, 8), 0)
    k = _rand((1, 40, 2, 8), 1)
    v = _rand((1, 40, 2, 8), 2)
    out = flash_attention(q, k, v, causal=False, block_q=8, block_k=8)
    # dense reference built by hand (dense_attention assumes Tq == Tk)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(8.0)
    ref = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_causal_decode_offset():
    """Causal with Tq < Tk: queries are the LAST Tq positions of the key
    sequence (kv-cache decode convention) — a single query must attend
    to the whole prefix, not just key 0."""
    Tq, Tk = 8, 32
    q = _rand((1, Tq, 1, 8), 0)
    k = _rand((1, Tk, 1, 8), 1)
    v = _rand((1, Tk, 1, 8), 2)
    out = flash_attention(q, k, v, causal=True, block_q=8, block_k=8)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(8.0)
    qpos = jnp.arange(Tq)[:, None] + (Tk - Tq)
    mask = qpos >= jnp.arange(Tk)[None, :]
    s = jnp.where(mask[None, None], s, -1e30)
    ref = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    # and the gradient path composes for the decode shape
    g = jax.grad(lambda q: flash_attention(
        q, k, v, causal=True, block_q=8, block_k=8).sum())(q)
    assert bool(jnp.all(jnp.isfinite(g)))


def test_causal_rejects_more_queries_than_keys():
    q = _rand((1, 16, 1, 8), 0)
    k = _rand((1, 12, 1, 8), 1)
    with pytest.raises(ValueError, match="Tq <= Tk"):
        flash_attention(q, k, k, causal=True, block_q=8, block_k=8)


# -- grouped queries, the blocks, the rule (PR 39) ---------------------------

# what the benchmark's cells pass a head: (name, q shape, k/v shape)
CELL_SHAPES = [
    ("olmoe", (1, 4096, 16, 128), (1, 4096, 16, 128)),
    ("laguna", (1, 4096, 1, 6, 128), (1, 4096, 1, 128)),
    ("qwen3next", (1, 4096, 1, 8, 256), (1, 4096, 1, 256)),
    ("gpt2", (8, 1023, 12, 64), (8, 1023, 12, 64)),
]


@pytest.mark.parametrize("t,block", [(512, 128), (200, 64)],
                         ids=["T512", "T200_ragged"])
@pytest.mark.parametrize("kv,group,d", [(1, 6, 128), (1, 8, 256),
                                        (1, 8, 128), (1, 6, 256),
                                        (2, 3, 16)])
def test_grouped_queries_match_grouped_attention(kv, group, d, t, block):
    """``grouped_attention``'s contract served by the kernel: query head
    h reads key/value head h // G, dK and dV summed over the group
    inside the kernel; outputs and the gradients of q, k, v."""
    from geomx_tpu.models.transformer import grouped_attention

    q = _rand((1, t, kv, group, d), 0)
    k, v = _rand((1, t, kv, d), 1), _rand((1, t, kv, d), 2)

    def kernel(q, k, v):
        return flash_attention(q, k, v, block_q=block, block_k=block)

    want, back_want = jax.vjp(grouped_attention, q, k, v)
    got, back_got = jax.vjp(kernel, q, k, v)
    assert got.shape == q.shape
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    cot = _rand(want.shape, 3)
    for name, a, b in zip("qkv", back_got(cot), back_want(cot)):
        np.testing.assert_allclose(a, b, atol=2e-4, rtol=2e-4,
                                   err_msg=f"d{name}")


def test_grouped_queries_in_the_flat_head_layout_and_unequal_blocks():
    """[B, T, KV * G, D] on [B, T, KV, D], block_q != block_k, bfloat16
    operands against the dense path with float32 scores."""
    from geomx_tpu.models.transformer import grouped_attention

    q = _rand((2, 96, 2, 2, 32), 0, jnp.bfloat16)
    k, v = (_rand((2, 96, 2, 32), i, jnp.bfloat16) for i in (1, 2))
    want = grouped_attention(q, k, v, scores_dtype=jnp.float32)
    got = flash_attention(q.reshape(2, 96, 4, 32), k, v, block_q=32,
                          block_k=64)
    np.testing.assert_allclose(
        np.asarray(got, np.float32).reshape(want.shape),
        np.asarray(want, np.float32), atol=2e-2, rtol=2e-2)
    with pytest.raises(ValueError, match="not groups"):
        flash_attention(q.reshape(2, 96, 4, 32), k[:, :, :1].repeat(3, 2),
                        v[:, :, :1].repeat(3, 2))


@pytest.mark.parametrize("t,block", [(256, 128), (200, 64)],
                         ids=["T256", "T200_ragged"])
@pytest.mark.parametrize("kv,group,d,dv", [
    (2, 1, 192, 128), (1, 2, 192, 128), (4, 1, 48, 32), (2, 3, 48, 32),
    (2, 1, 32, 48)], ids=["192_128", "192_128_grouped", "48_32",
                          "48_32_grouped", "32_48"])
def test_a_value_head_of_another_size(kv, group, d, dv, t, block):
    """A query/key head of ``d`` beside a value head of ``dv`` (latent
    attention: 192 / 128): the scale is over ``d``, the output and dV
    have ``dv`` dims; forward and the gradients of q, k, v against the
    dense product, grouped and not."""
    from geomx_tpu.models.transformer import grouped_attention

    q = _rand((1, t, kv, group, d), 0)
    k, v = _rand((1, t, kv, d), 1), _rand((1, t, kv, dv), 2)

    def kernel(q, k, v):
        return flash_attention(q, k, v, block_q=block, block_k=block)

    want, back_want = jax.vjp(grouped_attention, q, k, v)
    got, back_got = jax.vjp(kernel, q, k, v)
    assert got.shape == want.shape == (1, t, kv, group, dv)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    cot = _rand(want.shape, 3)
    grads = back_got(cot)
    assert [g.shape for g in grads] == [q.shape, k.shape, v.shape]
    for name, a, b in zip("qkv", grads, back_want(cot)):
        np.testing.assert_allclose(a, b, atol=2e-4, rtol=2e-4,
                                   err_msg=f"d{name}")


def test_a_value_head_of_another_size_in_the_flat_layout_and_bfloat16():
    """[B, T, H, 192] on k [B, T, H, 192] and v [B, T, H, 128], the
    blocks ``attention_blocks`` gives a head of 192, bfloat16 operands
    against the dense product with float32 scores; a key head that
    differs from the query head is refused."""
    from geomx_tpu.ops.flash_attention import attention_blocks

    q, k = (_rand((1, 72, 2, 192), i, jnp.bfloat16) for i in (0, 1))
    v = _rand((1, 72, 2, 128), 2, jnp.bfloat16)
    want = dense_attention(q, k, v, scores_dtype=jnp.float32)
    got = flash_attention(q, k, v)
    assert got.shape == (1, 72, 2, 128) and got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=2e-2, rtol=2e-2)
    assert attention_blocks(8192, 192) == (512, 512)
    with pytest.raises(ValueError, match="not groups"):
        flash_attention(q, k[..., :128], v)


@pytest.mark.parametrize("name,q_shape,_kv", CELL_SHAPES,
                         ids=[s[0] for s in CELL_SHAPES])
def test_the_blocks_of_the_cells_shapes(name, q_shape, _kv):
    from geomx_tpu.ops.flash_attention import attention_blocks

    want = {"olmoe": (512, 1024), "laguna": (512, 1024),
            "qwen3next": (512, 512), "gpt2": (512, 1024)}[name]
    assert attention_blocks(q_shape[1], q_shape[-1]) == want
    # a sequence shorter than a block is one block, whole sublanes
    assert attention_blocks(37, q_shape[-1]) == (40, 40)


@pytest.mark.parametrize("name,q_shape,_kv", CELL_SHAPES,
                         ids=[s[0] for s in CELL_SHAPES])
def test_the_rule_by_backend_length_and_mesh(name, q_shape, _kv,
                                             monkeypatch):
    """``runs_kernel``: dense wherever Pallas is interpreted (this
    backend); where it compiles, the kernel at the 4,096-token shapes
    and dense at GPT-2's length; dense again under a mesh, as a context
    or as the operand's own sharding."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    import geomx_tpu.ops
    from geomx_tpu.models.transformer import KERNEL_MIN_T, runs_kernel

    def answer(x, under=lambda f: f):
        """The rule's answer while ``x`` is traced (a fresh function a
        call: a trace that is cached asks nothing)."""
        out = []

        def ask(q):
            out.append(runs_kernel(q))
            return q

        jax.jit(under(ask)).lower(x)
        return out[0]

    q = jax.ShapeDtypeStruct(q_shape, jnp.bfloat16)
    assert not answer(q)                # a CPU backend
    monkeypatch.setattr(geomx_tpu.ops, "pallas_interpret", lambda: False)
    assert answer(q) == (q_shape[1] >= KERNEL_MIN_T) == (name != "gpt2")
    assert runs_kernel(q, forced=True) and not runs_kernel(q, forced=False)
    mesh = jax.make_mesh((2, 2), ("dp", "tp"))
    with jax.set_mesh(mesh):
        assert not answer(q)
    sharded = jax.ShapeDtypeStruct(
        (2,) + q_shape[1:], jnp.bfloat16,
        sharding=NamedSharding(mesh, P("dp")))
    assert not answer(sharded)
    assert not answer(sharded, lambda f: jax.shard_map(
        f, mesh=mesh, in_specs=P("dp"), out_specs=P("dp")))


@pytest.mark.parametrize("t,block_q,block_k", [
    (4096, 512, 1024), (4096, 512, 512), (4096, 128, 128), (1023, 512, 1024),
    (520, 512, 256), (200, 64, 32), (37, 40, 40)])
def test_computed_entries_are_the_live_blocks(t, block_q, block_k):
    """The count the models book against a brute count: the blocks of
    the padded [T, T] square that hold an entry the causal mask keeps."""
    from geomx_tpu.ops.flash_attention import live_blocks

    nq, nk = -(-t // block_q), -(-t // block_k)
    mask = np.zeros((nq * block_q, nk * block_k), bool)
    mask[:t, :t] = np.tril(np.ones((t, t), bool))
    brute = mask.reshape(nq, block_q, nk, block_k).any(axis=(1, 3)).sum()
    assert live_blocks(t, block_q, block_k) == brute


def test_kernel_score_entries_at_the_cells_shapes():
    from geomx_tpu.models.transformer import (kernel_score_entries,
                                              kernel_window_score_entries,
                                              score_entries)
    from geomx_tpu.ops.flash_attention import attention_blocks

    # the sliding layers run 512 x 512 whatever the head: Mellum2's 45
    # live tiles of 256 (window 1,024 at 8,192), Laguna's 15 of 64
    # (512 at 4,096)
    assert attention_blocks(8192, 128, 1024) == (512, 512)
    assert attention_blocks(4096, 128, 512) == (512, 512)
    assert attention_blocks(37, 128, 8) == (40, 40)
    assert kernel_window_score_entries(8192, 1024, 128) == 45 * 512 * 512
    assert kernel_window_score_entries(4096, 512, 128) == 15 * 512 * 512
    for t, window in ((8192, 1024), (4096, 512)):
        live, blocked = score_entries(t, window)
        assert live < kernel_window_score_entries(t, window, 128) < blocked

    # 20 live tiles of 32 at 512 x 1,024; 36 of 64 at 512 x 512
    assert kernel_score_entries(4096, 128) == 20 * 512 * 1024 == 10_485_760
    assert kernel_score_entries(4096, 256) == 36 * 512 * 512 == 9_437_184
    live, dense = score_entries(4096)
    assert live < kernel_score_entries(4096, 128) < dense
    # latent attention at 8,192: a query/key head of 192 runs 512 x 512,
    # 136 live tiles of 256
    assert kernel_score_entries(8192, 192) == 136 * 512 * 512 == 35_651_584


# -- the block mask of block-diffusion training -------------------------------

# (t, block, block_q, block_k): tiles that straddle the two copies, a
# short last block, a block no power of two, one tile for everything,
# and the cell's own shape
BLOCK_MASK_SHAPES = [
    (16, 4, 8, 8), (16, 4, 8, 16), (24, 4, 16, 8), (20, 4, 8, 16),
    (36, 4, 16, 32), (18, 4, 8, 8), (30, 3, 8, 16), (8, 4, 16, 16),
    (100, 4, 32, 64), (64, 16, 8, 8), (64, 2, 8, 24), (4096, 4, 512, 1024)]


@pytest.mark.parametrize("t,block,block_q,block_k", BLOCK_MASK_SHAPES)
def test_the_block_rule_against_the_mask_written_out(t, block, block_q,
                                                     block_k):
    """The kernels' five questions about the block mask (``_block_rule``
    over numpy) against the [2T, 2T] mask itself: the elementwise mask;
    a tile is live where it holds a kept entry and whole where every
    entry of its real rows is kept; a sweep fetches a live tile as
    itself and, at a dead one, the live tile it has in hand (the last
    before it, else the first after)."""
    from geomx_tpu.models.transformer import block_diffusion_mask
    from geomx_tpu.ops.flash_attention import (_block_rule, _round_up,
                                               block_mask_live_blocks)

    n = 2 * t
    block_q, block_k = (min(b, _round_up(n, 8)) for b in (block_q, block_k))
    nq, nk = -(-n // block_q), -(-n // block_k)
    mask, live, whole, k_seen, q_seen = _block_rule(
        np, t, block, block_q, block_k)
    want = np.zeros((nq * block_q, nk * block_k), bool)
    want[:n, :n] = block_diffusion_mask(t, block)
    rows, cols = (np.arange(m * b, dtype=np.int32)
                  for m, b in ((nq, block_q), (nk, block_k)))
    np.testing.assert_array_equal(mask(rows[:n, None], cols[None]), want[:n])
    tiles = want.reshape(nq, block_q, nk, block_k)
    qi, kj = np.meshgrid(np.arange(nq), np.arange(nk), indexing="ij")
    alive = tiles.any(axis=(1, 3))
    np.testing.assert_array_equal(live(qi, kj), alive)
    assert block_mask_live_blocks(t, block, block_q, block_k) == alive.sum()
    real = np.arange(nq * block_q).reshape(nq, block_q) < n
    np.testing.assert_array_equal(
        whole(qi, kj), (tiles | ~real[:, :, None, None]).all(axis=(1, 3)))

    def in_hand(alive_along):
        """For each index of a sweep: itself where live, else the last
        live one before it, else the first live one."""
        at = np.where(alive_along, np.arange(len(alive_along)), -1)
        last = np.maximum.accumulate(at)
        return np.where(last >= 0, last, np.argmax(alive_along))

    np.testing.assert_array_equal(
        k_seen(qi, kj), np.stack([in_hand(row) for row in alive]))
    np.testing.assert_array_equal(
        q_seen(qi, kj), np.stack([in_hand(col) for col in alive.T]).T)


def test_the_block_mask_at_the_cells_shape():
    from geomx_tpu.models.transformer import (block_score_entries,
                                              kernel_block_score_entries)

    # 20 + 20 + 8 live tiles of 512 x 1,024 over [8,192, 8,192]
    assert kernel_block_score_entries(4096, 4, 128) == 48 * 512 * 1024
    live, dense = block_score_entries(4096, 4)
    assert live < kernel_block_score_entries(4096, 4, 128) < dense


@pytest.mark.parametrize("t,block,block_q,block_k", [
    (16, 4, 8, 8), (36, 4, 16, 32), (20, 4, 16, 8), (30, 3, 8, 16)])
def test_block_mask_kernels_match_the_dense_product(t, block, block_q,
                                                    block_k):
    """Forward, dQ, dK and dV of the kernels under the block mask
    (interpreted) against the dense product under the mask written out:
    grouped queries, tiles that straddle the boundary between the
    copies, T no multiple of a tile."""
    from geomx_tpu.models.transformer import block_diffusion_attention

    q = _rand((2, 2 * t, 2, 2, 16), 0)
    k, v = _rand((2, 2 * t, 2, 16), 1), _rand((2, 2 * t, 2, 16), 2)
    cot = _rand(q.shape, 3)

    def kernel(q, k, v):
        return flash_attention(q, k, v, block_mask=(t, block),
                               block_q=block_q, block_k=block_k)

    out, back = jax.vjp(kernel, q, k, v)
    want, want_back = jax.vjp(
        lambda q, k, v: block_diffusion_attention(q, k, v, block), q, k, v)
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)
    for name, a, b in zip("qkv", back(cot), want_back(cot)):
        np.testing.assert_allclose(a, b, atol=2e-4, rtol=2e-4,
                                   err_msg=f"d{name}")
    with pytest.raises(ValueError, match="block mask"):
        flash_attention(q[:, :-2], k, v, block_mask=(t, block))


@pytest.mark.parametrize("t", [24, 36])
def test_the_kernel_form_of_block_diffusion_attention(t, monkeypatch):
    """``block_diffusion_attention`` in the form the rule gives on a TPU
    (forced here, interpreted) against its dense form, two lengths, all
    gradients; the kernel form is not rematerialised."""
    from functools import partial

    from geomx_tpu.models import transformer

    q = _rand((1, 2 * t, 1, 4, 16), 4)
    k, v = _rand((1, 2 * t, 1, 16), 5), _rand((1, 2 * t, 1, 16), 6)
    cot = _rand(q.shape, 7)
    want, want_back = jax.vjp(
        lambda q, k, v: transformer.block_diffusion_attention(q, k, v, 4),
        q, k, v)
    monkeypatch.setattr(transformer, "runs_kernel",
                        partial(transformer.runs_kernel, forced=True))
    out, back = jax.vjp(
        lambda q, k, v: transformer.block_diffusion_attention(q, k, v, 4),
        q, k, v)
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)
    for name, a, b in zip("qkv", back(cot), want_back(cot)):
        np.testing.assert_allclose(a, b, atol=2e-4, rtol=2e-4,
                                   err_msg=f"d{name}")
    text = str(jax.make_jaxpr(
        lambda q: transformer.block_diffusion_attention(q, k, v, 4))(q))
    assert "pallas_call" in text and "remat" not in text


# -- the sliding window -------------------------------------------------------

# (t, window, block_q, block_k, group): T no multiple of the tiles, a
# window over the whole sequence, one smaller than a tile, one no
# multiple of a tile, unequal tiles both ways, eight queries on one
# key/value head, one tile for everything
WINDOW_SHAPES = [
    (64, 16, 16, 16, 2), (70, 16, 16, 32, 2), (70, 24, 32, 16, 1),
    (50, 100, 16, 16, 2), (50, 50, 16, 8, 1), (50, 5, 16, 16, 8),
    (37, 11, 8, 16, 2), (100, 33, 32, 16, 2), (100, 33, 16, 32, 8),
    (64, 1, 16, 16, 1), (40, 16, 64, 64, 2)]


def _window_mask(t, window):
    at = np.arange(t, dtype=np.int32)
    behind = at[:, None] - at[None]
    return (behind >= 0) & (behind < window)


@pytest.mark.parametrize("t,window,block_q,block_k,group", WINDOW_SHAPES)
def test_window_kernels_match_the_masked_product(t, window, block_q,
                                                 block_k, group):
    """Forward, dQ, dK and dV of the kernels under the window rule
    (interpreted) against the dense product under the mask written out,
    and the blocked product ``window_attention`` against the same."""
    from geomx_tpu.models.transformer import window_attention

    q = _rand((1, t, 1, group, 16), 0)
    k, v = _rand((1, t, 1, 16), 1), _rand((1, t, 1, 16), 2)
    cot = _rand(q.shape, 3)

    def masked(q, k, v):
        s = jnp.einsum("bqkgd,bjkd->bkgqj", q, k) / 4.0
        p = jax.nn.softmax(jnp.where(_window_mask(t, window), s, -1e30), -1)
        return jnp.einsum("bkgqj,bjkd->bqkgd", p, v)

    def kernel(q, k, v):
        return flash_attention(q, k, v, window=window, block_q=block_q,
                               block_k=block_k)

    out, back = jax.vjp(kernel, q, k, v)
    want, want_back = jax.vjp(masked, q, k, v)
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)
    for name, a, b in zip("qkv", back(cot), want_back(cot)):
        np.testing.assert_allclose(a, b, atol=2e-4, rtol=2e-4,
                                   err_msg=f"d{name}")
    np.testing.assert_allclose(
        window_attention(q, k, v, window, scores_dtype=jnp.float32), want,
        atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("t,window,block_q,block_k", [
    s[:4] for s in WINDOW_SHAPES] + [
    (8192, 1024, 512, 1024), (8192, 1024, 512, 512), (4096, 512, 256, 256),
    (4096, 512, 1024, 512), (1000, 300, 128, 256)])
def test_the_window_rule_against_the_mask_written_out(t, window, block_q,
                                                      block_k):
    """The kernels' questions about the window (``_window_rule`` over
    numpy) against the [T, T] mask itself: the elementwise mask; a tile
    is live where it holds a kept entry and whole where every entry of
    its real rows is kept; a block's band is the run of its live tiles,
    its sweep as long as the longest; ``window_live_blocks`` counts the
    live tiles."""
    from geomx_tpu.ops.flash_attention import (_round_up, _window_rule,
                                               _window_sweeps,
                                               window_live_blocks)

    block_q, block_k = (min(b, _round_up(t, 8)) for b in (block_q, block_k))
    nq, nk = -(-t // block_q), -(-t // block_k)
    mask, live, whole, k_band, q_band = _window_rule(
        np, t, window, block_q, block_k)
    want = np.zeros((nq * block_q, nk * block_k), bool)
    want[:t, :t] = _window_mask(t, window)
    rows, cols = (np.arange(n * b, dtype=np.int32)
                  for n, b in ((nq, block_q), (nk, block_k)))
    np.testing.assert_array_equal(mask(rows[:t, None], cols[None]), want[:t])
    tiles = want.reshape(nq, block_q, nk, block_k)
    # a sweep's last steps may stand one past the sequence: dead
    qi, kj = np.meshgrid(np.arange(nq + 1), np.arange(nk + 1), indexing="ij")
    alive = np.zeros((nq + 1, nk + 1), bool)
    alive[:nq, :nk] = tiles.any(axis=(1, 3))
    np.testing.assert_array_equal(live(qi, kj), alive)
    assert window_live_blocks(t, window, block_q, block_k) == alive.sum()
    real = np.arange(nq * block_q).reshape(nq, block_q) < t
    np.testing.assert_array_equal(
        whole(qi[:nq, :nk], kj[:nq, :nk]),
        (tiles | ~real[:, :, None, None]).all(axis=(1, 3)))
    alive = alive[:nq, :nk]
    for band, along in ((k_band(np.arange(nq)), alive),
                        (q_band(np.arange(nk)), alive.T)):
        first, last = band
        np.testing.assert_array_equal(first, along.argmax(axis=1))
        np.testing.assert_array_equal(last - first + 1, along.sum(axis=1))
    assert _window_sweeps(t, window, block_q, block_k) == (
        alive.sum(axis=1).max(), alive.sum(axis=0).max())


def test_a_window_is_over_as_many_queries_as_keys():
    q = _rand((1, 16, 2, 8), 0)
    k = v = _rand((1, 24, 2, 8), 1)
    with pytest.raises(ValueError, match="as many queries as keys"):
        flash_attention(q, k, v, window=4)
    with pytest.raises(ValueError, match="as many queries as keys"):
        flash_attention(k, k, v, window=0)


@pytest.mark.parametrize("rule,forward_hash,text_hash", [
    (dict(causal=True), "97384fd3caa4cef7", "14f153684dcc719f"),
    (dict(block_mask=(20, 4)), "ef7074b924705d60", "d37d27452274a20d"),
    (dict(causal=False), "59cc76c6c1cb9b2d", "10d56db61ccc035d")],
    ids=["causal", "block_mask", "none"])
def test_the_other_rules_lower_to_the_text_they_had(rule, forward_hash,
                                                    text_hash):
    """The window is a third branch beside rules that do not change:
    a causal, a block-mask and an unmasked call (interpreted) lower to
    the text they had. The forward's is the text from before the window
    rule came (PR 50; equal on PR 64's parent and on its tree: that PR
    left the forward kernel alone); forward and backward together lower
    to PR 64's text, whose backward is one kernel where there were two
    (the hashes are of the text under this JAX)."""
    import hashlib
    from functools import partial

    q = jax.ShapeDtypeStruct((1, 40, 1, 2, 16), jnp.float32)
    k = v = jax.ShapeDtypeStruct((1, 40, 1, 16), jnp.float32)
    attend = partial(flash_attention, block_q=16, block_k=8, **rule)

    def both(q, k, v):
        out, back = jax.vjp(attend, q, k, v)
        return out, back(out)

    for f, want in ((attend, forward_hash), (both, text_hash)):
        text = jax.jit(f).lower(q, k, v).as_text()
        assert hashlib.sha256(text.encode()).hexdigest()[:16] == want


@pytest.mark.parametrize("case", ["cpu", "tpu", "forced", "short", "mesh",
                                  "narrow"])
def test_the_form_of_a_window_core(case, monkeypatch):
    """``window_core`` asks THE rule: the blocked product, computed
    again on the way back, wherever Pallas is interpreted, under a mesh,
    under ``KERNEL_MIN_T`` and under ``KERNEL_MIN_WINDOW``; the kernels
    with their window rule, not rematerialised, where the rule says they
    run."""
    from functools import partial

    import geomx_tpu.ops
    from geomx_tpu.models import transformer

    t = 64 if case == "short" else transformer.KERNEL_MIN_T
    window = transformer.KERNEL_MIN_WINDOW - (
        8 if case in ("narrow", "forced") else 0)
    q = jax.ShapeDtypeStruct((1, t, 1, 2, 8), jnp.float32)
    k = v = jax.ShapeDtypeStruct((1, t, 1, 8), jnp.float32)
    inv_freq, factor = transformer.rotary_frequencies(
        {"rope_type": "default", "rope_theta": 10000.0}, 8)
    if case == "forced":
        monkeypatch.setattr(transformer, "runs_kernel",
                            partial(transformer.runs_kernel, forced=True))
    elif case != "cpu":
        # the rule's answer on a TPU backend; a jaxpr lowers nothing
        monkeypatch.setattr(geomx_tpu.ops, "pallas_interpret", lambda: False)

    def text():
        return str(jax.make_jaxpr(
            lambda q, k, v: transformer.rotary_attention(
                q, k, v, inv_freq, factor, window=window))(q, k, v))

    if case == "mesh":
        with jax.set_mesh(jax.make_mesh((2, 2), ("dp", "tp"))):
            got = text()
    else:
        got = text()
    kernel = case in ("tpu", "forced")
    assert ("pallas_call" in got) == kernel
    assert ("remat" in got or "checkpoint" in got) == (not kernel)


def _masked_reference(q, k, v, mask):
    """Attention of flat-head ``q`` [B, Tq, KV * G, D] on ``k``, ``v``
    [B, Tk, KV, .] under ``mask`` [Tq, Tk] written out: the dense
    product, float32, every key/value head repeated for its group."""
    group = q.shape[2] // k.shape[2]
    k, v = (jnp.repeat(x, group, axis=2) for x in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(
        jnp.float32(q.shape[-1]))
    s = jnp.where(jnp.asarray(mask)[None, None], s, -1e30)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)


def _rule_mask(tq, tk, rule):
    """[tq, tk] of the mask ``rule`` (``flash_attention``'s keywords)
    describes."""
    from geomx_tpu.models.transformer import block_diffusion_mask

    qpos, kpos = np.arange(tq)[:, None] + (tk - tq), np.arange(tk)[None]
    if "block_mask" in rule:
        return np.asarray(block_diffusion_mask(*rule["block_mask"], np))
    if "window" in rule:
        return (qpos >= kpos) & (qpos - kpos < rule["window"])
    return (qpos >= kpos) if rule["causal"] else np.ones((tq, tk), bool)


def _count_eqns(jaxpr, primitive):
    """Equations of ``primitive`` in ``jaxpr`` and the jaxprs its
    equations carry (a ``jit``'s, a ``custom_vjp``'s), the kernel
    bodies under a ``pallas_call`` left out."""
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == primitive:
            n += 1
            continue
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    n += _count_eqns(sub, primitive)
    return n


def _gradients_against_the_mask_written_out(tq, tk, kv, group, d, dv, rule,
                                            block_q, block_k):
    """The backward of ``flash_attention`` as (pallas_call equations of
    forward + backward, the three cotangents, the reference's)."""
    q = _rand((1, tq, kv * group, d), 0)
    k, v = _rand((1, tk, kv, d), 1), _rand((1, tk, kv, dv), 2)
    cot = _rand((1, tq, kv * group, dv), 3)

    def loss(attend):
        return lambda q, k, v: (attend(q, k, v) * cot).sum()

    def kernel(q, k, v):
        return flash_attention(q, k, v, block_q=block_q, block_k=block_k,
                               **rule)

    def reference(q, k, v):
        return _masked_reference(q, k, v, _rule_mask(tq, tk, rule))

    grad = jax.grad(loss(kernel), argnums=(0, 1, 2))
    calls = _count_eqns(jax.make_jaxpr(grad)(q, k, v).jaxpr, "pallas_call")
    return calls, grad(q, k, v), jax.grad(
        loss(reference), argnums=(0, 1, 2))(q, k, v)


# name: (Tq, Tk, KV, G, D, Dv, the rule, block_q, block_k): the rules
# and the shapes the cells call the kernels with, small
ONE_BACKWARD_CASES = {
    "causal": (96, 96, 2, 1, 16, 16, dict(causal=True), 32, 32),
    "causal_fewer_queries": (32, 96, 2, 1, 16, 16, dict(causal=True), 16, 32),
    "grouped": (96, 96, 1, 3, 16, 16, dict(causal=True), 32, 64),
    "value_head_of_its_own": (64, 64, 2, 2, 48, 32, dict(causal=True),
                              32, 32),
    "block_mask": (80, 80, 1, 2, 16, 16, dict(block_mask=(40, 4)), 16, 32),
    "window": (96, 96, 1, 2, 16, 16, dict(window=24), 16, 16),
    "pads": (100, 100, 2, 1, 16, 16, dict(causal=True), 32, 64),
    "unmasked": (48, 80, 1, 2, 16, 16, dict(causal=False), 16, 16),
}


@pytest.mark.parametrize("case", list(ONE_BACKWARD_CASES))
def test_one_backward_kernel_gives_the_three_cotangents(case):
    """The backward is ONE kernel: the gradient of ``flash_attention``
    holds exactly two ``pallas_call`` equations (forward, backward)
    under every rule, and dQ, dK, dV are the dense product's."""
    calls, got, want = _gradients_against_the_mask_written_out(
        *ONE_BACKWARD_CASES[case])
    assert calls == 2
    for name, a, b in zip("qkv", got, want):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, atol=2e-4, rtol=2e-4,
                                   err_msg=f"d{name}")


# the tile counts of the largest cells' cores (T 8,192: 16 q-blocks on
# 8 k-blocks of 1,024 at heads of 128, on 16 of 512 at heads of 192)
# with small tiles and heads: what the interpreter can walk
RESIDENT_BLOCK_CASES = {
    "mellum_full_16x8_grouped": (128, 128, 1, 4, 8, 8, dict(causal=True),
                                 8, 16),
    "kanana_16x16_value_head": (128, 128, 2, 1, 24, 16, dict(causal=True),
                                8, 8),
    "sdar_16x8_block_mask": (128, 128, 1, 4, 8, 8, dict(block_mask=(64, 4)),
                             8, 16),
    "mellum_window_16x16": (128, 128, 1, 4, 8, 8, dict(window=16), 8, 8),
}


@pytest.mark.parametrize("case", list(RESIDENT_BLOCK_CASES))
def test_every_tile_of_the_resident_cotangents(case):
    """dQ's block is a head's whole sequence, updated in place a
    q-block at a time (dK and dV a k-block at a time): at the largest
    cells' tile counts every q-block of dQ and every k-block of dK and
    dV is the reference's, the first and the last by name, so an
    off-by-one in the in-place update has no single-tile test to hide
    in."""
    tq, tk, _kv, _g, _d, _dv, _rule, block_q, block_k = shape = \
        RESIDENT_BLOCK_CASES[case]
    assert tq // block_q == 16 and tk // block_k in (8, 16)
    calls, got, want = _gradients_against_the_mask_written_out(*shape)
    assert calls == 2
    for name, a, b, block in zip("qkv", got, want,
                                 (block_q, block_k, block_k)):
        for tile, rows in (("first", slice(0, block)),
                           ("last", slice(-block, None)),
                           ("every", slice(None))):
            np.testing.assert_allclose(
                a[:, rows], b[:, rows], atol=2e-4, rtol=2e-4,
                err_msg=f"d{name}, {tile} tile")
        assert float(jnp.abs(b[:, :block]).max()) > 0
        assert float(jnp.abs(b[:, -block:]).max()) > 0
