"""Mixture-of-Experts FFN: a no-drop sparse dispatch, and a top-1 block
with expert parallelism over the "ep" axis.

Beyond the reference (its op set predates MoE; SURVEY.md §2.3 — the
rubric's EP axis). Expert weights are STACKED along a leading expert
dimension and sharded ``P("ep", ...)``.

:func:`sparse_dispatch` is the one dispatch. The (row, slot) pairs a
top-k router chose are ordered by expert, held experts first: index
work on ``N * k`` integers. Then only the rows held here move: the
first ``C`` pairs of that order are gathered straight from the rows
(``C`` a cap read from the shapes, :func:`dispatch_cap`: about twice
what even routing sends to the held experts, all ``N * k`` where every
expert is held), the expert FFN runs as grouped matmuls over the group
sizes (``jax.lax.ragged_dot``), and the ``C`` results, times their
gates, are added into their tokens' rows. One rank of an
expert-parallel layout that holds 8 of 512 experts gathers 1,536 rows
of 40,960, not all of them. A pass that holds more than ``C`` pairs
takes the next ``C`` of the order through the same body, and so on:
a loop of ``ceil(held pairs / C)`` tiles, one in the usual pass, so
work follows the rows routed, shapes stay static, no capacity factor
drops a token. It takes ``k`` from its inputs and the
contiguous range of experts held here as an argument: a rank computes
its own experts' terms and nothing for the others. The gates are
whatever weights the caller made of its router's scores: softmax
probabilities as they are (``models/olmoe.py``, top-8 of 64), sigmoid
scores normalised over the chosen and scaled (``models/laguna.py``,
top-8 of 256 beside a shared expert that never comes here), softmax
probabilities normalised over the chosen (``models/qwen3_next.py``,
top-10 of 512), sigmoid scores chosen by score plus a bias and weighed
by the score alone (:func:`biased_sigmoid_router`: ``models/kanana.py``,
top-6 of 128, and ``models/nemotron_h.py``, the same rule over experts
with no gate, :func:`plain_experts`), softmax
probabilities normalised over the chosen again (``models/sdar.py``,
top-8 of 128 over two copies of every sequence), one probability (:class:`MoEBlock`, the ``k = 1``
case); the dispatch multiplies and sums, it normalises nothing.

:class:`MoEBlock` router: top-1 (Switch-style) with optional jitter
noise and the standard load-balancing auxiliary loss (mean fraction x
mean gate per expert, scaled by E).
"""

from __future__ import annotations

from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from geomx_tpu.models.transformer import HIGHEST, runs_kernel

__all__ = ["MoEBlock", "moe_param_sharding", "is_expert_param",
           "sparse_dispatch", "dispatch_cap", "gated_experts",
           "plain_experts", "biased_sigmoid_router", "next_token_loss",
           "masked_diffusion_loss"]

# leaf names of expert-stacked params (leading axis = expert dim)
EXPERT_PARAM_NAMES = ("w_up", "b_up", "w_dn", "b_dn")


def is_expert_param(path: str) -> bool:
    """True when a '/'-joined param path names an expert-stacked leaf
    (the single source of truth for ep-sharding rules)."""
    return path.rsplit("/", 1)[-1] in EXPERT_PARAM_NAMES


def expert_spec(ndim: int) -> P:
    """PartitionSpec for an expert-stacked leaf: experts over "ep",
    everything else replicated."""
    return P(*(["ep"] + [None] * (ndim - 1)))


def _sum_of_tiles(tile, count, ints):
    """``sum(tile(t, *ints) for t in range(count))`` for a traced
    ``count``: a loop as long as its input asks, where ``lax.scan``
    needs a length and ``lax.cond`` a copy of the body a branch. The way
    back is the same loop over each tile's own vjp, recomputed: nothing
    is kept a tile, and what the program holds is one body forward and
    one backward. ``tile`` takes integer arrays and may close over the
    floating-point ones it reads (they are found and differentiated)."""
    like = jax.eval_shape(tile, 0, *ints)
    tile, closed = jax.closure_convert(tile, 0, *ints)

    @jax.custom_vjp
    def run(count, ints, closed):
        return jax.lax.fori_loop(
            0, count, lambda t, y: y + tile(t, *ints, *closed),
            jnp.zeros(like.shape, like.dtype))

    def back(kept, g):
        count, ints, closed = kept

        def add(t, sums):
            terms, = jax.vjp(lambda closed: tile(t, *ints, *closed),
                             closed)[1](g)
            return jax.tree_util.tree_map(jnp.add, sums, terms)

        return None, None, jax.lax.fori_loop(
            0, count, add, jax.tree_util.tree_map(jnp.zeros_like, closed))

    run.defvjp(lambda *kept: (run(*kept), kept), back)
    return run(count, ints, closed)


# the cap is a whole number of these: rows of a grouped matmul's tile
ROW_TILE = 512


def dispatch_cap(n, k, held_n, num_experts=None):
    """Rows :func:`sparse_dispatch` gathers in the usual pass, from
    shapes alone: about twice what ``held_n`` of ``num_experts`` experts
    get of ``n * k`` (row, slot) pairs when the router spreads them
    evenly, rounded up to ``ROW_TILE``, never over ``n * k``; all ``n *
    k`` where every expert is held or the router's width is not given.
    A pass that holds more pairs runs further tiles of as many: a
    caller that wants to know how often counts ``sum(group_sizes) >
    dispatch_cap(...)``."""
    pairs = n * k
    if num_experts is None or held_n >= num_experts:
        return pairs
    tiles = -(-2 * pairs * held_n // (num_experts * ROW_TILE))
    return min(pairs, max(tiles, 1) * ROW_TILE)


def sparse_dispatch(h, expert_idx, gates, expert_fn, local_experts,
                    num_experts=None):
    """No-drop top-k dispatch to a contiguous range of experts.

    ``h`` [N, D] rows, ``expert_idx`` [N, k] the experts each row chose
    (ids of the whole router), ``gates`` [N, k] their weights, as the
    caller normalised them, ``local_experts`` (lo, hi) the experts held
    here, ``num_experts`` the router's width. The (row, slot) pairs are
    ordered by expert, held experts first and in order (integers only);
    the first ``C = dispatch_cap(...)`` of that order are gathered from
    ``h``, so that ``expert_fn(rows [C, D], group_sizes [hi-lo],
    row_expert [C]) -> [C, D_out]`` sees each expert's rows contiguous
    (what ``jax.lax.ragged_dot`` wants), and their results, times their
    gates, are added into their tokens' rows. Rows past the held pairs
    are zero on the way in and on the way out and cost shape, not
    arithmetic. The body runs ``ceil(held pairs / C)`` times, tile
    after tile of the order (:func:`_sum_of_tiles`): once in the usual
    pass, not at all where no pair is held, and as often as it takes
    where the router crowds the held experts: shapes are static,
    nothing is dropped whatever the routing, and no pass builds an
    array of ``N * k`` rows unless ``C`` is that. Returns the rows'
    ``sum_slot gate * expert(row)`` over the held experts, [N, D_out],
    and the group sizes of the whole pass.
    """
    lo, hi = local_experts
    held_n = hi - lo
    n, k = expert_idx.shape
    pairs = n * k
    cap = dispatch_cap(n, k, held_n, num_experts)
    with jax.named_scope("dispatch"):
        e = expert_idx.reshape(-1) - lo
        slot = jnp.where((e >= 0) & (e < held_n), e, held_n)
        order = jnp.argsort(slot, stable=True)
        group_sizes = jnp.bincount(slot, length=held_n + 1)[
            :held_n].astype(jnp.int32)
        ends = jnp.cumsum(group_sizes)
        flat_gates = gates.reshape(-1)
    like = jax.eval_shape(
        expert_fn, jax.ShapeDtypeStruct((cap, h.shape[1]), h.dtype),
        group_sizes, jax.ShapeDtypeStruct((cap,), slot.dtype))

    def tile(t, order, slot, ends, group_sizes):
        """The pairs ``order[t * cap:(t + 1) * cap]`` through the
        experts: their gated results added into their tokens' rows of
        zeros, [N, D_out] float32."""
        start = t * cap
        with jax.named_scope("dispatch"):
            at = start + jnp.arange(cap)
            held = (at < ends[-1])[:, None]
            pair = order[jnp.minimum(at, pairs - 1)]
            token = pair // k
            sizes = jnp.clip(ends, start, start + cap) \
                - jnp.clip(ends - group_sizes, start, start + cap)
            # a select, not a product: what a grouped matmul leaves past
            # its groups need not be a number, in either direction
            rows = jnp.where(held, h[token], jnp.zeros((), h.dtype))
        out = expert_fn(rows, sizes, slot[pair])
        with jax.named_scope("combine"):
            # the gates in the rows' dtype; a token's slots are summed
            # in float32 and rounded once
            gate = flat_gates[pair][:, None].astype(out.dtype)
            terms = jnp.where(held, out, jnp.zeros((), out.dtype)).astype(
                jnp.float32) * gate.astype(jnp.float32)
            return jnp.zeros((n, like.shape[1]), jnp.float32).at[
                token].add(terms)

    ints = (order, slot, ends, group_sizes)
    if cap == pairs:
        y = tile(0, *ints)
    else:
        y = _sum_of_tiles(tile, -(-ends[-1] // cap), ints)
    return y.astype(like.dtype), group_sizes


def gated_experts(w_gate, w_up, w_down):
    """The ``expert_fn`` of :func:`sparse_dispatch` for stacks of
    SiLU-gated experts, ``w_gate`` and ``w_up`` [E, D, W], ``w_down``
    [E, W, D]: ``(silu(x Wg_e) * (x Wu_e)) Wd_e`` as three grouped
    matmuls over the rows' groups. :func:`plain_experts` is the sibling
    for experts with no gate."""
    def experts(rows, group_sizes, _row_expert):
        with jax.named_scope("expert_matmuls"):
            a = nn.silu(jax.lax.ragged_dot(rows, w_gate, group_sizes)) \
                * jax.lax.ragged_dot(rows, w_up, group_sizes)
            return jax.lax.ragged_dot(a, w_down, group_sizes)

    return experts


def plain_experts(w_up, w_down, act):
    """:func:`gated_experts`' sibling for stacks of experts with no
    gate, ``w_up`` [E, D, W], ``w_down`` [E, W, D]: ``act(x Wu_e) Wd_e``
    as two grouped matmuls over the rows' groups
    (``models/nemotron_h.py``: ``act`` the squared ReLU)."""
    def experts(rows, group_sizes, _row_expert):
        with jax.named_scope("expert_matmuls"):
            return jax.lax.ragged_dot(
                act(jax.lax.ragged_dot(rows, w_up, group_sizes)), w_down,
                group_sizes)

    return experts


def biased_sigmoid_router(module, m, num_experts: int, k: int, scale: float):
    """The ``noaux_tc`` router with one group, inside ``module``'s
    compact call (``models/kanana.py``, ``models/nemotron_h.py``): ``s
    = sigmoid(m Wr)`` over ALL experts in float32 at ``highest``
    (``module``'s parameter ``router/kernel``); the ``k`` largest of ``s
    + b``, where ``b`` is ``module``'s ``e_score_correction_bias`` in
    the collection ``buffers`` (zeros from ``init``, never a trained
    leaf); the weights ``scale * s[chosen] / (sum s[chosen] + 1e-20)``:
    the bias chooses, it does not weigh. Returns (chosen [..., k],
    weights [..., k])."""
    with jax.named_scope("router"):
        scores = nn.sigmoid(nn.Dense(
            num_experts, use_bias=False, dtype=jnp.float32,
            precision=HIGHEST, name="router")(m))
        bias = module.variable(
            "buffers", "e_score_correction_bias", jnp.zeros,
            (num_experts,), jnp.float32).value
        _, chosen = jax.lax.top_k(scores + bias, k)
        chosen_s = jnp.take_along_axis(scores, chosen, -1)
        weights = scale * chosen_s / (
            jnp.sum(chosen_s, -1, keepdims=True) + 1e-20)
    return chosen, weights


def next_token_loss(model, variables, toks):
    """``toks`` [B, T+1]: the mean next-token cross-entropy of a rank's
    share of a sparse decoder (``models/laguna.py``,
    ``models/qwen3_next.py``, ``models/mellum.py``,
    ``models/kanana.py``): ``model.apply``
    gives (logits, rows routed to the held experts), and
    ``model.counts(batch, t, kernel)`` what the pass has by its shapes.
    Returns (loss, [the rows routed here, then ``model.counts``]), the
    counts as float32. ``models/nemotron_h.py`` keeps a loss of its own
    in the same form: its head runs a block of rows at a time and never
    holds a sequence's logits."""
    logits, rows_local = model.apply(variables, toks[:, :-1])
    logp = jax.nn.log_softmax(logits)
    loss = -jnp.mean(jnp.take_along_axis(logp, toks[:, 1:, None], axis=-1))
    by_shape = model.counts(toks.shape[0], toks.shape[1] - 1,
                            runs_kernel(toks[:, :-1]))
    return loss, jnp.stack([rows_local.astype(jnp.float32),
                            *(jnp.float32(c) for c in by_shape)])


NOISE_STEPS = 1000      # a block's masking probability is n / NOISE_STEPS


def masked_diffusion_loss(model, variables, batch):
    """The masked block-diffusion loss of a rank's share of a sparse
    decoder (``models/sdar.py``). ``batch`` [B, 3, T+1] int32 (the last
    column is dropped): row 0 the clean ids ``x0``, each under
    ``model.vocab - 1``, which is the MASK id; row 1 ``m``, 1 where the
    position is masked in the noised copy; row 2 ``n`` in
    1..``NOISE_STEPS``, constant over a block: the block's masking
    probability is ``n / NOISE_STEPS``. The noise is data: nothing is
    drawn here. The model sees ``[x0 ; where(m, MASK, x0)]`` and gives
    the logits of the noised half; the loss is the cross-entropy AT the
    masked positions (no shift), each weighted by the inverse of its
    block's probability, over all positions:

        (1 / (B T)) sum m * (NOISE_STEPS / n) * -log softmax(logits)[x0]

    Returns (loss, [the rows routed here, then ``model.counts``, the
    masked positions, the positions that could bear loss]) in
    :func:`next_token_loss`'s form."""
    x0, m, n = (batch[:, i, :-1] for i in range(3))
    masked = m > 0
    ids = jnp.concatenate([x0, jnp.where(masked, model.vocab - 1, x0)], 1)
    logits, rows_local = model.apply(variables, ids)
    nll = -jnp.take_along_axis(jax.nn.log_softmax(logits), x0[..., None],
                               axis=-1)[..., 0]
    weight = masked * (NOISE_STEPS / n.astype(jnp.float32))
    loss = jnp.sum(weight * nll) / x0.size
    by_shape = model.counts(*x0.shape, runs_kernel(ids))
    return loss, jnp.stack([
        rows_local.astype(jnp.float32),
        *(jnp.float32(c) for c in by_shape),
        jnp.sum(masked).astype(jnp.float32), jnp.float32(x0.size)])


class MoEBlock(nn.Module):
    """Drop-in FFN block: LayerNorm -> top-1 MoE MLP -> residual."""

    dim: int
    num_experts: int = 4
    mlp_ratio: int = 4
    jitter: float = 0.0
    compute_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = False):
        dt = self.compute_dtype
        E, D, H = self.num_experts, self.dim, self.mlp_ratio * self.dim
        h = nn.LayerNorm(dtype=dt, name="ln")(x)

        # router (f32 for a stable softmax/argmax)
        logits = nn.Dense(E, dtype=jnp.float32, name="router")(
            h.astype(jnp.float32))
        if train and self.jitter > 0.0:
            rng = self.make_rng("router")
            logits = logits * jax.random.uniform(
                rng, logits.shape, minval=1.0 - self.jitter,
                maxval=1.0 + self.jitter)
        gates = jax.nn.softmax(logits, axis=-1)           # [B, T, E]
        expert_idx = jnp.argmax(gates, axis=-1)           # [B, T]
        onehot = jax.nn.one_hot(expert_idx, E, dtype=gates.dtype)
        gate_val = jnp.sum(gates * onehot, axis=-1)       # [B, T]

        # load-balancing aux loss (Switch Transformer eq. 4-6)
        frac_tokens = jnp.mean(onehot, axis=(0, 1))       # [E]
        frac_gates = jnp.mean(gates, axis=(0, 1))         # [E]
        self.sow("losses", "moe_aux",
                 E * jnp.sum(frac_tokens * frac_gates))

        # expert-stacked MLP params: [E, D, H] / [E, H, D] — shard the
        # leading axis over "ep" (moe_param_sharding); GSPMD partitions
        # the grouped matmuls from the shardings
        w_up = self.param("w_up", nn.initializers.lecun_normal(),
                          (E, D, H), jnp.float32).astype(dt)
        b_up = self.param("b_up", nn.initializers.zeros,
                          (E, H), jnp.float32).astype(dt)
        w_dn = self.param("w_dn", nn.initializers.lecun_normal(),
                          (E, H, D), jnp.float32).astype(dt)
        b_dn = self.param("b_dn", nn.initializers.zeros,
                          (E, D), jnp.float32).astype(dt)

        # top-1 is the k=1 case of the no-drop sparse dispatch: rows
        # sorted by expert, grouped matmuls over the group sizes, so
        # the work follows the rows routed and not E times the tokens
        def experts(rows, group_sizes, row_expert):
            he = jax.lax.ragged_dot(rows, w_up, group_sizes) \
                + b_up[row_expert]
            return jax.lax.ragged_dot(nn.gelu(he), w_dn, group_sizes) \
                + b_dn[row_expert]

        B, T, _ = h.shape
        y, _sizes = sparse_dispatch(
            h.reshape(B * T, D), expert_idx.reshape(B * T, 1),
            gate_val.reshape(B * T, 1), experts, (0, E))
        y = y.reshape(B, T, D)
        return x + y.astype(x.dtype)


def moe_param_sharding(mesh: Mesh):
    """device_put MoE params with experts over "ep" (router/norm
    replicated)."""

    def shard(params):
        def put(path_entries, leaf):
            path = "/".join(str(getattr(p, "key", p)) for p in path_entries)
            spec = expert_spec(leaf.ndim) if is_expert_param(path) else P()
            return jax.device_put(leaf, NamedSharding(mesh, spec))

        return jax.tree_util.tree_map_with_path(put, params)

    return shard
