"""Plain reference for the ``sdar`` family: one rank's share of an SDAR
decoder (JetLM/SDAR-30B-A3B-Chat, ``sdar_moe``) under the masked
block-diffusion objective it is trained with (BD3-LMs, Arriola et al.
2025, vectorised), in straightforward ``jax.numpy``, float32, matmuls
at ``highest``.

No flax, no sort, no grouped matmul, no kernel, nothing taken from the
program: the attention is the ``[2T, 2T]`` product per held query head
under the mask written out from its four rules (a slab of
``QUERY_BLOCK`` queries against ALL keys at a time, so that 8,192
positions fit), rotary positions by position id from the formula, the
experts a loop over the experts held here with a mask. The weights are
made here from the seed (:func:`init_params`) under the path names the
program's parameter tree happens to use, and handed to both sides.

The batch is ``[S, 3, T+1]`` int32 (``data/block_noise.py``; the last
column is dropped): ``x0`` the clean ids in ``[0, V-1)``, ``m`` 1 where
a position is masked, ``n`` in 1..1000 constant over each block of
``block_length`` positions (the block's masking probability is
n / 1000). V-1 is the MASK id of the vocabulary slice held here.

Equations (``n*`` RMSNorm with a learned scale, eps ``rms_norm_eps``;
hd = ``head_dim``; B = ``block_length``):
    z = [wte[x0] ; wte[where(m, V-1, x0)]]      [2T, D], clean half first
    position id of row i: i mod T;  its block b(i) = (i mod T) // B
    every layer:
      a = n1(z); q = a Wq [heads x hd]; k, v = a Wk, a Wv [kv x hd]
      q, k = q_norm(q), k_norm(k)   RMSNorm over each head's hd dims
      rotary positions on all hd dims of q and k (half-split layout),
          f_i = theta^(-2i/hd), angle = position id * f_i
      key j is live for query i when
          i clean,  j clean:   b(j) <= b(i)
          i clean,  j noised:  never
          i noised, j clean:   b(j) <  b(i)
          i noised, j noised:  b(j) == b(i)
      o_h = softmax(q_h k_{kv(h)}^T / sqrt(hd) over the live keys) v
      h' = z + o Wo;  g = n2(h')
      p = softmax(g Wr) over ALL ``num_experts``; the
          ``num_experts_per_tok`` largest; w_e = p_e / (sum of the
          chosen p)   (``norm_topk_prob``)
      z = h' + sum over the chosen e in ``local_experts`` of
          w_e * (silu(g Wg_e) * (g Wu_e)) Wd_e
    logits = norm(z[T:]) Whead      (the noised half, the rows held here)
    loss = (1 / T) sum_i m_i * (1000 / n_i) * -log softmax(logits_i)[x0_i]
           a sequence, the mean over the sequences; no auxiliary loss

The share: as ``references/mellum.py`` takes it (the configuration's
``query_heads``, ``key_value_heads``, ``local_experts`` and vocabulary
rows held here; the router keeps its published width; nothing stands in
for what other ranks would add).

``operand_dtype`` is the control of ``correct``: the same mathematics
with every operand of a matmul that the configuration runs in its
compute dtype rounded to that type first (an 8-bit float with a scale
per tensor). The router product is float32 in the configuration and
stays so in the control. ``None`` is the reference itself.
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

# what the families' references share: the norm, the gated FFN, the
# half-split rotation, the router of normalised softmax top-k
from benchmark.references.laguna import _gated, _rms_norm, _rope
from benchmark.references.mellum import router_weights
# the rounded-operand matmul of the control is the families' common one
from benchmark.references.transformer import HIGHEST, _mm, _rounded

INIT_STD = 0.02     # assumed: initializer_range
QUERY_BLOCK = 1024  # queries whose scores against all keys are held at once
NOISE_STEPS = 1000  # a block's masking probability is n / NOISE_STEPS


def _heads(cfg: dict) -> Tuple[int, int]:
    """(query heads, key/value heads) held here."""
    q, kv = cfg["query_heads"], cfg["key_value_heads"]
    return q[1] - q[0], kv[1] - kv[0]


def param_shapes(cfg: dict) -> Dict[str, Tuple[int, ...]]:
    d, hd, v = cfg["hidden_size"], cfg["head_dim"], cfg["vocab_size"]
    w = cfg["moe_intermediate_size"]
    lo, hi = cfg["local_experts"]
    heads, kv = _heads(cfg)
    shapes = {"embed/embedding": (v, d)}
    for i in range(cfg["num_hidden_layers"]):
        b = f"block{i}/"
        shapes.update({
            b + "n1/scale": (d,),
            b + "q/kernel": (d, heads * hd), b + "k/kernel": (d, kv * hd),
            b + "v/kernel": (d, kv * hd), b + "o/kernel": (heads * hd, d),
            b + "q_norm/scale": (hd,), b + "k_norm/scale": (hd,),
            b + "n2/scale": (d,),
            b + "router/kernel": (d, cfg["num_experts"]),
            b + "w_gate": (hi - lo, d, w), b + "w_up": (hi - lo, d, w),
            b + "w_down": (hi - lo, w, d)})
    shapes.update({"norm/scale": (d,), "head/kernel": (d, v)})
    return shapes


def num_params(cfg: dict) -> int:
    return sum(math.prod(shape) for shape in param_shapes(cfg).values())


def init_params(cfg: dict, seed: int) -> Dict[str, jax.Array]:
    """Every weight from the seed in ONE jitted call on the default
    device, float32: matrices, expert stacks and the embedding
    normal(0, 0.02), RMSNorm scales 1."""
    shapes = param_shapes(cfg)

    def make(key):
        return {name: (jnp.ones(shape, jnp.float32)
                       if name.endswith("/scale") else
                       INIT_STD * jax.random.normal(
                           jax.random.fold_in(key, i), shape, jnp.float32))
                for i, (name, shape) in enumerate(shapes.items())}

    # a seed may exceed 32 signed bits: fold it in two halves
    key = jax.random.fold_in(jax.random.PRNGKey(int(seed) & 0x7FFFFFFF),
                             int(seed) >> 31)
    return jax.jit(make)(key)


def rotary_table(theta: float, head_dim: int, positions):
    """(cos, sin) [len(positions), head_dim] at the given position
    ids: every dim of a head turns, pair i at theta^(-2i/head_dim)."""
    inv = jnp.asarray([float(theta) ** (-2.0 * i / head_dim)
                       for i in range(head_dim // 2)], jnp.float32)
    ang = positions.astype(jnp.float32)[:, None] * inv[None]
    ang = jnp.concatenate([ang, ang], -1)
    return jnp.cos(ang), jnp.sin(ang)


def live_keys(rows, t: int, block: int):
    """[len(rows), 2T] bool: the keys each of the query ``rows`` (indices
    into [0, 2T), the clean copy first) may see, the four rules written
    out."""
    i, j = rows[:, None], jnp.arange(2 * t)[None]
    i_clean, j_clean = i < t, j < t
    bi, bj = (i % t) // block, (j % t) // block
    return jnp.where(
        i_clean, j_clean & (bj <= bi),
        jnp.where(j_clean, bj < bi, bj == bi))


def attention(q, k, v, t: int, block: int, od=None):
    """``q`` [2T, heads, hd] on ``k``, ``v`` [2T, kv, hd], positions
    already on them: softmax(q k^T / sqrt(hd)) v over the live keys.
    The [2T, 2T] product is taken ``QUERY_BLOCK`` queries at a time
    against every key (padded to whole slabs with queries nobody reads),
    a slab computed again on the way back. Returns [2T, heads * hd]."""
    n, heads, hd = q.shape
    # held query head h reads the held key/value head h // (heads / kv)
    of = jnp.arange(heads) // (heads // k.shape[1])
    if od is not None:      # a scale per tensor, not per slab
        q, k, v = (_rounded(x, od) for x in (q, k, v))
    k, v = k[:, of], v[:, of]
    slab = min(QUERY_BLOCK, n)
    nb = -(-n // slab)
    q = jnp.pad(q, ((0, nb * slab - n), (0, 0), (0, 0)))

    @jax.checkpoint
    def some_queries(q, rows, k, v):
        # a padded query takes the last row's keys: it is sliced off
        mask = live_keys(jnp.minimum(rows, n - 1), t, block)
        s = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) \
            / jnp.sqrt(jnp.float32(hd))
        p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
        if od is not None:
            p = _rounded(p, od)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)

    o = jax.lax.map(lambda rows: some_queries(*rows, k, v), (
        q.reshape(nb, slab, heads, hd),
        jnp.arange(nb * slab).reshape(nb, slab)))
    return o.reshape(nb * slab, heads * hd)[:n]


def layer(params, b: str, z, cfg: dict, od=None):
    """A layer (parameters under the prefix ``b``) on both copies of one
    sequence, ``z`` [2T, D]."""
    hd, eps = cfg["head_dim"], cfg["rms_norm_eps"]
    n = z.shape[0]
    heads, kv = _heads(cfg)
    cos, sin = rotary_table(cfg["rope_theta"], hd, jnp.arange(n) % (n // 2))
    a = _rms_norm(z, params[b + "n1/scale"], eps)
    q = _mm("td,de->te", a, params[b + "q/kernel"], od).reshape(n, heads, hd)
    k = _mm("td,de->te", a, params[b + "k/kernel"], od).reshape(n, kv, hd)
    v = _mm("td,de->te", a, params[b + "v/kernel"], od).reshape(n, kv, hd)
    q = _rms_norm(q, params[b + "q_norm/scale"], eps)
    k = _rms_norm(k, params[b + "k_norm/scale"], eps)
    o = attention(_rope(q, cos, sin), _rope(k, cos, sin), v, n // 2,
                  cfg["block_length"], od)
    z = z + _mm("te,ed->td", o, params[b + "o/kernel"], od)
    g = _rms_norm(z, params[b + "n2/scale"], eps)
    chosen, weights = router_weights(g, params[b + "router/kernel"],
                                     cfg["num_experts_per_tok"])
    lo, hi = cfg["local_experts"]

    def add_expert(y, held):
        e, gate, up, down = held
        weight = jnp.sum(jnp.where(chosen == e, weights, 0.0), -1)
        return y + weight[:, None] * _gated(g, gate, up, down, od), None

    # a loop over the experts held here, one at a time (a scan: the
    # experts' code is compiled once, not once an expert)
    y, _ = jax.lax.scan(add_expert, jnp.zeros_like(z), (
        jnp.arange(lo, hi), params[b + "w_gate"], params[b + "w_up"],
        params[b + "w_down"]))
    return z + y


def sequence_loss(params: Dict[str, jax.Array], seq, cfg: dict,
                  operand_dtype: Optional[str] = None):
    """The loss of ONE sequence, ``seq`` [3, T+1] int32 (x0, m, n). A
    layer's intermediates are computed again on the way back
    (``jax.checkpoint`` around each layer: the same arithmetic, twice);
    the layers, all alike, run as one loop over their stacked weights (a
    scan: their code is compiled once)."""
    od = None if operand_dtype is None else jnp.dtype(operand_dtype)
    x0, m, n = seq[0, :-1], seq[1, :-1], seq[2, :-1]
    t = x0.shape[0]
    mask_id = cfg["vocab_size"] - 1
    z = params["embed/embedding"][
        jnp.concatenate([x0, jnp.where(m > 0, mask_id, x0)])]
    layers = [{name[len(f"block{l}/"):]: p for name, p in params.items()
               if name.startswith(f"block{l}/")}
              for l in range(cfg["num_hidden_layers"])]
    one = jax.checkpoint(lambda mine, z: layer(mine, "", z, cfg, od))
    z, _ = jax.lax.scan(
        lambda z, mine: (one(mine, z), None), z,
        jax.tree_util.tree_map(lambda *a: jnp.stack(a), *layers))
    z = _rms_norm(z[t:], params["norm/scale"], cfg["rms_norm_eps"])
    logp = jax.nn.log_softmax(
        _mm("td,dv->tv", z, params["head/kernel"], od), axis=-1)
    nll = -jnp.take_along_axis(logp, x0[:, None], axis=-1)[:, 0]
    weight = jnp.where(m > 0, NOISE_STEPS / n.astype(jnp.float32), 0.0)
    return jnp.sum(weight * nll) / t


def loss_and_grads(params, batch, cfg: dict,
                   operand_dtype: Optional[str] = None):
    """``batch`` [S, 3, T+1]: the mean over the sequences of each one's
    loss and gradient, a sequence at a time (every sequence has the
    same number of positions, so this is the batch's mean)."""
    grad = jax.value_and_grad(sequence_loss)

    def add(total, seq):
        return jax.tree_util.tree_map(
            jnp.add, total, grad(params, seq, cfg, operand_dtype)), None

    zero = (jnp.zeros((), jnp.float32),
            jax.tree_util.tree_map(jnp.zeros_like, params))
    total, _ = jax.lax.scan(add, zero, batch)
    return jax.tree_util.tree_map(lambda s: s / batch.shape[0], total)


def live_score_entries(cfg: dict, seq_len: int) -> int:
    """Score entries the block mask keeps, one sequence of T tokens (2T
    positions), all layers and held query heads: T(T+B)/2 for the clean
    rows and as many for the noised rows, a head and layer."""
    return _heads(cfg)[0] * cfg["num_hidden_layers"] * seq_len * (
        seq_len + cfg["block_length"])


def forward_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Operations the forward pass of THIS SHARE requires per COUNTED
    token (a token of the clean sequence: it costs two positions, its
    clean and its noised copy) at T tokens a sequence (multiply-add = 2):
        per layer   both positions through q, o over the held query
                    heads 2 * 2*D*H*hd and k, v over the held key/value
                    heads 2 * 2*D*KV*hd; the router 2*D*E; the rows a
                    position sends to the experts held here, at their
                    EXPECTED number under even routing, k * E_local / E,
                    each 3 * 2*D*W_expert
                    QK^T and PV over the LIVE score entries only, 4*hd
                    an entry: T(T+B) a head, layer and sequence
        not         the LAST layer's clean half feeds nothing (no later
                    layer reads it and no loss is taken there): its q
                    and o, router and experts and the clean rows' score
                    entries (T(T+B)/2 a head) are not required; its k
                    and v are (the noised queries read them)
        head        2*D*V over the vocabulary rows held here, ONCE: the
                    noised copy alone has logits
    The program runs the last layer's clean half like any other; what
    that costs is in the device time and not here. Under skewed routing
    the rows routed here differ from the expectation:
    ``sdar.local_row_share`` reports them. Masked score entries,
    lookups, norms, rotary positions, softmax, SiLU and the combine are
    not counted, nor anything computed a second time on the way back."""
    d, hd, v = cfg["hidden_size"], cfg["head_dim"], cfg["vocab_size"]
    lo, hi = cfg["local_experts"]
    heads, kv = _heads(cfg)
    layers = cfg["num_hidden_layers"]
    rows = cfg["num_experts_per_tok"] * (hi - lo) / cfg["num_experts"]
    q_o, k_v = 4 * d * heads * hd, 4 * d * kv * hd
    routed = 2 * d * cfg["num_experts"] \
        + rows * 6 * d * cfg["moe_intermediate_size"]
    positions = 2 * layers * (q_o + k_v + routed) - (q_o + routed)
    live = live_score_entries(cfg, seq_len) / seq_len
    last_clean = heads * (seq_len + cfg["block_length"]) / 2
    return positions + 4.0 * hd * (live - last_clean) + 2 * d * v


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward + backward (twice forward) per COUNTED token (two
    positions, see :func:`forward_flops_per_token`); nothing recomputed
    counts."""
    return 3 * forward_flops_per_token(cfg, seq_len)
