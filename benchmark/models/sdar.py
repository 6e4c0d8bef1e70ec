"""The ``sdar`` family as the SYSTEM runs it: the program's own
``geomx_tpu.models.sdar.Sdar`` (flax; a clean and a noised copy of
every sequence behind ``transformer.block_diffusion_attention``, sparse
no-drop dispatch, compute dtype from the configuration) and its
``masked_diffusion_loss``, wrapped to the leaf-list contract of
``DeviceResidentTrainer``.

``grad_step(leaves, batch, None) -> (loss, grad_leaves)`` takes the
batch of ``data/block_noise.py``, ``[S, 3, T+1]`` int32 (clean ids,
mask, noise level), and accumulates over microbatches of
``microbatch_sequences`` sequences inside the jitted program (the
program's ``accumulate_gradients``); their number follows the batch's
leading size, so the one function serves ``correct`` (a)'s two
sequences and the trainer's batch. Its ``counted`` twin also returns
the step's counts, which the trainer books as the counters
``moe.rows_local``, ``moe.rows_total``, ``attn.score_entries_live``,
``attn.score_entries_computed``, ``diffusion.positions_masked`` and
``diffusion.positions``.

The weights are not the program's: they come from the benchmark's
seeded generator (``references/sdar.init_params``) and are laid into
the program's parameter tree by path name.
"""

from __future__ import annotations

from typing import Callable, List, Tuple

import jax
import jax.numpy as jnp

from benchmark.models.transformer import _path_name, leaves_from  # noqa: F401
# at the top: a program without the family fails here, before any device
from geomx_tpu.models.sdar import Sdar, masked_diffusion_loss
from geomx_tpu.parallel.grad_accum import accumulate_gradients

COUNTERS = ("moe.rows_local", "moe.rows_total", "attn.score_entries_live",
            "attn.score_entries_computed", "diffusion.positions_masked",
            "diffusion.positions")


def model_of(cfg: dict):
    return Sdar(
        vocab=cfg["vocab_size"], dim=cfg["hidden_size"],
        head_dim=cfg["head_dim"], depth=cfg["num_hidden_layers"],
        query_heads=tuple(cfg["query_heads"]),
        key_value_heads=tuple(cfg["key_value_heads"]),
        block_length=cfg["block_length"], rope_theta=cfg["rope_theta"],
        num_experts=cfg["num_experts"],
        experts_per_token=cfg["num_experts_per_tok"],
        expert_width=cfg["moe_intermediate_size"],
        local_experts=tuple(cfg["local_experts"]),
        eps=cfg["rms_norm_eps"],
        compute_dtype=jnp.dtype(cfg["compute_dtype"]))


def build(cfg: dict, seq_len: int) -> Tuple[List[str], Callable]:
    """(leaf names in the program's flatten order, grad_step)."""
    model = model_of(cfg)
    abstract = jax.eval_shape(
        model.init, jax.random.PRNGKey(0),
        jnp.zeros((1, 2 * seq_len), jnp.int32))
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    names = [_path_name(path) for path, _ in flat]
    micro = cfg["microbatch_sequences"]

    def loss_fn(leaf_list, batch):
        return masked_diffusion_loss(
            model, jax.tree_util.tree_unflatten(treedef, leaf_list), batch)

    grad = jax.value_and_grad(loss_fn, has_aux=True)

    def counted(leaf_list, batch, _y):
        n = max(batch.shape[0] // micro, 1)
        (loss, counts), grads = accumulate_gradients(
            grad, n, has_aux=True)(leaf_list, batch)
        return loss, grads, counts

    def grad_step(leaf_list, batch, _y):
        return counted(leaf_list, batch, _y)[:2]

    grad_step.counted = (COUNTERS, counted)
    return names, grad_step
