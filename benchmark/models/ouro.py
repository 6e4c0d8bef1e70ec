"""The ``ouro`` family as the SYSTEM runs it: the program's own
``geomx_tpu.models.ouro.Ouro`` (flax; the stack's passes one ``scan``
with the parameters broadcast, every block and the exit
rematerialised, the causal core behind ``transformer.causal_core``,
compute dtype from the configuration) and its ``looped_exit_loss``,
wrapped to the leaf-list contract of ``DeviceResidentTrainer``.

``grad_step(leaves, toks, None) -> (loss, grad_leaves)`` takes
``[S, T+1]`` tokens and accumulates over microbatches of
``microbatch_sequences`` sequences inside the jitted program (the
program's ``accumulate_gradients``); their number follows the batch's
leading size, so the one function serves ``correct`` (a)'s two
sequences and the trainer's batch. Its ``counted`` twin also returns
the step's counts, which the trainer books as the counters
:func:`counters` names.

The weights are not the program's: they come from the benchmark's
seeded generator (``references/ouro.init_params``) and are laid into
the program's parameter tree by path name.
"""

from __future__ import annotations

from typing import Callable, List, Tuple

import jax
import jax.numpy as jnp

from benchmark.models.transformer import _path_name, leaves_from  # noqa: F401
# at the top: a program without the family fails here, before any device
from geomx_tpu.models.ouro import Ouro, looped_exit_loss
from geomx_tpu.parallel.grad_accum import accumulate_gradients


def counters(steps: int) -> Tuple[str, ...]:
    """The names of ``looped_exit_loss``'s counts, in its order."""
    return ("ouro.positions", "ouro.layer_applications",
            "attn.score_entries_live", "attn.score_entries_computed",
            *(f"ouro.exit_mass_t{t}" for t in range(1, steps + 1)),
            *(f"ouro.nll_sum_t{t}" for t in range(1, steps + 1)))


def model_of(cfg: dict):
    return Ouro(
        vocab=cfg["vocab_size"], dim=cfg["hidden_size"],
        depth=cfg["num_hidden_layers"], steps=cfg["total_ut_steps"],
        heads=cfg["num_attention_heads"], head_dim=cfg["head_dim"],
        width=cfg["intermediate_size"], rope_theta=cfg["rope_theta"],
        eps=cfg["rms_norm_eps"],
        compute_dtype=jnp.dtype(cfg["compute_dtype"]))


def build(cfg: dict, seq_len: int) -> Tuple[List[str], Callable]:
    """(leaf names in the program's flatten order, grad_step)."""
    model = model_of(cfg)
    abstract = jax.eval_shape(
        model.init, jax.random.PRNGKey(0),
        jnp.zeros((1, seq_len), jnp.int32))
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    names = [_path_name(path) for path, _ in flat]
    micro = cfg["microbatch_sequences"]

    def loss_fn(leaf_list, toks):
        return looped_exit_loss(
            model, jax.tree_util.tree_unflatten(treedef, leaf_list), toks)

    grad = jax.value_and_grad(loss_fn, has_aux=True)

    def counted(leaf_list, toks, _y):
        n = max(toks.shape[0] // micro, 1)
        (loss, counts), grads = accumulate_gradients(
            grad, n, has_aux=True)(leaf_list, toks)
        return loss, grads, counts

    def grad_step(leaf_list, toks, _y):
        return counted(leaf_list, toks, _y)[:2]

    grad_step.counted = (counters(cfg["total_ut_steps"]), counted)
    return names, grad_step
