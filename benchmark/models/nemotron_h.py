"""The ``nemotron_h`` family as the SYSTEM runs it: the program's own
``geomx_tpu.models.nemotron_h.NemotronH`` (flax; the chunked selective
scan of ``geomx_tpu/ops/ssd.py`` in its Mamba-2 layers, ungated
squared-ReLU experts behind ``moe.sparse_dispatch``, grouped-query
attention with no positional term, compute dtype from the
configuration) and its ``next_token_loss``, wrapped to the leaf-list
contract of ``DeviceResidentTrainer``.

``grad_step(leaves, tokens, None) -> (loss, grad_leaves)`` accumulates
over microbatches of ``microbatch_sequences`` sequences inside the
jitted program (the program's ``accumulate_gradients``); their number
follows the batch's leading size, so the one function serves
``correct`` (a)'s two sequences and the trainer's batch. Its
``counted`` twin also returns the step's counts, which the trainer
books as the counters named in ``COUNTERS``.

The weights are not the program's: they come from the benchmark's
seeded generator (``references/nemotron_h.init_params``) and are laid
into the program's parameter tree by path name. The leaves are the
model's ``params`` alone. The router's correction bias is the model's
``buffers`` collection, a constant of the configuration
(``references/nemotron_h.correction_bias``) closed over by
``grad_step``: no leaf, no key, no gradient.
"""

from __future__ import annotations

from typing import Callable, List, Tuple

import jax
import jax.numpy as jnp

from benchmark.models.transformer import _path_name, leaves_from  # noqa: F401

COUNTERS = ("moe.rows_local", "moe.rows_total", "attn.score_entries_live",
            "attn.score_entries_computed", "ssd.head_tokens", "ssd.chunks")


def model_of(cfg: dict):
    from geomx_tpu.models.nemotron_h import NemotronH

    return NemotronH(
        vocab=cfg.get("vocab_rows", cfg["vocab_size"]),
        dim=cfg["hidden_size"],
        pattern=cfg["hybrid_override_pattern"],
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
        routed_scale=cfg["routed_scaling_factor"],
        eps=cfg["layer_norm_epsilon"],
        compute_dtype=jnp.dtype(cfg["compute_dtype"]))


def buffers_of(cfg: dict) -> dict:
    """The model's ``buffers`` collection from the configuration's
    correction bias, ``{block<l>: {e_score_correction_bias: [E]}}``."""
    from benchmark.references.nemotron_h import correction_bias

    tree = {}
    for name, value in correction_bias(cfg).items():
        block, leaf = name.split("/")
        tree[block] = {leaf: jnp.asarray(value)}
    return tree


def build(cfg: dict, seq_len: int) -> Tuple[List[str], Callable]:
    """(leaf names in the program's flatten order, grad_step)."""
    from geomx_tpu.models.nemotron_h import next_token_loss
    from geomx_tpu.parallel.grad_accum import accumulate_gradients

    model = model_of(cfg)
    abstract = jax.eval_shape(
        model.init, jax.random.PRNGKey(0),
        jnp.zeros((1, seq_len), jnp.int32))
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract["params"])
    names = [_path_name(path) for path, _ in flat]
    micro = cfg["microbatch_sequences"]
    buffers = buffers_of(cfg)

    def loss_fn(leaf_list, toks):
        return next_token_loss(
            model, {"params": jax.tree_util.tree_unflatten(
                treedef, leaf_list), "buffers": buffers}, toks)

    grad = jax.value_and_grad(loss_fn, has_aux=True)

    def counted(leaf_list, toks, _y):
        n = max(toks.shape[0] // micro, 1)
        (loss, counts), grads = accumulate_gradients(
            grad, n, has_aux=True)(leaf_list, toks)
        return loss, grads, counts

    def grad_step(leaf_list, toks, _y):
        return counted(leaf_list, toks, _y)[:2]

    grad_step.counted = (COUNTERS, counted)
    return names, grad_step
