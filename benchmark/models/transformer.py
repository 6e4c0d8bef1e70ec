"""The ``transformer`` family as the SYSTEM runs it: the program's own
``geomx_tpu.models.transformer.Transformer`` (flax, compute dtype from
the configuration), wrapped to the leaf-list contract of
``DeviceResidentTrainer``: ``grad_step(leaves, tokens, None) ->
(loss, grad_leaves)``.

The weights are not the program's: they come from the benchmark's seeded
generator (``references/transformer.init_params``) and are laid into the
program's parameter tree by path name.
"""

from __future__ import annotations

from typing import Callable, Dict, List, Tuple

import jax
import jax.numpy as jnp


def _path_name(path) -> str:
    parts = [str(getattr(p, "key", p)) for p in path]
    return "/".join(parts[1:] if parts[0] == "params" else parts)


def build(cfg: dict, seq_len: int) -> Tuple[List[str], Callable]:
    """(leaf names in the program's flatten order, grad_step)."""
    from geomx_tpu.models.transformer import Transformer

    model = Transformer(
        vocab=cfg["vocab_size"], dim=cfg["n_embd"], depth=cfg["n_layer"],
        heads=cfg["n_head"], max_len=cfg["n_positions"],
        compute_dtype=jnp.dtype(cfg["compute_dtype"]))
    abstract = jax.eval_shape(
        model.init, jax.random.PRNGKey(0),
        jnp.zeros((1, seq_len), jnp.int32))
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    names = [_path_name(path) for path, _ in flat]

    def loss_fn(leaf_list, toks):
        p = jax.tree_util.tree_unflatten(treedef, leaf_list)
        logits = model.apply(p, toks[:, :-1])
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(
            jnp.take_along_axis(logp, toks[:, 1:, None], axis=-1))

    def grad_step(leaf_list, toks, _y):
        return jax.value_and_grad(loss_fn)(leaf_list, toks)

    return names, grad_step


def leaves_from(params: Dict[str, jax.Array], names: List[str]) -> list:
    """The seeded weights in the program's leaf order; a name the
    generator does not make is an error, not a default."""
    missing = [n for n in names if n not in params]
    if missing or len(names) != len(params):
        raise ValueError(f"parameter trees differ: program-only {missing}, "
                         f"{len(names)} leaves vs {len(params)} made")
    return [params[n] for n in names]
