"""Readers of the ``laguna`` family's per-layer metrics.

They read counters the program books once a round through
``grad_step.counted``: ``moe.rows_local`` (as ``moe_readers`` does) and
``attn.score_entries_live`` / ``attn.score_entries_computed`` (the
attention score entries the masks keep, and those the program's score
products have by shape; all layers and held heads, all workers). A
program without the counters gives nothing to read, and the metric is
left out.
"""

from __future__ import annotations

import dataclasses

from benchmark import moe_readers
from benchmark.readers import Context


def attn_live_score_share(ctx: Context, spec: dict):
    """Live attention score entries over computed ones, window delta,
    in %: 100 would be a score product that computes no masked entry."""
    last = len(ctx.snaps) - 1
    live = moe_readers._rows(ctx, "attn.score_entries_live", 0, last)
    computed = moe_readers._rows(ctx, "attn.score_entries_computed", 0, last)
    return 100.0 * live / computed if computed else None


def _as_expert_layers(cfg: dict) -> dict:
    """The configuration as ``moe_readers`` reads one: its expert width
    under ``intermediate_size`` (here that key is the dense layer's) and
    its expert layers as the depth (the dense layer has no experts)."""
    return dict(cfg, intermediate_size=cfg["moe_intermediate_size"],
                num_hidden_layers=sum(
                    kind == "sparse" for kind in cfg["mlp_layer_types"]))


def grouped_matmul_need(cfg: dict, rows: float, calls: float) -> dict:
    """What the routed experts' grouped matmuls REQUIRE for ``rows``
    routed rows in ``calls`` passes (one pass = one expert layer of one
    microbatch), at the experts' own width ``moe_intermediate_size``:
    ``moe_readers.grouped_matmul_need``'s operations and bytes. The
    shared expert and the dense layer are plain matmuls and are not
    counted here."""
    return moe_readers.grouped_matmul_need(_as_expert_layers(cfg), rows,
                                           calls)


def expert_matmul_roofline(ctx: Context, spec: dict):
    """``moe_readers.expert_matmul_roofline`` over the four expert
    layers at the experts' width. The program runs the forward products
    a second time on the way back (``jax.checkpoint``); what is
    recomputed is in the device time, is not required and is not
    counted."""
    return moe_readers.expert_matmul_roofline(
        dataclasses.replace(ctx, cfg=_as_expert_layers(ctx.cfg)), spec)
