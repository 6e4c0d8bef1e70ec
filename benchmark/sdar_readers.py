"""Readers of the ``sdar`` family's per-layer metrics.

They keep what the core of block-diffusion attention REQUIRES (its
operations and bytes, :func:`blockdiff_core_need`) and read the core's
device time by its named scope (``blockdiff_core``,
``models/transformer.py::block_diffusion_attention``) from the run's
own trace, the live score entries and the masked positions from the
counters the program books once a round through ``grad_step.counted``
(``attn.score_entries_live``, ``diffusion.positions_masked``,
``diffusion.positions``). The routed experts' roofline is
``moe_readers``' at this family's keys. A program without the scope or
the counters (a parent commit, another family) gives nothing to read,
and the metric is left out.
"""

from __future__ import annotations

import dataclasses

from benchmark import moe_readers, qwen3next_readers
from benchmark.readers import Context


def blockdiff_core_need(cfg: dict, live_entries: float,
                        head_positions: float) -> dict:
    """What the attention core REQUIRES for ``live_entries`` score
    entries under the block mask and ``head_positions`` (position, query
    head) pairs, forward and backward, whatever the algorithm:
        operations  an entry's part of q k^T and of p v over hd dims
                    each, 2 * (hd + hd) forward; the way back has two
                    products for each (dS k and dS^T q; p^T dO and
                    dO v^T): 3 * 2 * (hd + hd) in all
        bytes       q, k, v, o in the compute dtype (2 bytes), hd dims
                    each, move once, and so do their cotangents:
                    2 * 2 * 4 * hd a pair. The key/value head is counted
                    a query head, as the kernels read it
    Masked entries, the softmax, the log-sum-exp and what the kernels
    compute again on the way back (s and p a tile) are not required."""
    hd = cfg["head_dim"]
    return {"flops": live_entries * 6.0 * (hd + hd),
            "bytes": head_positions * 16.0 * hd}


def attn_core_roofline(ctx: Context, spec: dict):
    """The least time the chip could take for the traced rounds'
    block-diffusion attention cores (the larger of operations over the
    bf16 peak and bytes over the HBM peak) over the device time under
    the scope, in %."""
    ms = qwen3next_readers.scope_ms_per_round(ctx, spec)
    if ms is None or ctx.peaks is None or "block_length" not in ctx.cfg:
        return None
    traced = ctx.trace["rounds"]
    live = moe_readers._rows(ctx, "attn.score_entries_live", 0, traced)
    if not live:
        return None
    heads = ctx.cfg["query_heads"][1] - ctx.cfg["query_heads"][0]
    # a counted token is two positions: its clean and its noised copy
    need = blockdiff_core_need(
        ctx.cfg, live,
        2 * ctx.tokens_traced * heads * ctx.cfg["num_hidden_layers"])
    least_s = max(need["flops"] / ctx.peaks["bf16_flops_per_s"],
                  need["bytes"] / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms * 1e-3 * traced)


def masked_position_share(ctx: Context, spec: dict):
    """Positions masked in the noised copy over the positions that
    could bear loss, window delta, in %: the mean masking probability
    of the window's batches, about 50 under the linear schedule. 0
    would be noise that never reached the loss."""
    last = len(ctx.snaps) - 1
    masked = moe_readers._rows(ctx, "diffusion.positions_masked", 0, last)
    positions = moe_readers._rows(ctx, "diffusion.positions", 0, last)
    return 100.0 * masked / positions if positions else None


def expert_matmul_roofline(ctx: Context, spec: dict):
    """``moe_readers.expert_matmul_roofline`` at the experts' width
    (here ``intermediate_size`` is a key nothing reads) with a pass
    counted as it is: one layer of one microbatch is BOTH copies of
    ``microbatch_sequences`` sequences, so the passes are those of
    ``tokens_traced`` counted tokens."""
    if "block_length" not in ctx.cfg:
        return None
    return moe_readers.expert_matmul_roofline(dataclasses.replace(
        ctx, cfg=dict(ctx.cfg,
                      intermediate_size=ctx.cfg["moe_intermediate_size"])),
        spec)
