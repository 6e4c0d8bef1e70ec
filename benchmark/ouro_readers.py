"""Readers of the ``ouro`` family's per-layer metrics.

They keep what the causal attention core REQUIRES (its operations and
bytes, :func:`causal_core_need`) and read the core's device time by its
named scope (``causal_core``, ``models/ouro.py``) from the run's own
trace, the live score entries, the positions and the exits' masses from
the counters the program books once a round through
``grad_step.counted`` (``attn.score_entries_live``, ``ouro.positions``,
``ouro.exit_mass_t<R>``). A program without the scope or the counters
(a parent commit, another family) gives nothing to read, and the metric
is left out.
"""

from __future__ import annotations

from benchmark import moe_readers, qwen3next_readers
from benchmark.readers import Context


def causal_core_need(cfg: dict, live_entries: float,
                     head_positions: float) -> dict:
    """What the attention core REQUIRES for ``live_entries`` score
    entries under the causal mask and ``head_positions`` (position,
    head, layer application) triples, forward and backward, whatever
    the algorithm:
        operations  an entry's part of q k^T and of p v over hd dims
                    each, 2 * (hd + hd) forward; the way back has two
                    products for each (dS k and dS^T q; p^T dO and
                    dO v^T): 3 * 2 * (hd + hd) in all
        bytes       q, k, v, o in the compute dtype (2 bytes), hd dims
                    each, move once, and so do their cotangents:
                    2 * 2 * 4 * hd a triple
    Masked entries, the softmax, the log-sum-exp and what is computed
    again on the way back (the forward kernel of a rematerialised
    block, s and p a tile) are not required."""
    hd = cfg["head_dim"]
    return {"flops": live_entries * 6.0 * (hd + hd),
            "bytes": head_positions * 16.0 * hd}


def attn_core_roofline(ctx: Context, spec: dict):
    """The least time the chip could take for the traced rounds' causal
    attention cores (the larger of operations over the bf16 peak and
    bytes over the HBM peak) over the device time under the scope, in
    %."""
    ms = qwen3next_readers.scope_ms_per_round(ctx, spec)
    if ms is None or ctx.peaks is None or "total_ut_steps" not in ctx.cfg:
        return None
    traced = ctx.trace["rounds"]
    live = moe_readers._rows(ctx, "attn.score_entries_live", 0, traced)
    if not live:
        return None
    applications = ctx.cfg["num_hidden_layers"] * ctx.cfg["total_ut_steps"]
    need = causal_core_need(
        ctx.cfg, live,
        ctx.tokens_traced * ctx.cfg["num_attention_heads"] * applications)
    least_s = max(need["flops"] / ctx.peaks["bf16_flops_per_s"],
                  need["bytes"] / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms * 1e-3 * traced)


def last_exit_share(ctx: Context, spec: dict):
    """The exit distribution's mass on the last pass over the positions,
    window delta, in %: 12.5 where every gate stands at 0.5 over four
    passes."""
    if "total_ut_steps" not in ctx.cfg:
        return None
    last = len(ctx.snaps) - 1
    mass = moe_readers._rows(
        ctx, f"ouro.exit_mass_t{ctx.cfg['total_ut_steps']}", 0, last)
    positions = moe_readers._rows(ctx, "ouro.positions", 0, last)
    return 100.0 * mass / positions if positions else None
