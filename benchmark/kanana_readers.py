"""Readers of the ``kanana`` family's per-layer metrics.

They keep what the core of latent attention REQUIRES (its operations
and bytes, :func:`mla_core_need`) and read the core's device time by
its named scope (``latent_core``, ``models/transformer.py::
latent_attention``) from the run's own trace, the live score entries
from the counter the program books once a round through
``grad_step.counted`` (``attn.score_entries_live``). The routed
experts' roofline is ``moe_readers``' at this family's keys. A program
without the scope or the counters (a parent commit, another family)
gives nothing to read, and the metric is left out.
"""

from __future__ import annotations

import dataclasses

from benchmark import moe_readers, qwen3next_readers
from benchmark.readers import Context


def mla_core_need(cfg: dict, live_entries: float, head_tokens: float) -> dict:
    """What the core of latent attention REQUIRES for ``live_entries``
    score entries under the causal mask and ``head_tokens`` (token,
    head) pairs, forward and backward, whatever the algorithm:
        operations  an entry's part of q k^T over Dn + Dr dims and of
                    p v over Dv dims, 2 * (Dn + Dr + Dv) forward; the
                    way back has two products for each (dS k and dS^T q;
                    p^T dO and dO v^T): 3 * 2 * (Dn + Dr + Dv) in all
        bytes       q, k over Dn + Dr dims, v, o over Dv dims, in the
                    compute dtype (2 bytes), move once, and so do their
                    cotangents: 2 * 2 * 2 * (Dn + Dr + Dv) a pair. The
                    rotary key is counted a head, as the core is handed
                    it
    Masked entries, the softmax, the log-sum-exp and what the kernels
    compute again on the way back (s and p a block) are not required."""
    dqk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    dv = cfg["v_head_dim"]
    return {"flops": live_entries * 6.0 * (dqk + dv),
            "bytes": head_tokens * 8.0 * (dqk + dv)}


def mla_core_roofline(ctx: Context, spec: dict):
    """The least time the chip could take for the traced rounds' latent
    attention cores (the larger of operations over the bf16 peak and
    bytes over the HBM peak) over the device time under the scope, in
    %."""
    ms = qwen3next_readers.scope_ms_per_round(ctx, spec)
    if ms is None or ctx.peaks is None:
        return None
    traced = ctx.trace["rounds"]
    live = moe_readers._rows(ctx, "attn.score_entries_live", 0, traced)
    if not live:
        return None
    heads = ctx.cfg["query_heads"][1] - ctx.cfg["query_heads"][0]
    need = mla_core_need(
        ctx.cfg, live,
        ctx.tokens_traced * heads * ctx.cfg["num_hidden_layers"])
    least_s = max(need["flops"] / ctx.peaks["bf16_flops_per_s"],
                  need["bytes"] / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms * 1e-3 * traced)


def _as_expert_layers(cfg: dict) -> dict:
    """The configuration as ``moe_readers`` reads one: its expert width
    under ``intermediate_size`` (here that key is the dense layer's) and
    its expert layers as the depth (the leading dense layers have no
    experts)."""
    return dict(cfg, intermediate_size=cfg["moe_intermediate_size"],
                num_hidden_layers=cfg["num_hidden_layers"]
                - cfg["first_k_dense_replace"])


def expert_matmul_roofline(ctx: Context, spec: dict):
    """``moe_readers.expert_matmul_roofline`` over the expert layers at
    the experts' width. The program runs the forward products a second
    time on the way back (``jax.checkpoint``); what is recomputed is in
    the device time, is not required and is not counted."""
    if "first_k_dense_replace" not in ctx.cfg:
        return None
    return moe_readers.expert_matmul_roofline(
        dataclasses.replace(ctx, cfg=_as_expert_layers(ctx.cfg)), spec)
