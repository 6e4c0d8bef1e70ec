"""Readers of the ``nemotron_h`` family's per-layer metrics.

They keep what the selective state-space recurrence REQUIRES (its
operations and bytes, :func:`ssd_need`) and what this family's routed
experts do (two matmuls an expert, no gate: :func:`expert_need`), and
read device time by named scope (``ssd_scan``,
``models/nemotron_h.py``) or instruction name from the run's own trace
and the pairs and rows from the counters the program books once a round
through ``grad_step.counted`` (``ssd.head_tokens``,
``moe.rows_local``). A program without the scopes or the counters (a
parent commit, another family) gives nothing to read, and the metric is
left out.
"""

from __future__ import annotations

from benchmark import qwen3next_readers
from benchmark.readers import Context, trace_op_ms_per_round


def _booked(ctx: Context, name: str):
    """What the counter ``name`` gained over the traced rounds, or None
    where the program books no such counter."""
    counters = [s.get("counters", {}) for s in ctx.snaps]
    last = ctx.trace["rounds"]
    if len(counters) <= last or name not in counters[last]:
        return None
    return counters[last][name] - counters[0].get(name, 0.0)


def _share(ctx: Context, need: dict, ms_per_round: float) -> float:
    """The least time the chip could take for ``need`` (the larger of
    its operations over the bf16 peak and its bytes over the HBM peak)
    over the device time of the traced rounds, in %."""
    least_s = max(need["flops"] / ctx.peaks["bf16_flops_per_s"],
                  need["bytes"] / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms_per_round * 1e-3 * ctx.trace["rounds"])


def ssd_need(cfg: dict, head_tokens: float) -> dict:
    """What the recurrence REQUIRES for ``head_tokens`` (token, head)
    pairs, forward and backward, whatever the algorithm:
        operations  the token recurrence's two [P, N] products a pair
                    (x (x) B into the state, the state under C),
                    2 * 2*P*N, and their backward twice that: 12*P*N
        bytes       x, y [P] a head in the compute dtype (2 bytes), dt
                    a head in float32, B and C [N] a GROUP in the
                    compute dtype (r = heads a group share them, so
                    2 * 2*N / r a pair) move once, and so do their
                    gradients: 2 * (2 * 2*P + 4 + 2 * 2*N / r)
    The chunked form's extra products (C B^T and the decay matrix inside
    a chunk, the chunks' states), the decays and the skip term are not
    required."""
    p, n = cfg["mamba_head_dim"], cfg["ssm_state_size"]
    r = (cfg["mamba_heads"][1] - cfg["mamba_heads"][0]) / (
        cfg["mamba_groups"][1] - cfg["mamba_groups"][0])
    return {"flops": head_tokens * 12.0 * p * n,
            "bytes": head_tokens * 2.0 * (4 * p + 4 + 4 * n / r)}


def ssd_scan_roofline(ctx: Context, spec: dict):
    """The least time the chip could take for the traced rounds'
    recurrence (the larger of operations over the bf16 peak and bytes
    over the HBM peak) over the device time under its scope, in %."""
    if "mamba_head_dim" not in ctx.cfg:
        return None
    ms = qwen3next_readers.scope_ms_per_round(ctx, spec)
    if ms is None or ctx.peaks is None:
        return None
    pairs = _booked(ctx, "ssd.head_tokens")
    if not pairs:
        return None
    return _share(ctx, ssd_need(ctx.cfg, pairs), ms)


def expert_need(cfg: dict, rows: float, calls: float) -> dict:
    """What the grouped expert matmuls REQUIRE for ``rows`` routed rows
    in ``calls`` passes (one pass = one expert layer of one microbatch:
    the TWO matmuls up and down, each forward, its gradient to the rows
    and its gradient to the weights; ``moe_readers.grouped_matmul_need``
    is the gated families' three).
        operations  rows * 2 matmuls * 3 products * 2*D*W
        bytes       bf16: a product reads or writes its rows on both
                    sides (D + W elements a row) and its expert stack
                    (E_local*D*W) once: 6 * (rows*(D+W) + calls*E_local*D*W)
                    elements of 2 bytes
    Rows past the held experts' groups need nothing."""
    d, w = cfg["hidden_size"], cfg["moe_intermediate_size"]
    held = cfg["local_experts"][1] - cfg["local_experts"][0]
    return {"flops": rows * 12.0 * d * w,
            "bytes": 2.0 * 6.0 * (rows * (d + w) + calls * held * d * w)}


def expert_matmul_roofline(ctx: Context, spec: dict):
    """The least time the chip could take for the traced rounds' grouped
    expert matmuls (the larger of operations over the bf16 peak and
    bytes over the HBM peak) over their device time, in %. The program
    runs the forward products a second time on the way back (every
    block is rematerialised); what is recomputed is in the device time,
    is not required and is not counted."""
    if "hybrid_override_pattern" not in ctx.cfg:
        return None
    ms = trace_op_ms_per_round(ctx, spec)
    if ms is None or ctx.peaks is None:
        return None
    rows = _booked(ctx, "moe.rows_local")
    if not rows:
        return None
    calls = (ctx.cfg["hybrid_override_pattern"].count("E")
             * ctx.tokens_traced
             / (ctx.seq_len * ctx.cfg["microbatch_sequences"]))
    return _share(ctx, expert_need(ctx.cfg, rows, calls), ms)
