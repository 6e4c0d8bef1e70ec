"""Readers of the mixture-of-experts per-layer metrics.

They read what the program books once a round from the group sizes its
step computes anyway, the counters ``moe.rows_local`` (the (token, slot)
rows routed to the experts held here, all workers and layers) and
``moe.rows_total`` (all routed rows), and the grouped matmuls' device
time from the trace. A program without the counters (a dense family, a
parent commit) gives nothing to read, and the metric is left out.
"""

from __future__ import annotations

from benchmark.readers import Context, trace_op_ms_per_round


def _rows(ctx: Context, name: str, first: int, last: int):
    counters = [s.get("counters", {}) for s in ctx.snaps]
    if len(counters) <= last or name not in counters[last]:
        return None
    return counters[last][name] - counters[first].get(name, 0.0)


def local_row_share(ctx: Context, spec: dict):
    """Rows routed to the held experts over all routed rows, window
    delta, in %."""
    local = _rows(ctx, "moe.rows_local", 0, len(ctx.snaps) - 1)
    total = _rows(ctx, "moe.rows_total", 0, len(ctx.snaps) - 1)
    return 100.0 * local / total if total else None


def grouped_matmul_need(cfg: dict, rows: float, calls: float) -> dict:
    """What the grouped expert matmuls REQUIRE for ``rows`` routed rows
    in ``calls`` passes (one pass = one layer of one microbatch: the
    three matmuls gate, up, down, each forward, its gradient to the
    rows and its gradient to the weights).
        operations  rows * 3 matmuls * 3 products * 2*D*W
        bytes       bf16: a product reads or writes its rows on both
                    sides (D + W elements a row) and its expert stack
                    (E_local*D*W) once: 9 * (rows*(D+W) + calls*E_local*D*W)
                    elements of 2 bytes
    Rows past the held experts' groups need nothing."""
    d, w = cfg["hidden_size"], cfg["intermediate_size"]
    held = cfg["local_experts"][1] - cfg["local_experts"][0]
    return {"flops": rows * 18.0 * d * w,
            "bytes": 2.0 * 9.0 * (rows * (d + w) + calls * held * d * w)}


def expert_matmul_roofline(ctx: Context, spec: dict):
    """The least time the chip could take for the traced rounds' grouped
    expert matmuls (the larger of operations over the bf16 peak and
    bytes over the HBM peak) over their device time, in %."""
    ms = trace_op_ms_per_round(ctx, spec)
    if ms is None or ctx.peaks is None:
        return None
    traced = ctx.trace["rounds"]
    rows = _rows(ctx, "moe.rows_local", 0, traced)
    if not rows:
        return None
    calls = (ctx.cfg["num_hidden_layers"] * ctx.tokens_traced
             / (ctx.seq_len * ctx.cfg["microbatch_sequences"]))
    need = grouped_matmul_need(ctx.cfg, rows, calls)
    least_s = max(need["flops"] / ctx.peaks["bf16_flops_per_s"],
                  need["bytes"] / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms * 1e-3 * traced)
