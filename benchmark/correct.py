"""What decides ``correct``. Every part runs outside the timed window:

(a) :func:`reference_errors` — the system's loss and parameter gradients
    (the benchmark's ``grad_step`` through the program's model, in the
    configuration's compute dtype) against the plain float32 reference
    at the published widths, on seeded sequences. One steady number is
    compared: the relative L2 error of the whole gradient,
    ``||g_sys - g_ref|| / ||g_ref||`` over every parameter, beside the
    relative error of the loss. :func:`control_errors` puts the
    reference with lower-precision operands in the program's place.
    The same number is read a second time in (b), from the trainer's
    own program at the cell's batch.
(b) the first (untimed) round, recomputed in numpy from what the
    trainers hold and what crossed their kvstores:
    :func:`check_select` — what each worker pushed is the exact per-key
    top-k of its accumulator, and that accumulator is, within the limit
    of (a), the reference's gradient of its batch;
    :func:`check_aggregate` — what every worker applied
    against what left the devices; :func:`check_apply` — the parameters
    after the round are the initial ones less ``lr`` times that
    aggregate.
(c) after the window (``run.finish``): replicas bit-identical and
    moved, losses finite, nothing compiled, WAN bytes counted and none
    of the payload raw.

A limit that was read from runs is data: ``limits`` in the
configuration's file (what depends on the model and its precision) and
in the cell's file (what depends on the protocol). The limits written
here as 0 are exact comparisons. How each was set is in PERF.md
section 2.
"""

from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

U32 = 2.0 ** -24        # unit roundoff of float32
# check_apply, in units of the last place of the largest of the operands
# and the result of ``init - lr * aggregate``: 0 where the product is
# rounded before the difference as numpy does, up to 1 where the two are
# fused; the same arithmetic in bfloat16 reads tens of thousands (PERF.md
# section 2)
APPLY_MAX_ULPS = 4


def _errors_fn(names: Sequence[str]):
    import jax.numpy as jnp

    def errs(loss_a, grads_a, loss_b, grads_b):
        num = sum(jnp.sum((a.astype(jnp.float32) - grads_b[n]) ** 2)
                  for a, n in zip(grads_a, names))
        den = sum(jnp.sum(grads_b[n] ** 2) for n in names)
        return {"loss": loss_a, "loss_ref": loss_b,
                "loss_rel_err": jnp.abs(loss_a - loss_b) / jnp.abs(loss_b),
                "grad_rel_l2": jnp.sqrt(num / den)}

    return errs


def reference_step(ref, cfg: dict, operand_dtype: str = None):
    """The jitted reference, ``(params, tokens) -> (loss, {name: grad})``;
    with ``operand_dtype`` the control: the same mathematics with its
    matmul operands rounded to that type, in the program's place."""
    import jax

    return jax.jit(lambda p, x: ref.loss_and_grads(p, x, cfg, operand_dtype))


def reference_errors(ref_step, params: Dict, names: List[str], grad_step,
                     toks) -> Dict[str, float]:
    """The program against the reference, one jitted call each; the
    gradients never leave the device."""
    import jax

    leaves = [params[n] for n in names]
    loss_s, grads_s = jax.jit(grad_step)(leaves, toks, None)
    loss_r, grads_r = ref_step(params, toks)
    out = jax.jit(_errors_fn(names))(loss_s, grads_s, loss_r, grads_r)
    return {k: float(v) for k, v in out.items()}


def control_errors(ref_step, control_step, params: Dict, names: List[str],
                   toks) -> Dict[str, float]:
    """The control against the reference, as the program is in
    :func:`reference_errors`."""
    import jax

    loss_c, grads_c = control_step(params, toks)
    loss_r, grads_r = ref_step(params, toks)
    out = jax.jit(_errors_fn(names))(
        loss_c, [grads_c[n] for n in names], loss_r, grads_r)
    return {k: float(v) for k, v in out.items()}


def batch_gradient(step, params: Dict, names: List[str], batch: np.ndarray,
                   rows: int) -> np.ndarray:
    """The gradient of the mean loss over ``batch`` from ``step`` (a
    :func:`reference_step`), flat in the order of ``names``, on the host:
    the mean over shards of ``rows`` sequences, the shape ``step`` has
    been compiled for already."""
    import jax
    import jax.numpy as jnp

    shards = np.split(batch, len(batch) // rows)
    add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b))
    total = None
    for shard in shards:
        grads = step(params, jnp.asarray(shard))[1]
        total = grads if total is None else add(total, grads)
    return np.concatenate([np.asarray(total[n], np.float32).ravel()
                           for n in names]) / np.float32(len(shards))


def rel_l2(x: np.ndarray, want: np.ndarray) -> float:
    return float(np.linalg.norm(x - want)) / float(np.linalg.norm(want))


def _offsets(sizes: Sequence[int]) -> np.ndarray:
    return np.concatenate([[0], np.cumsum(sizes)]).astype(np.int64)


def _bf16(x: np.ndarray) -> np.ndarray:
    import ml_dtypes

    return x.astype(ml_dtypes.bfloat16).astype(np.float32)


def check_select(pushed: dict, v_after: np.ndarray, sizes: Sequence[int],
                 threshold: float, grad: np.ndarray = None,
                 workers: int = 1) -> dict:
    """(b) the device's Bi-Sparse select in the first round, worker by
    worker. ``pushed``: ``{key: (values, key-relative indices)}`` as the
    trainer handed them to its kvstore, keys ``0..n-1`` in leaf order;
    ``v_after``: the trainer's flat accumulator after the round.

    Exact, limit 0 each: every key pushes ``max(int(size * threshold),
    1)`` distinct positions of its own (``keys_bad_count``); the
    accumulator is cleared there (``not_cleared``); and no entry that
    stayed behind is larger than the smallest that left, so the pushed
    set is the exact top-k by magnitude (``keys_not_topk``). An
    approximate top-k fails the last.

    With ``grad`` (the plain reference's gradient of this worker's
    first batch, flat): ``grad_rel_l2``, the relative L2 distance
    between the accumulator before selection (``v_after`` with the
    pushed values put back) and ``grad / workers``, which it is in a
    first round: the number of part (a), read from the trainer's own
    program, select included."""
    offs = _offsets(sizes)
    rest_max = np.maximum.reduceat(np.abs(v_after), offs[:-1])
    bad_count = not_topk = not_cleared = 0
    v_pre = v_after.copy() if grad is not None else None
    if sorted(pushed) != list(range(len(sizes))):
        raise ValueError("the pushed keys are not the leaves, in order")
    for i, size in enumerate(sizes):
        vals, idx = (np.asarray(x) for x in pushed[i])
        k = max(int(size * threshold), 1)
        if (len(idx) != k or len(np.unique(idx)) != k or idx.min() < 0
                or idx.max() >= size):
            bad_count += 1
            continue
        at = offs[i] + idx
        not_cleared += int(np.count_nonzero(v_after[at]))
        if not np.abs(vals).min() >= rest_max[i]:       # NaN fails too
            not_topk += 1
        if v_pre is not None:
            v_pre[at] = vals
    out = {"keys": len(sizes), "keys_bad_count": bad_count,
           "keys_not_topk": not_topk, "not_cleared": not_cleared}
    if grad is not None:
        out["grad_rel_l2"] = rel_l2(v_pre, grad / np.float32(workers))
    return out


def check_apply(flat0: np.ndarray, flat1: np.ndarray, applied: dict,
                sizes: Sequence[int], lr: float,
                control: bool = False) -> dict:
    """(b) the apply step of the first round: with the momentum buffer
    still zero, the parameters after it are ``flat0 - lr * aggregate``
    in float32, whatever the momentum. ``mismatched_untouched``:
    parameters the aggregate does not reach that changed a bit (limit
    0); ``max_ulps``: the farthest a touched parameter lies from the
    numpy float32 result, in units of the last place of the largest of
    the two operands and the result (of the result alone would punish
    a cancellation).
    ``control``: the same arithmetic in bfloat16, the next precision
    down."""
    offs = _offsets(sizes)
    g = np.zeros_like(flat0)
    for key, (vals, idx) in applied.items():
        np.add.at(g, offs[key] + np.asarray(idx, np.int64),
                  np.asarray(vals, np.float32))
    at = np.nonzero(g)[0]
    step = np.float32(lr) * g[at]
    want = flat0[at] - step
    ulp = np.spacing(np.maximum.reduce(
        [np.abs(flat0[at]), np.abs(step), np.abs(want)])).astype(np.float64)

    def far(got):
        return float((np.abs(got.astype(np.float64) - want) / ulp)
                     .max(initial=0.0))

    expect = flat0.copy()
    expect[at] = flat1[at]      # leaves only the untouched to compare
    out = {"touched": len(at),
           "mismatched_untouched": int(np.count_nonzero(
               expect.view(np.uint32) != flat1.view(np.uint32))),
           "max_ulps": far(flat1[at])}
    if control:
        out["control_max_ulps"] = far(
            _bf16(_bf16(flat0[at]) - _bf16(step)))
    return out


def _coded(entries: dict, rank: dict):
    """``{key: (values, indices)}`` as one sorted array of int64 codes
    (key rank in the high half, index in the low) and its values."""
    codes = [np.asarray(i, np.int64) + (rank[k] << 32)
             for k, (_v, i) in entries.items()]
    vals = [np.asarray(v, np.float32) for _k, (v, _i) in entries.items()]
    if not codes:
        return np.zeros(0, np.int64), np.zeros(0, np.float32)
    codes, vals = np.concatenate(codes), np.concatenate(vals)
    order = np.argsort(codes, kind="stable")
    return codes[order], vals[order]


def check_aggregate(pushed: List[dict], applied: List[dict]) -> dict:
    """(b). ``pushed[p]``: ``{key: (values, indices)}`` as van speaker
    ``p`` handed them to its kvstore; ``applied[w]``: what worker ``w``
    got back, in one round in which no server held earlier state. Any
    number of pushers.

    What holds with Bi-Sparse set on the party->global hop (each party's
    server selects again from its party's aggregate, so a pushed entry
    may stay behind in that server's residual): every applied entry is
    at a position some pusher pushed (``not_pushed_by_anyone``, limit
    0), once (``duplicates``, limit 0); its value is the float32 sum of
    what a non-empty subset of the pushers there pushed
    (``value_not_a_sum``, limit 0): bit for bit for one or two terms,
    and for more within the proven bound of float32 summation in any
    order, ``2 (n-1) u sum|x|``; every worker applies the same aggregate.
    ``pusher_share_min`` is, over the pushers, the smallest share of a
    pusher's entries that came back inside an applied value: a silenced
    party reads 0, and the cell's file holds the floor. ``complete``
    says whether every pushed position came back with the sum of ALL its
    pushers, which is what holds without re-selection.

    The subsets of the ``c`` pushers present at a position are tried
    largest first, so the cost is 2^c only where re-selection split
    them."""
    keys = sorted(set(applied[0]).union(*pushed))
    rank = {k: i for i, k in enumerate(keys)}
    same = all(
        set(o) == set(applied[0]) and all(
            np.array_equal(np.asarray(o[k][1]), np.asarray(applied[0][k][1]))
            and np.array_equal(
                np.asarray(o[k][0], np.float32).view(np.uint32),
                np.asarray(applied[0][k][0], np.float32).view(np.uint32))
            for k in o)
        for o in applied[1:])
    ac, av = _coded(applied[0], rank)
    m, n = len(pushed), len(ac)
    vals = np.zeros((m, n), np.float32)
    there = np.zeros((m, n), bool)
    codes, sent = [], []
    for p, entries in enumerate(pushed):
        pc, pv = _coded(entries, rank)
        codes.append(pc)
        sent.append(len(pc))
        if n:
            pos = np.minimum(np.searchsorted(ac, pc), n - 1)
            hit = ac[pos] == pc
            vals[p, pos[hit]], there[p, pos[hit]] = pv[hit], True
    present = there.sum(0)
    matched = np.zeros(n, bool)
    used = np.zeros((m, n), bool)       # the pushers inside the match
    for c in np.unique(present[present > 0]):
        cols = np.nonzero(present == c)[0]
        who = np.argsort(~there[:, cols], axis=0, kind="stable")[:c]
        x = np.take_along_axis(vals[:, cols], who, axis=0)
        want, left = av[cols], np.ones(len(cols), bool)
        for mask in sorted(range(1, 1 << int(c)),
                           key=lambda b: -bin(b).count("1")):
            rows = [i for i in range(int(c)) if mask >> i & 1]
            total = x[rows[0]].copy()
            for i in rows[1:]:
                total = total + x[i]
            if len(rows) <= 2:
                hit = left & (total == want)
            else:
                room = 2 * (len(rows) - 1) * U32 * np.abs(
                    x[rows].astype(np.float64)).sum(0)
                hit = left & (np.abs(total.astype(np.float64) - want)
                              <= room)
            at = np.nonzero(hit)[0]
            used[who[rows][:, at], cols[at]] = True
            left &= ~hit
            if not left.any():
                break
        matched[cols] = ~left
    inside = used.sum(0)
    shares = [float(used[p].sum()) / sent[p] for p in range(m) if sent[p]]
    union = len(np.unique(np.concatenate(codes))) if codes else 0
    out = {
        "applied_entries": n, "pushed_positions": union,
        "duplicates": int((ac[1:] == ac[:-1]).sum()),
        "not_pushed_by_anyone": int((present == 0).sum()),
        "value_not_a_sum": int(((present > 0) & ~matched).sum()),
        "part_of_its_pushers": int((matched & (inside < present)).sum()),
        "pusher_share_min": min(shares, default=0.0),
        "workers_agree": bool(same)}
    out["complete"] = bool(n == union and not out["part_of_its_pushers"])
    out["exact"] = bool(same and n > 0 and not (
        out["duplicates"] or out["not_pushed_by_anyone"]
        or out["value_not_a_sum"]))
    return out
