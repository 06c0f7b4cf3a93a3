"""The comparison that decides ``correct`` for a training cell.

Eight numbers, each the worst over the clients (and steps, leaves, rows
or entries) of one run.  Of each client's first three local steps:

  loss_gap    |loss - ref| / |ref| of each step
  grad_gap    the first gradient, as the optimizer got it: per leaf,
              |norm - ref norm| / max(ref norm, median leaf's ref norm)
  update_gap  the same for each leaf's change over the three steps
  cache_gap   the h^l rows the client pulled for the round: per row,
              max |row - ref row| / max(ref row absmax, median absmax)

Of the whole round (every client's local epochs, push, FedAvg, eval):

  push_gap    the rows each client pulls after the round, as its
              owner's push left them: the share of entries off the
              reference's by more than half the row's int8 step
  push_stale  the same rows: the share of those the round moved (by at
              least a tenth of the median row's move) that are off the
              reference's by more than half their move
  fedavg_gap  the averaged model: per leaf, |avg - ref avg| /
              max(|ref avg - start|, median leaf's)
  acc_gap     |test accuracy - ref test accuracy| of the averaged model

A cell's limit of ``None`` shows a number without judging it: the
number is not steady from seed to seed there (``PERF.md``).

A leaf whose reference gradient is under a thousandth of the median
leaf's moves by round-off alone; it is left out of ``grad_gap``,
``update_gap`` and ``fedavg_gap`` (the rule in ``PERF.md``).
"""

from __future__ import annotations

import numpy as np

NAMES = ("loss_gap", "grad_gap", "update_gap", "cache_gap", "push_gap",
         "push_stale", "fedavg_gap", "acc_gap")


def _leaf_norms(pairs) -> list[float]:
    return [float(np.linalg.norm(np.asarray(a, np.float64)))
            for wb in pairs for a in wb]


def _norm_gap(prog, ref, keep) -> float:
    pn, rn = _leaf_norms(prog), _leaf_norms(ref)
    med = float(np.median(rn))
    gaps = [abs(p - r) / max(r, med, 1e-30)
            for p, r, k in zip(pn, rn, keep) if k]
    return max(gaps) if gaps else 0.0


def moving_leaves(ref_grad) -> list[bool]:
    norms = _leaf_norms(ref_grad)
    med = float(np.median(norms))
    return [n >= 1e-3 * med for n in norms]


def _delta(after, before):
    return [tuple(np.asarray(a, np.float64) - np.asarray(b, np.float64)
                  for a, b in zip(wa, wb)) for wa, wb in zip(after, before)]


def cache_gap(prog_rows: list[np.ndarray], ref_rows: list[np.ndarray]
              ) -> float:
    worst = 0.0
    for p, r in zip(prog_rows, ref_rows):
        p = np.asarray(p, np.float64)
        r = np.asarray(r, np.float64)
        if r.size == 0:
            continue
        absmax = np.abs(r).max(axis=1)
        den = np.maximum(absmax, max(float(np.median(absmax)), 1e-30))
        worst = max(worst, float((np.abs(p - r).max(axis=1) / den).max()))
    return worst


def step_share(prog_rows: list[np.ndarray], ref_rows: list[np.ndarray]
               ) -> tuple[int, int]:
    """(entries off by more than half the reference row's int8 step,
    entries)."""
    off = total = 0
    for p, r in zip(prog_rows, ref_rows):
        p = np.asarray(p, np.float64)
        r = np.asarray(r, np.float64)
        if r.size == 0:
            continue
        half = 0.5 * np.abs(r).max(axis=1, keepdims=True) / 127.0
        off += int((np.abs(p - r) > half).sum())
        total += r.size
    return off, total


def stale_share(prog_rows, ref_rows, before_rows) -> tuple[int, int]:
    """(rows off the reference by more than half of what the round moved
    them, rows the round moved); ``before_rows`` are the rows pulled
    before the round."""
    moved, prog_off = [], []
    for p, r, b in zip(prog_rows, ref_rows, before_rows):
        r = np.asarray(r, np.float64)
        if r.size == 0:
            continue
        move = np.linalg.norm(r - np.asarray(b, np.float64), axis=1)
        off = np.linalg.norm(np.asarray(p, np.float64) - r, axis=1)
        moved.append(move)
        prog_off.append(off > 0.5 * move)
    if not moved:
        return 0, 0
    move = np.concatenate(moved)
    counted = move >= 0.1 * float(np.median(move))
    counted &= move > 0
    off = np.concatenate(prog_off) & counted
    return int(off.sum()), int(counted.sum())


def client_gaps(prog: dict, ref: dict, params0) -> dict:
    """First-step gaps of one client.  ``prog`` and ``ref`` each hold
    ``loss`` (per step), ``grad`` (first gradient, per layer (W, b)),
    ``params`` (after the last checked step) and ``cache`` (pulled rows
    per layer)."""
    keep = moving_leaves(ref["grad"])
    if len(prog["loss"]) != len(ref["loss"]):
        loss = float("inf")
    else:
        loss = max(abs(lp - lr) / max(abs(lr), 1e-30)
                   for lp, lr in zip(prog["loss"], ref["loss"]))
    return {
        "loss_gap": float(loss),
        "grad_gap": _norm_gap(prog["grad"], ref["grad"], keep),
        "update_gap": _norm_gap(_delta(prog["params"], params0),
                                _delta(ref["params"], params0), keep),
        "cache_gap": cache_gap(prog["cache"], ref["cache"]),
    }


def round_gaps(prog: dict, ref: dict, params0, keep) -> dict:
    """Whole-round gaps.  ``prog`` and ``ref`` each hold ``pulled``
    (per client, the rows pulled after the round per layer), ``avg``
    (the averaged model, per layer (W, b)) and ``acc``; ``ref`` also
    ``before`` (per client, the rows pulled before the round)."""
    off = total = stale = rows = 0
    for c in ref["pulled"]:
        o, t = step_share(prog["pulled"][c], ref["pulled"][c])
        off, total = off + o, total + t
        o, t = stale_share(prog["pulled"][c], ref["pulled"][c],
                           ref["before"][c])
        stale, rows = stale + o, rows + t
    rn = _leaf_norms(_delta(ref["avg"], params0))
    dn = _leaf_norms(_delta(prog["avg"], ref["avg"]))
    med = float(np.median(rn))
    fed = [d / max(r, med, 1e-30) for d, r, k in zip(dn, rn, keep) if k]
    return {"push_gap": off / total if total else 0.0,
            "push_stale": stale / rows if rows else 0.0,
            "fedavg_gap": max(fed) if fed else 0.0,
            "acc_gap": abs(float(prog["acc"]) - float(ref["acc"]))}


def worst(per_client: list[dict]) -> dict:
    return {k: max(g[k] for g in per_client) for k in per_client[0]}


def verdict(gaps: dict, limits: dict) -> tuple[bool, dict]:
    """``correct`` and the compared numbers beside their limits."""
    shown = {k: {"value": gaps[k], "limit": limits[k]} for k in NAMES}
    ok = all(np.isfinite(gaps[k]) and gaps[k] <= limits[k]
             for k in NAMES if limits[k] is not None)
    return bool(ok), shown
