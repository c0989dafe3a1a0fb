"""The comparison that decides ``correct``: what the timed path produced
against the plain reference, each number beside its limit. The limits are
data (``benchmark/limits/<workload>.json``), set from readings on the chip
that ``PERF.md`` lists."""
from __future__ import annotations

import statistics
import sys

import numpy as np

#: leaves whose first gradient in the reference is under this share of the
#: median leaf's move under Adam by round-off alone: left out of the change
SILENT_GRADIENT = 1e-3


def norm_gap(prog: dict, ref: dict, skip=()) -> tuple[float, str]:
    """The worst leaf's gap between the program's norm and the reference's
    (not the norm of a difference), measured against the reference's norm
    of that leaf or of the median leaf, whichever is larger."""
    floor = statistics.median(ref.values())
    worst, where = 0.0, ""
    for name, r in ref.items():
        if name in skip:
            continue
        gap = abs(prog[name] - r) / max(r, floor)
        if not np.isfinite(gap):
            return float("inf"), name
        if gap > worst:
            worst, where = gap, name
    return worst, where


def silent_leaves(ref_grad_norm: dict) -> set[str]:
    floor = SILENT_GRADIENT * statistics.median(ref_grad_norm.values())
    return {n for n, g in ref_grad_norm.items() if g < floor}


def train_numbers(prog: dict, ref: dict) -> tuple[dict, dict]:
    """(numbers, notes). ``prog`` and ``ref`` hold ``losses`` (one per
    checked step), ``grad_norm`` and ``change_norm`` (per leaf, a fused
    leaf as the parts that its family splits it into)."""
    numbers = {}
    loss_gaps = [abs(a - b) / abs(b)
                 for a, b in zip(prog["losses"], ref["losses"])]
    g, g_at = norm_gap(prog["grad_norm"], ref["grad_norm"])
    skip = silent_leaves(ref["grad_norm"])
    c, c_at = norm_gap(prog["change_norm"], ref["change_norm"], skip)
    numbers["grad_norm_gap"] = g
    numbers["change_norm_gap"] = c
    # the losses are printed, not compared: at a random start every loss is
    # ln(vocabulary) to four digits whatever the step does (PERF.md)
    return numbers, {"grad_norm_gap_at": g_at, "change_norm_gap_at": c_at,
                     "loss_gaps": loss_gaps, "silent_leaves": len(skip)}


def gap_numbers(gaps: np.ndarray) -> dict:
    """The number compared for a served model, from the gaps of all the
    served tokens compared (how far each lies below the reference's best):
    their mean square. A token is second-best where the two best logits lie
    within the arithmetic's error of each other, so the count of such
    tokens grows with that error and so does each gap: the mean square
    grows with its third power and tells float32 served through one
    bfloat16 pass from bfloat16 throughout, which the widest gap and the
    mean do not (PERF.md)."""
    return {"served_gap_meansq": float(np.mean(np.square(gaps)))}


def gap_stats(g: np.ndarray) -> dict:
    """What else is printed of the gaps, not compared."""
    return {"widest": float(g.max()), "mean": float(g.mean()),
            "meansq": float(np.mean(np.square(g))),
            "not_best_share": float((g > 0).mean()), "tokens": int(g.size)}


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """Every number within its limit. A number without a limit, or a limit
    without its number, is a fault of the benchmark and fails the run."""
    rows, ok = {}, True
    for name in sorted(set(numbers) | set(limits)):
        value, limit = numbers.get(name), limits.get(name)
        good = (value is not None and limit is not None
                and np.isfinite(value) and value <= limit)
        ok &= bool(good)
        rows[name] = {"value": value, "limit": limit}
    return ok, rows


def say_compared(rows: dict, correct: bool) -> None:
    """The numbers compared, as the last lines of standard error."""
    print(f"[bench] compared (correct={str(correct).lower()}):",
          file=sys.stderr)
    for name, r in rows.items():
        print(f"[bench]   {name} = {r['value']} (limit {r['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
