"""The comparison that decides ``correct``.

:func:`merge` and :func:`verdict` serve every program: they fold the
readings a program's ``check`` returns, one per sweep, and hold each
number to its limit. :func:`compare` is the lattice's reading.

The lattice's numbers compared, each against a limit kept with the
configuration (``limits`` in ``perfbench/configs/<config>.json``):

* ``n_scheduled_mismatch``: cell-rounds whose realized |S^t| differs from
  the reference's. Exact: the limit is 0.
* ``grad_norm_gap``, ``e_com_gap``, ``e_var_gap``: over every compared
  cell and each of the first rounds, the largest gap between the
  program's record and the reference's, as a share of the reference's
  value or of the median reference value of that field, whichever is
  larger (e_com is exactly 0 in noise-free cells). ``grad_norm`` is
  ||ŷ^t||, the update the optimizer steps with, so rounds after the first
  also check the weights the earlier updates left.
* ``eval0_loss_gap``, ``eval0_acc_gap``: the same gap for the test loss
  and the test accuracy evaluated after the first round's update.

A cell is compared only where every one of its reference draws had a gap
of at least ``tie_margin`` between the two best Gumbel-perturbed
log-probabilities: below that, rounding and not the algorithm decides
which device is drawn, and the cell's later records follow a different
schedule. How many cells that leaves out is printed with the numbers.
"""
from __future__ import annotations

import numpy as np

GAP_FIELDS = ("grad_norm", "e_com", "e_var")
EVAL_FIELDS = ("eval0_loss", "eval0_acc")


def _gap(prog, ref) -> float:
    prog = np.asarray(prog, np.float64)
    ref = np.asarray(ref, np.float64)
    if prog.size == 0:
        return 0.0
    floor = np.median(np.abs(ref))
    den = np.maximum(np.abs(ref), floor)
    gap = np.abs(prog - ref) / np.where(den > 0, den, 1.0)
    # a non-finite program record is as far off as a record can be
    return float(np.max(np.where(np.isfinite(prog), gap, np.inf)))


def compare(prog: dict, ref: dict, tie_margin: float) -> dict:
    """``prog`` and ``ref`` map each field to per-cell arrays: ``(cells,
    rounds)`` for ``n_scheduled`` and :data:`GAP_FIELDS`, ``(cells,)`` for
    :data:`EVAL_FIELDS`. Returns the numbers compared and the cell counts."""
    keep = np.asarray(ref["margin"]) >= tie_margin
    out = {
        "n_scheduled_mismatch": int(np.sum(
            np.asarray(prog["n_scheduled"]) != np.asarray(ref["n_scheduled"])
        )),
    }
    for f in GAP_FIELDS:
        out[f"{f}_gap"] = _gap(np.asarray(prog[f])[keep], np.asarray(ref[f])[keep])
    for f in EVAL_FIELDS:
        out[f"{f}_gap"] = _gap(np.asarray(prog[f])[keep], np.asarray(ref[f])[keep])
    out["cells_compared"] = int(np.sum(keep))
    out["cells_tied"] = int(np.sum(~keep))
    return out


def merge(readings: list[dict]) -> dict:
    """Fold the readings of several sweeps: worst gap, summed counts."""
    out = {}
    for r in readings:
        for k, v in r.items():
            if k.startswith("cells_") or k == "n_scheduled_mismatch":
                out[k] = out.get(k, 0) + v
            else:
                out[k] = max(out.get(k, 0.0), v)
    return out


def verdict(numbers: dict, limits: dict) -> tuple[bool, list[dict]]:
    """``correct`` and, for every number that has a limit, ``{name, value,
    limit}``. A number with no limit is not compared; no cells compared is
    not correct."""
    rows = [
        {"name": k, "value": numbers[k], "limit": limits[k]}
        for k in sorted(limits) if k in numbers
    ]
    ok = all(r["value"] <= r["limit"] for r in rows)
    ok = ok and numbers.get("cells_compared", 0) > 0
    return ok and all(k in numbers for k in limits), rows
