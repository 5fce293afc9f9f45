"""The comparison that decides ``correct`` for a training cell.

The program's readings are taken from the compiled step and its state as
the window drives them; the reference's from ``references/train.py``.  A
*unit* is one layer's slice of a stacked parameter, or an unstacked one.

- ``loss_gap``: the largest relative gap of the first steps' losses;
  ``loss_gap_first`` the first step's alone, steady from seed to seed
  where rounding compounds over the later steps.
- ``grad_gap``: the first step's gradient as the optimizer got it (its
  first moment over 1 - beta1) against the reference's clipped gradient:
  per unit the gap of the two norms, over the reference's norm of that
  unit or of the median unit, whichever is larger; the worst unit.
- ``grad_gap_median``: the median unit's gap of the same, steady where one
  small unit's rounding swings the worst.
- ``update_gap``: the same as ``grad_gap`` for the change of the
  parameters over the first steps.  Units whose raw reference gradient
  is under a thousandth of the median unit's are left out: Adam moves
  them by round-off alone.
- ``reshard_mismatch``: units of the state whose exact fingerprint
  differs after a resize from before it (elastic cells; limit 0).

A cell compares the numbers its ``limits/<workload>.json`` names; the
others are printed as readings.
"""
from __future__ import annotations

import statistics
from typing import Callable, Dict, List, Optional, Tuple

ROUNDOFF_GRAD = 1e-3


def unit_gaps(prog: Dict[str, float], ref: Dict[str, float],
              keep: Optional[Callable[[str], bool]] = None
              ) -> Dict[str, float]:
    """Per unit: the gap of the two norms over the larger of the unit's
    and the median unit's reference norm."""
    names = [n for n in ref if keep is None or keep(n)]
    if not names:
        raise ValueError("no unit to compare")
    missing = [n for n in names if n not in prog]
    if missing:
        raise ValueError(f"the program has no reading of {missing[:3]}")
    med = statistics.median(ref[n] for n in names)
    out = {}
    for n in names:
        base = max(ref[n], med)
        out[n] = abs(prog[n] - ref[n]) / base if base > 0 else 0.0
    return out


def _worst(gaps: Dict[str, float]) -> Tuple[float, str]:
    at = max(gaps, key=gaps.get)
    return gaps[at], at


def numbers(prog: dict, ref: dict, beta1: float) -> Dict[str, dict]:
    """``prog``: losses, mu (first moment norms after step 1), delta.
    ``ref``: the dict ``references.train.train`` returns."""
    lg = [abs(a - b) / abs(b) for a, b in zip(prog["losses"], ref["losses"])]
    if len(lg) != len(ref["losses"]) or not lg:
        raise ValueError("the program reported too few losses")
    k = max(range(len(lg)), key=lambda i: lg[i])
    grad = unit_gaps({n: v / (1.0 - beta1) for n, v in prog["mu"].items()},
                     ref["grad"])
    med_raw = statistics.median(ref["grad_raw"].values())
    keep = lambda n: ref["grad_raw"][n] >= ROUNDOFF_GRAD * med_raw  # noqa
    upd = unit_gaps(prog["delta"], ref["delta"], keep)
    g, g_at = _worst(grad)
    u, u_at = _worst(upd)
    left_out = sorted(n for n in ref["grad_raw"] if not keep(n))
    return {"loss_gap": {"value": lg[k], "at": f"step {k + 1}"},
            "loss_gap_first": {"value": lg[0], "at": "step 1"},
            "grad_gap": {"value": g, "at": g_at},
            "grad_gap_median": {"value": statistics.median(grad.values()),
                                "at": f"median of {len(grad)} units"},
            "update_gap": {"value": u, "at": u_at, "left_out": left_out}}


def judge(nums: Dict[str, dict], limits: Dict[str, float]
          ) -> Tuple[bool, Dict[str, dict], List[str]]:
    """The numbers the cell's limits name, each against its limit:
    ``(correct, checks, lines)``; the others are printed as readings."""
    if not set(limits) & set(nums):
        raise ValueError("the cell's limits name none of its numbers")
    ok, checks, lines = True, {}, []
    for name, n in nums.items():
        v = n["value"]
        if name not in limits:
            lines.append(f"reading {name} {v!r} (not compared) "
                         f"({n.get('at', '')})")
            continue
        lim = limits[name]
        passed = v <= lim and v == v
        ok = ok and passed
        checks[name] = {"value": v, "limit": lim}
        lines.append(f"check {name} {v!r} limit {lim!r} "
                     f"{'ok' if passed else 'FAIL'} ({n.get('at', '')})")
    return ok, checks, lines
