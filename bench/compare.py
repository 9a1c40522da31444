"""The comparison that decides ``correct``: what the timed ``run_round``
path produced in its first rounds against the plain reference's rounds
from the same seed.

Numbers (a cell compares those that ``bench/limits/<cell>.json`` gives a
limit):

* ``mask_diff``    -- clients whose delivered bit differs in the first
  round (Eq. 10 selection and delivery); exact. Later rounds select by
  reputation at a margin that drift through the rounds' SGD steps moves
  (sound top-k runs flip up to two clients in rounds 2-3), so their
  masks are not compared bit for bit (see PERF.md).
* ``wire_diff``    -- rounds x {intra bytes, cross bytes, dollars} that
  differ, over every compared round (Eq. 1-4 in float64); exact.
* ``update_gap``   -- the first round's server update (w0 - w1), leaf by
  leaf: the gap between the program's norm and the reference's, over the
  larger of that leaf's and the median leaf's reference norm; the worst
  leaf.
* ``change_gap``   -- the same for w0 - w_R after the last compared round.
* ``update_cos``   -- the first round's server update, leaf by leaf: one
  less the cosine between the program's and the reference's; the worst
  leaf. Eq. 12 rescales every client update to its cloud's reference
  norm, so a fault in the clients' training alone moves the update's
  direction more than its norm.
* ``update_cos_all`` -- the same over the whole update at once: steadier
  from seed to seed where one small leaf's cosine swings (the top-k
  codec's fc1 bias, PERF.md).
* ``rep_gap``      -- the largest gap of the first round's reputations,
  over the reference's largest (Eq. 7-9).

Where uplinks are compressed (the top-k codec with error feedback):

* ``residual_gap`` -- the gap of the client error-feedback table's norm
  after the first round, over the reference's norm;
* ``update_gap_r2`` -- the second round's server update (w1 - w2) as
  ``update_gap``;
* ``residual_gap_r2`` -- the client table's norm gap after the second
  round.

The table is zero before the first round, so only the second round
reads what the first left in it.

Leaves whose reference change is under a thousandth of the median leaf's
are left out of the norm gaps and cosines: they move by round-off alone.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

NEGLIGIBLE = 1e-3


def _leaf_norms(a: Dict[str, np.ndarray], b: Dict[str, np.ndarray]
                ) -> Dict[str, float]:
    return {k: float(np.linalg.norm((np.asarray(a[k], np.float64)
                                     - np.asarray(b[k], np.float64)).ravel()))
            for k in a}


def norm_gap(prog_before, prog_after, ref_before, ref_after) -> float:
    """Worst leaf's gap of change norms, as set out above (a common
    factor such as server_lr cancels)."""
    p = _leaf_norms(prog_before, prog_after)
    r = _leaf_norms(ref_before, ref_after)
    med = float(np.median(list(r.values())))
    gaps = [abs(p[k] - r[k]) / max(r[k], med)
            for k in r if r[k] >= NEGLIGIBLE * med]
    return float(max(gaps))


def _cos_dist(a: np.ndarray, b: np.ndarray) -> float:
    a = np.asarray(a, np.float64).ravel()
    b = np.asarray(b, np.float64).ravel()
    return 1.0 - float(a @ b) / max(float(np.linalg.norm(a)
                                          * np.linalg.norm(b)), 1e-30)


def leaf_cos(prog_before, prog_after, ref_before, ref_after
             ) -> Dict[str, float]:
    """One less the cosine of each leaf's change, program against
    reference, for the leaves that move (see above); key ``all`` is the
    whole update's."""
    def delta(a, b):
        return {k: (np.asarray(a[k], np.float64)
                    - np.asarray(b[k], np.float64)).ravel() for k in a}
    p = delta(prog_before, prog_after)
    r = delta(ref_before, ref_after)
    rn = {k: float(np.linalg.norm(v)) for k, v in r.items()}
    med = float(np.median(list(rn.values())))
    out = {k: _cos_dist(p[k], r[k]) for k in r if rn[k] >= NEGLIGIBLE * med}
    out["all"] = _cos_dist(np.concatenate([p[k] for k in sorted(p)]),
                           np.concatenate([r[k] for k in sorted(r)]))
    return out


def _rel(prog: float, ref: float) -> float:
    return abs(prog - ref) / max(ref, 1e-30)


def numbers(prog: List[Dict], ref: List[Dict], compressed: bool) -> Dict[str, float]:
    rounds = len(ref) - 1
    first_rep = ref[1]["rep"]
    cos = leaf_cos(prog[0]["params"], prog[1]["params"],
                   ref[0]["params"], ref[1]["params"])
    out = {"mask_diff": float(np.sum(prog[1]["delivered"] != ref[1]["delivered"])),
           "wire_diff": float(sum(prog[t][q] != ref[t][q]
                                  for t in range(1, rounds + 1)
                                  for q in ("intra_bytes", "cross_bytes",
                                            "dollars"))),
           "update_gap": norm_gap(prog[0]["params"], prog[1]["params"],
                                  ref[0]["params"], ref[1]["params"]),
           "change_gap": norm_gap(prog[0]["params"], prog[rounds]["params"],
                                  ref[0]["params"], ref[rounds]["params"]),
           "update_cos": max(v for k, v in cos.items() if k != "all"),
           "update_cos_all": cos["all"],
           "rep_gap": float(np.max(np.abs(prog[1]["rep"] - first_rep))
                            / np.max(first_rep))}
    if compressed:
        out.update(
            residual_gap=_rel(prog[1]["res_client_norm"],
                              ref[1]["res_client_norm"]),
            update_gap_r2=norm_gap(prog[1]["params"], prog[2]["params"],
                                   ref[1]["params"], ref[2]["params"]),
            residual_gap_r2=_rel(prog[2]["res_client_norm"],
                                 ref[2]["res_client_norm"]))
    return out


def leaf_gaps(prog: List[Dict], ref: List[Dict], t: int) -> Dict[str, float]:
    """Signed gap of each leaf's change norm over rounds 1..t, over the
    larger of its and the median leaf's reference norm; key ``all`` is
    the whole update's. For the record, as ``later_rounds``."""
    p = _leaf_norms(prog[0]["params"], prog[t]["params"])
    r = _leaf_norms(ref[0]["params"], ref[t]["params"])
    med = float(np.median(list(r.values())))
    out = {k: (p[k] - r[k]) / max(r[k], med) for k in r}
    out["all"] = (float(np.sqrt(sum(v * v for v in p.values())))
                  / float(np.sqrt(sum(v * v for v in r.values()))) - 1.0)
    return out


def later_rounds(prog: List[Dict], ref: List[Dict]) -> Dict[str, float]:
    """What the rounds after the first read, for the record: masks and
    reputations there are not compared (see ``mask_diff``)."""
    rounds = len(ref) - 1
    return {"mask_diff_later": float(sum(
                np.sum(prog[t]["delivered"] != ref[t]["delivered"])
                for t in range(2, rounds + 1))),
            "rep_gap_later": max(
                float(np.max(np.abs(prog[t]["rep"] - ref[t]["rep"]))
                      / np.max(ref[t]["rep"])) for t in range(2, rounds + 1))}


def verdict(nums: Dict[str, float], limits: Dict[str, float]) -> bool:
    """Every number the cell's limits name is there, finite and within
    its limit. A cell compares the numbers its limits file names."""
    return all(k in nums and np.isfinite(nums[k]) and nums[k] <= lim
               for k, lim in limits.items())
