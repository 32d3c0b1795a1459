"""The comparison that decides ``correct``: numbers, each beside a limit
of its own, printed in every run.  The limits are data (the cell's traffic
file, keyed by configuration); how each was set is in PERF.md."""

import fnmatch
import math

import numpy as np


class Comparison:
    def __init__(self, limits):
        self.limits = limits
        self.rows = []

    def add(self, name, value, limit_key=None):
        limit = self.limits[limit_key or name]
        ok = math.isfinite(value) and value <= limit
        self.rows.append((name, float(value), float(limit), ok))
        print(f"check {name} {value:.6g} limit {limit:.6g} "
              f"{'ok' if ok else 'FAILED'}", flush=True)
        return ok

    def require(self, name, condition, detail=""):
        self.rows.append((name, 0.0 if condition else 1.0, 0.0,
                          bool(condition)))
        print(f"check {name} {'ok' if condition else 'FAILED'} {detail}",
              flush=True)
        return bool(condition)

    @property
    def correct(self):
        return bool(self.rows) and all(ok for *_, ok in self.rows)


def leaf_gaps(program_norms, reference_norms, own_scale=False):
    """Every leaf's gap between the program's norm and the reference's,
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger (some gradients are all but zero) — or, with
    ``own_scale``, against the leaf's own reference norm alone.  None where
    the two sides have different leaves."""
    program = np.asarray(program_norms, np.float64)
    reference = np.asarray(reference_norms, np.float64)
    if program.shape != reference.shape:
        return None
    if own_scale:
        scale = np.maximum(reference, np.finfo(np.float64).tiny)
    else:
        scale = np.maximum(reference, np.median(reference))
    return np.abs(program - reference) / scale


def left_out(paths, patterns):
    """Which of the leaves ``paths`` a comparison leaves out: those that
    match one of ``patterns`` (``fnmatch``), the leaves the configuration's
    file names with the reason for each."""
    return np.asarray([any(fnmatch.fnmatchcase(path, p) for p in patterns)
                       for path in paths], bool)


def worst_and_median_gap(program_norms, reference_norms, skip=None):
    """(the widest leaf gap, the median leaf's gap) over the leaves that
    ``skip`` (a boolean per leaf) does not leave out.  The widest finds a
    fault in one leaf.  The median — each leaf against its own reference
    norm, which the median can afford: an all but zero leaf is one leaf —
    averages over the leaves' noise and finds what moves them all (a part
    of the batch left out, an exchange summed for averaged, another
    learning rate)."""
    gaps = leaf_gaps(program_norms, reference_norms)
    if gaps is None:
        return math.inf, math.inf
    own = leaf_gaps(program_norms, reference_norms, own_scale=True)
    if skip is not None:
        keep = ~np.asarray(skip, bool)
        gaps, own = gaps[keep], own[keep]
    return float(gaps.max()), float(np.median(own))


def leaf_norms_flat(flat, sizes):
    """Per-leaf L2 norms of a 1-D array that holds the leaves end to end."""
    flat = np.asarray(flat, np.float32).reshape(-1)
    bounds = np.concatenate([[0], np.cumsum(sizes)])
    assert bounds[-1] == flat.size, (bounds[-1], flat.size)
    # float32 dot products (pairwise sums, good to ~1e-6 of a norm): a
    # float64 copy of 1.5 G elements would cost seconds of every set-up
    return np.asarray([math.sqrt(float(np.dot(flat[a:b], flat[a:b])))
                       for a, b in zip(bounds[:-1], bounds[1:])])


def compare_training(comparison, program, reference, paths, named):
    """The first steps of the timed step against the reference's: each
    step's loss, the first gradient as the optimizer got it, the weights'
    change after the last step — norms by the worst leaf and, the
    gradient's, by the median leaf.  ``named`` is the configuration file's ``check`` block ({} where
    it has none: every leaf counts, and there are no head numbers):
    ``leaves_left_out`` maps "grad_norms" / "delta_norms" to the patterns
    of the leaves (``paths``) that number leaves out, and ``head_leaves``
    names the leaves every labelled position feeds, whose gradient — and
    so whose change under Adam — is steady from one draw of the dropout
    masks to the next: they get numbers, and tight limits, of their own."""
    for n, (p, r) in enumerate(zip(program["losses"], reference["losses"])):
        comparison.add(f"loss_gap_step{n + 1}", abs(p - r) / abs(r),
                       "loss_gap")
    skip = named.get("leaves_left_out", {})
    for key, name in (("grad_norms", "grad_norm"),
                      ("delta_norms", "delta_norm")):
        worst, median = worst_and_median_gap(
            program[key], reference[key],
            left_out(paths, skip[key]) if key in skip else None)
        comparison.add(f"{name}_gap", worst)
        if key == "grad_norms":
            # not for the change: after three Adam steps a run's encoder
            # leaves all carry one common factor that follows the dropout
            # masks (PERF.md, section 2); the head's leaves hold the faults
            # a median would be there for
            comparison.add(f"{name}_median_gap", median)
        if named.get("head_leaves"):
            head = left_out(paths, named["head_leaves"])
            comparison.add(f"head_{name}_gap", worst_and_median_gap(
                program[key], reference[key], ~head)[0])
    return comparison.correct
