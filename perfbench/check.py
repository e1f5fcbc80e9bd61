"""The comparison that decides ``correct`` for a training cell: the
program's first three steps against the plain reference's.

Each side gives ``{"loss": [l1, l2, l3], "grad": {leaf: norm}, "change":
{leaf: norm}}``: each step's loss, the norm of the first gradient as the
optimizer got it, and the norm of each leaf's change over the three steps.
A leaf's gap is the distance between the two sides' norms (not the norm of
a difference) over the reference's norm of that leaf or of the median
leaf, whichever is larger. Leaves whose reference gradient is under a
thousandth of the median leaf's (a one-expert gate under softmax, a fixed
gamma, a gamma whose scale the next batch norm takes out again) carry
round-off alone in any finite precision and are left out.

``loss_gap`` reads the worst of the three steps and ``loss_gap_first`` the
first, which weights rounded after an update cannot touch. ``grad_gap``
and ``change_gap`` read the worst leaf, ``*_median`` the median leaf: where
small leaves are sums that all but cancel (batch-norm leaves of a freshly
initialised ResNet) or carry the quantisation of a bfloat16 update (the
LM's plain SGD), the worst leaf reads tens of percent in sound runs and
only the median is steady from seed to seed (PERF.md, PR 25). A workload's
``limits`` say which numbers are held.
"""
import math
import statistics


def _leaf_gaps(got, want, leaves):
    floor = statistics.median(want[k] for k in leaves)
    gaps = {k: abs(got[k] - want[k]) / max(want[k], floor) for k in leaves}
    worst = max(leaves, key=lambda k: (math.isnan(gaps[k]), gaps[k]))
    return (gaps[worst], worst), (statistics.median(gaps.values()), "median")


def gaps(got, want):
    """name -> (gap, the leaf or step it was read at)."""
    losses = [abs(g - w) / abs(w) for g, w in zip(got["loss"], want["loss"])]
    if len(got["loss"]) != len(want["loss"]) or not all(
            math.isfinite(x) for x in losses):
        losses = [float("inf")]
    step = max(range(len(losses)), key=losses.__getitem__)
    floor = statistics.median(want["grad"].values())
    moved = sorted(k for k in want["grad"] if want["grad"][k] >= 1e-3 * floor)
    grad, grad_median = _leaf_gaps(got["grad"], want["grad"], moved)
    change, change_median = _leaf_gaps(got["change"], want["change"], moved)
    return {"loss_gap_first": (losses[0], "step1"),
            "loss_gap": (losses[step], "step%d" % (step + 1)),
            "grad_gap": grad, "grad_gap_median": grad_median,
            "change_gap": change, "change_gap_median": change_median}


def compare(got, want, limits):
    """(correct, {name: {"value", "limit", "at"}}); a number whose limit is
    null is reported and not held."""
    out, correct = {}, True
    for name, (gap, at) in gaps(got, want).items():
        limit = limits.get(name)
        out[name] = {"value": gap, "limit": limit, "at": at}
        if limit is not None and not gap <= limit:
            correct = False
    return correct, out
