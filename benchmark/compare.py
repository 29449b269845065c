"""The comparison that decides ``correct``: the program's readings beside
the plain reference's, as plain numbers that each get a limit.

Norms are compared by the worst leaf: the gap between the program's norm
and the reference's (not the norm of a difference), against the
reference's norm of that leaf or of the median leaf, whichever is larger,
since some gradients are all but zero.
"""
import statistics

#: a leaf whose reference gradient is under this share of the median
#: leaf's is nought to rounding (a bias in front of a BatchNorm); it moves
#: by round-off alone and is left out of the change
NOUGHT = 1e-3
#: what a reference's ``train_steps`` returns, in its order
TRAINING_READINGS = ("losses", "grad_norms", "update_norms", "row_losses")


def norm_gaps(got, want, leaves=None):
    """``(worst gap, its leaf, median gap)`` over ``leaves`` (all of
    ``want`` by default)."""
    leaves = list(want) if leaves is None else leaves
    floor = statistics.median(want[k] for k in leaves)
    gaps = {k: abs(got[k] - want[k]) / max(want[k], floor) for k in leaves}
    worst = max(gaps, key=gaps.get)
    return gaps[worst], worst, statistics.median(gaps.values())


def training(seen, want, matrices=()):
    """Numbers for a training cell from the program's first steps
    (``seen``) and the reference's (``want``): each with ``losses``,
    ``grad_norms`` and ``update_norms`` (the last two by leaf) and
    ``row_losses``, step 1's loss row by row. ``matrices`` names the
    leaves of more than one axis (kernels and matrices): the
    ``*_weights`` numbers are medians over them alone, since a vector's
    norm (a BatchNorm gain or offset) sums a few hundred terms and swings
    from seed to seed by more than a lower precision moves it."""
    out = {}
    rows = [abs(a - b) / abs(b) for a, b in zip(seen["row_losses"],
                                                want["row_losses"])]
    out["row_loss_gap_step1"] = (sum(r * r for r in rows) / len(rows)) ** 0.5
    out["row_loss_gap_step1_max"] = max(rows)
    for i, (a, b) in enumerate(zip(seen["losses"], want["losses"])):
        out[f"loss_gap_step{i + 1}"] = abs(a - b) / abs(b)
    g = want["grad_norms"]
    out["grad_norm_gap"], out["grad_norm_gap_leaf"], \
        out["grad_norm_gap_median"] = norm_gaps(seen["grad_norms"], g)
    floor = NOUGHT * statistics.median(g.values())
    moved = [k for k in g if g[k] >= floor]
    out["update_norm_gap"], out["update_norm_gap_leaf"], \
        out["update_norm_gap_median"] = norm_gaps(
            seen["update_norms"], want["update_norms"], moved)
    out["leaves_left_out"] = len(g) - len(moved)
    matrices = [k for k in matrices if k in moved]
    if matrices:
        out["grad_norm_gap_weights"] = norm_gaps(
            seen["grad_norms"], g, matrices)[2]
        out["update_norm_gap_weights"] = norm_gaps(
            seen["update_norms"], want["update_norms"], matrices)[2]
    return out
