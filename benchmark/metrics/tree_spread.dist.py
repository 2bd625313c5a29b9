"""How unevenly the chips shared each solve's tree, in %: the mean over
the window's solves of 100 x (the largest chip's explored tree over the
chips' mean, less 1), from the per-chip `tree` counters of each
`DistResult`. 0 is an even split; with 4 chips, 300 is one chip doing
everything."""


def read(run):
    spreads = []
    for s in run.items:
        trees = s.get("chip_tree") or ()
        mean = sum(trees) / len(trees) if trees else 0
        if mean > 0:
            spreads.append(100.0 * (max(trees) / mean - 1.0))
    return sum(spreads) / len(spreads) if spreads else None
