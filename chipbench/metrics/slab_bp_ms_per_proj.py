"""Device milliseconds of the back projection on the mesh per view.

The operations the traffic file's ``layers.backprojection`` pattern
matches on each chip (the Pallas kernel inside the sharded fold), as
the runner averages them over the cell's chips, per view folded: every
chip folds every view into its own slab, so this is one chip's time per
view, the mean over the chips.
"""


def read(run):
    if run.trace is None or not run.work["views"]:
        return None
    seconds = run.trace["layer_s"].get("backprojection", 0.0)
    return 1e3 * seconds / run.work["views"] if seconds > 0 else None
