"""Device milliseconds of the back projection per view, from the trace.

The back projection's operations are those the traffic file's
``layers.backprojection`` pattern matches: the Pallas kernel's events
on the streamed path, the ``_reconstruct_jit`` program on the one-shot
path.
"""


def read(run):
    if run.trace is None or not run.work["views"]:
        return None
    seconds = run.trace["layer_s"].get("backprojection", 0.0)
    return 1e3 * seconds / run.work["views"] if seconds > 0 else None
