"""Device milliseconds of the FDK filter per view, from the trace.

The filter's operations are those the traffic file's ``layers.filter``
pattern matches in ``<module>/<op>``: the engine's ``_filter_chunk``
program, or every program of a one-shot call outside the back
projection's.
"""


def read(run):
    if run.trace is None or not run.work["views"]:
        return None
    seconds = run.trace["layer_s"].get("filter", 0.0)
    return 1e3 * seconds / run.work["views"] if seconds > 0 else None
