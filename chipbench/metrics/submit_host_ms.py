"""Host milliseconds per view spent inside ``CTFrontDoor.submit``.

Read from the benchmark's own span around each ``submit`` call (host
clock), summed over the window and divided by the views submitted.  It
covers what the front door and the engine do on the host for a chunk:
the planner's check of first-seen stacks, the filter's and the fold's
dispatch, and the slot bookkeeping, and any time that dispatch blocks
until the device has finished the fold before it.  Where the device is
the bottleneck most of the span is that wait; the host's own share shows
as the trace's idle gaps under ``submit``.
"""


def read(run):
    return run.spans.mean_ms_per_unit("submit")
