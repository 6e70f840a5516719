"""Host milliseconds per view in the front door and engine themselves.

The self time of the program's ``frontdoor.submit`` spans over the
window: what ``CTFrontDoor.submit`` does on the host outside the
planner's checks and the filter's and fold's dispatch (admission, slot
bookkeeping, staging the filtered views, retirement).  With
``planner_ms_per_view``, ``filter_dispatch_ms_per_view`` and
``fold_dispatch_ms_per_view`` it adds up to the program's time in
``submit``.  Divided by the views submitted.
"""

from harness.program_spans import per_view_ms


def read(run):
    return per_view_ms(run, "frontdoor.submit", "self_s")
