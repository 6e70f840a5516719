"""Host milliseconds per view in the program's planner checks.

The total of the program's ``planner.check`` spans over the window: the
host planner's coverage checks of stacks not seen before
(``core/clipping.py`` through ``validate_strip_opts`` and the kernel's
validators), wherever the streamed path runs them; memoised stacks cost
no span.  Divided by the views submitted.
"""

from harness.program_spans import per_view_ms


def read(run):
    return per_view_ms(run, "planner.check")
