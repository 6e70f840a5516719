"""Host milliseconds per view dispatching the engine's filter.

The total of the program's ``engine.filter`` spans over the window: the
host side of each ``_filter_chunk`` call in
``ReconstructionEngine.submit``, including any time the dispatch waits
for the device.  Divided by the views submitted.
"""

from harness.program_spans import per_view_ms


def read(run):
    return per_view_ms(run, "engine.filter")
