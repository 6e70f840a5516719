"""Host milliseconds per view dispatching the engine's folds.

The self time of the program's ``engine.fold`` spans over the window:
the fold of the ready slots in ``ReconstructionEngine.step`` (the
kernel's launch with the slot's read and write, or the vmapped jnp
fold), including any time the dispatch waits for the device, and
without the ``planner.check`` spans inside it.  Divided by the views
submitted.
"""

from harness.program_spans import per_view_ms


def read(run):
    return per_view_ms(run, "engine.fold", "self_s")
