"""Host milliseconds per view placing chunks on the mesh's chips.

The total of the program's ``engine.put`` spans (the copy of each
submitted chunk onto every chip of the engine's mesh, in
``ReconstructionEngine.submit``) over the window, per view submitted.
``None`` where the window recorded no such span.
"""

from harness.program_spans import per_view_ms


def read(run):
    try:
        from repro import obs
    except ImportError:
        return None
    if "engine.put" not in obs.summary():
        return None
    return per_view_ms(run, "engine.put")
