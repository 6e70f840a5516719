"""The chip benchmark's own yardstick: data, reference, counts, traces.

Nothing here imports the program under test except :mod:`.program`,
which builds the program's geometry and plan for the entries
(``chipbench/entries/``, the only code that calls the program), and
:mod:`.runner`, which places the program's compile cache.
"""
