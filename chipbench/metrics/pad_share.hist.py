"""Padding among the edge slots the history cell's fixpoint sweeps executed, as a percentage.

The ``pad`` counters (sentinel slots: block padding and the all-sentinel
rows of masked lanes) over the ``slots`` counters of the program's
``cg.launch`` and ``cg.anchor.*`` spans in the traced window
(``spans.py``). Moves ``history_query_s``.
"""

import spans


def read(run):
    return spans.slot_share(spans.of_run(run), "pad")
