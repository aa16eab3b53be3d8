"""Useful over executed edge slots in the history cell's fixpoints, as a percentage.

The ``useful`` counters (relaxations from frontier vertices over real
edges) over the ``slots`` counters (edge slots the sweeps executed) of the
program's ``cg.launch`` and ``cg.anchor.*`` spans in the traced window
(``spans.py``). Moves ``history_query_s``: a dense sweep pays for every
slot, whatever the frontier holds.
"""

import spans


def read(run):
    return spans.slot_share(spans.of_run(run), "useful")
