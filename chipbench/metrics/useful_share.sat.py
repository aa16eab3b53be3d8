"""Useful over executed edge slots in the service's fixpoints, as a percentage (saturated cell).

The ``useful`` counters over the ``slots`` counters of the program's
``cg.launch`` spans (one per slide launch) and ``cg.anchor.*`` spans
(anchor rebuilds and hops) in the traced window (``spans.py``). Moves
``queries_per_s``: the chip's busy time goes to dense sweeps, most of
whose slots relax nothing.
"""

import spans


def read(run):
    return spans.slot_share(spans.of_run(run), "useful")
