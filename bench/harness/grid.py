"""The CA-server grid counts the program attaches to its step spans:
each ``train.dispatch`` span carries the plan's ``ca_*_cells`` and
``ca_*_cells_live`` (``repro.cad.session.grid_counts``)."""
from typing import Any, Iterable, Optional


def live_pct(spans: Iterable[Any], key: str) -> Optional[float]:
    """100 x the summed ``<key>_live`` over the summed ``<key>`` of the
    ``train.dispatch`` spans, or None where no span carries them."""
    cells = live = 0
    for s in spans:
        args = s.args or {}
        if s.name == "train.dispatch" and key in args:
            cells += args[key]
            live += args[key + "_live"]
    return 100.0 * live / cells if cells else None

