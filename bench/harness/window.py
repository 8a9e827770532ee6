"""The measured window, driven from the trainer's own loop.

``repro.train.trainer.train`` pulls one batch per step from
``session.attach_plans(...)`` on its own thread, after the previous
step's metrics have been read back (``log_every=1`` syncs every step).
``WindowedSession`` is the program's ``CADSession`` with one seam: its
``attach_plans`` plans the benchmark's own batches (through the
program's planner and prefetcher) in place of the pipeline's, and hands
each one out through ``Window.pulls``, which keeps the clock:

* pull 0 is before the first step, which compiles;
* at pull 1 the optimizer state holds one update: the first gradient
  the optimizer got is read from it;
* at pull 3 the parameters hold three updates and the history three
  losses: they are read, then the window opens;
* the window closes at the first pull at least ``seconds`` after it
  opened, i.e. at the end of the step that was running, reads every
  step's loss, and stops the trainer by raising ``WindowClosed`` out of
  its batch pull.

The readings are taken from ``train``'s frame, which calls the pull:
the trainer offers no other way to see its state between steps.
"""
from __future__ import annotations

import dataclasses
import inspect
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from repro.cad import CADSession

WARM_STEPS = 3


def _trainer() -> Dict[str, Any]:
    """The locals of ``train``, which is pulling a batch from ``pulls``."""
    return inspect.currentframe().f_back.f_back.f_locals


class WindowClosed(Exception):
    """Raised out of the trainer's batch pull when the window is over."""


class Window:
    def __init__(self, batches, seconds: float, *,
                 on_first_update: Callable[[Any], Dict],
                 on_third_update: Callable[[Any, List[Dict]], Dict],
                 on_open: Callable[[], None] = lambda: None,
                 on_close: Callable[[], None] = lambda: None):
        self.batches = batches
        self.seconds = float(seconds)
        self._first = on_first_update
        self._third = on_third_update
        self._on_open, self._on_close = on_open, on_close
        self.readings: Dict[str, Any] = {}
        self.t_open: Optional[float] = None
        self.t_close: Optional[float] = None
        self.steps = 0                   # whole steps inside the window
        self.losses: List[float] = []    # every step's loss, at the close
        self.window_batches: List[Dict[str, np.ndarray]] = []
        self.warm_batches: List[Dict[str, np.ndarray]] = []

    def pulls(self, planned):
        """Wrap the session's planned stream; runs on the trainer's
        thread, between its steps."""
        try:
            for i, batch in enumerate(planned):
                if i == 1:
                    self.readings.update(
                        self._first(_trainer()["opt_state"]))
                if i == WARM_STEPS:
                    state = _trainer()
                    self.readings.update(self._third(state["params"],
                                                     state["history"]))
                    del state
                    self._on_open()
                    self.t_open = time.perf_counter()
                elif i > WARM_STEPS and \
                        time.perf_counter() - self.t_open >= self.seconds:
                    self.t_close = time.perf_counter()
                    self.steps = i - WARM_STEPS
                    self._on_close()
                    self.losses = [h["loss"] for h in _trainer()["history"]]
                    raise WindowClosed
                host = {k: batch[k] for k in ("tokens", "labels",
                                              "segment_ids", "positions")}
                (self.window_batches if i >= WARM_STEPS
                 else self.warm_batches).append(host)
                yield batch
        finally:
            planned.close()

    @property
    def window_s(self) -> float:
        return self.t_close - self.t_open


@dataclasses.dataclass(frozen=True)
class WindowedSession(CADSession):
    """The program's attention session, planning the window's batches."""
    window: Any = None

    def attach_plans(self, batch_iter, *, prefetch=None):
        close = getattr(batch_iter, "close", None)
        if close is not None:
            close()                  # the pipeline's stream is not used
        planned = super().attach_plans(self.window.batches,
                                       prefetch=prefetch)
        return self.window.pulls(planned)
