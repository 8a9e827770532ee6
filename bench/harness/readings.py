"""What the per-layer readers (``bench/metrics/<metric>.py``) are given:
the window's clock, batches and configuration with its reference module
(which alone counts the configuration's work), the reduced device trace
(traced runs) and the program's own spans.  Each reader is a module with
``read(ctx) -> float | None``; None leaves the metric out of the line."""
from __future__ import annotations

import dataclasses
import importlib.util
from pathlib import Path
from typing import Any, Dict, List, Optional, Tuple

from harness import xplane

METRICS = Path(__file__).resolve().parents[1] / "metrics"


@dataclasses.dataclass
class Context:
    config: Dict                       # the configuration file
    ref: Any                           # its reference module
    peaks: Dict[str, float]
    chips: int
    n_nano: int                        # ping-pong nano-batches per step
    steps: int
    window_s: float
    batches: List[Dict]                # the window's batches (host arrays)
    trace: Optional[xplane.Trace] = None
    window_ps: Optional[Tuple[int, int]] = None
    spans: List[Any] = dataclasses.field(default_factory=list)

    def kernel_roofline(self, kernel: str, backward: bool,
                        passes: int) -> Optional[float]:
        """% of the roofline that the calls of ``kernel`` reach: the least
        time the chips could take for the work the window's batches need
        (FLOPs at the bf16 peak or bytes at the HBM peak, whichever is
        longer) over the calls' summed device time.  ``passes`` kernel
        calls make one call's worth of work (2 for a backward split into
        dq and dk/dv passes).  The layers that make calls and the work of
        one call are the reference module's (``ca_layers``,
        ``ca_call_work``).  None where the trace has no such call, no
        layer makes one, or the calls do not divide evenly over layers
        and nano-batches."""
        if self.trace is None or not self.trace.devices:
            return None
        lo, hi = self.window_ps
        t_ps, n = 0, 0
        for dev in self.trace.devices:
            t, k = xplane.kernel_time(dev, kernel, lo, hi)
            t_ps, n = t_ps + t, n + k
        c = self.config
        ca_layers = self.ref.ca_layers(c)
        per = passes * ca_layers * self.n_nano * self.chips * self.steps
        if n == 0 or t_ps == 0 or per == 0 or n % per:
            return None
        calls = n // per          # calls per layer, nano-batch and device
        work_f = work_b = 0.0
        for b in self.batches:
            w = self.ref.ca_call_work(c, b["segment_ids"], b["positions"],
                                      backward=backward)
            work_f += w["flops"]
            work_b += w["bytes"]
        layers = ca_layers * calls
        need_s = max(layers * work_f / self.peaks["bf16_flops"],
                     layers * work_b / self.peaks["hbm_bytes_per_s"])
        return 100.0 * need_s / (t_ps / 1e12)


def reader(name: str):
    path = METRICS / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no reader for metric {name!r} at {path}")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    if not callable(getattr(mod, "read", None)):
        raise TypeError(f"{path} has no read(ctx)")
    return mod
