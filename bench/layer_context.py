"""What a per-layer metric reader (`metrics/<name>.py`) is given: the
trace reduction of the traced window, the runner's records and counters
over that same window, the cell's configuration and traffic, the
published peaks of the device, and the counting functions."""
from __future__ import annotations

from bench import peaks


class LayerContext:
    def __init__(self, cell, runner, reduction, device_kind: str):
        self.cell = cell
        self.runner = runner
        self.trace = reduction
        self.model = cell.config["model"]
        self.config = cell.config
        self.traffic = cell.traffic
        self.counters = runner.counters
        self.peaks = peaks.lookup(device_kind)

    @property
    def window_s(self) -> float:
        return self.trace.window_s

    def share_of_peak_flops(self, flops: float) -> float:
        """Percent of the chip's bf16 peak that `flops` over the window
        would take."""
        return 100.0 * flops / self.window_s / self.peaks["bf16_flops_per_s"]

    def roofline_share(self, flops: float, nbytes: float,
                       seconds: float):
        """Percent: the least time the chip could take for the work
        (larger of FLOPs over peak FLOP/s and bytes over peak bytes/s)
        over the time it took. None when nothing ran."""
        if seconds <= 0 or flops <= 0:
            return None
        least = max(flops / self.peaks["bf16_flops_per_s"],
                    nbytes / self.peaks["hbm_bytes_per_s"])
        return 100.0 * least / seconds
