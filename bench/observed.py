"""What a run observed, handed to every metric reader, and the loader
that finds a reader by the metric's name (``bench/metrics/<name>.py``,
a module with ``read(obs) -> float | None``)."""
from __future__ import annotations

import dataclasses
import importlib.util
from pathlib import Path
from typing import Optional

import numpy as np

METRICS_DIR = Path(__file__).resolve().parent / "metrics"


@dataclasses.dataclass
class Observed:
    dims: dict                 # model dimensions (bench/cells.model_dims)
    chips: int
    max_slots: int
    peaks: dict                # bench/peaks.py entry of the device kind
    setup_s: float
    window: object             # bench/serve.Window
    ctx_log: list              # (host time, replica, offset, tokens)
    trace: Optional[object] = None   # bench/trace.Trace of the traced span
    failed_ttft_s: float = 0.0       # wait charged to a request never served

    # ---------------------------------------------------------- helpers
    @property
    def seconds(self) -> float:
        return self.window.t1 - self.window.t0

    @property
    def records(self) -> list:
        return self.window.records

    def traced_span(self) -> Optional[tuple[float, float]]:
        tw = self.window.trace
        if tw is None or tw.t_begin is None or tw.t_end is None:
            return None
        return tw.t_begin, tw.t_end

    def calls(self, suffix: str) -> list:
        return [c for log in self.window.calls for c in log
                if c["fn"].endswith(suffix)]


def p95(values) -> Optional[float]:
    v = np.asarray(list(values), np.float64)
    return float(np.percentile(v, 95)) if v.size else None


def reader(name: str):
    path = METRICS_DIR / f"{name}.py"
    if not path.exists():
        raise FileNotFoundError(f"metric {name!r} has no reader at {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench.metrics.{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def read_all(names, obs: Observed) -> dict:
    """{name: value} for every metric whose reader found something."""
    out = {}
    for name in names:
        v = reader(name)(obs)
        if v is not None:
            out[name] = float(v)
    return out
