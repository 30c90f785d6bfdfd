"""Steps D-F — the KernelBank: XCLBIN partitioning + residency + async load.

The FPGA holds a bounded number of hardware kernels per configuration
image; swapping one in is a multi-second partial reconfiguration.  The
TPU analogue keeps a bounded bank of compiled ACCEL (Pallas-variant)
executables; loading a non-resident one is an asynchronous compile +
warm-up on a background thread.  Algorithm 2's "No HW Kernel" branches
consult ``is_resident``; the latency-hiding behaviour (keep running on
a CPU target until the load completes) falls out naturally.  A load
that FAILS is recorded, never silently dropped: ``load_sync``,
``load_async`` and ``raise_if_failed`` re-raise it, so a broken ACCEL
build cannot turn into HOST service that looks healthy.

``partition`` reproduces the XCLBIN-partitioning step: greedy grouping
of kernels into images under a per-image area budget.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from typing import Callable, Optional


@dataclasses.dataclass
class BankEntry:
    name: str
    loaded_at: float
    last_used: float
    payload: object = None          # compiled executable (or sim placeholder)


class KernelBank:
    def __init__(self, slots: int = 4,
                 load_fn: Optional[Callable[[str], object]] = None,
                 min_load_seconds: float = 0.0,
                 clock: Callable[[], float] = time.monotonic):
        """load_fn(name) -> payload; runs on the loader thread.

        ``min_load_seconds`` simulates reconfiguration latency when the
        real compile is instant (tests / simulator).
        """
        self.slots = slots
        self.load_fn = load_fn or (lambda name: name)
        self.min_load_seconds = min_load_seconds
        self.clock = clock
        self._resident: dict[str, BankEntry] = {}
        self._loading: dict[str, threading.Thread] = {}
        self._failed: dict[str, BaseException] = {}
        self._lock = threading.Lock()
        self.stats = {"loads": 0, "evictions": 0, "hits": 0, "misses": 0}

    # ------------------------------------------------------------ queries
    def is_resident(self, name: str) -> bool:
        with self._lock:
            hit = name in self._resident
            self.stats["hits" if hit else "misses"] += 1
            if hit:
                self._resident[name].last_used = self.clock()
            return hit

    def is_loading(self, name: str) -> bool:
        with self._lock:
            t = self._loading.get(name)
            return t is not None and t.is_alive()

    def get(self, name: str) -> object:
        with self._lock:
            e = self._resident[name]
            e.last_used = self.clock()
            return e.payload

    def resident_kernels(self) -> list[str]:
        with self._lock:
            return sorted(self._resident)

    # ------------------------------------------------------------ loading
    def raise_if_failed(self, name: str) -> None:
        """Re-raise the recorded error of a failed load of ``name``."""
        with self._lock:
            err = self._failed.get(name)
        if err is not None:
            raise RuntimeError(
                f"ACCEL build of {name!r} failed to load: {err!r}") from err

    def load_async(self, name: str) -> None:
        """Algorithm 2 l.11/16: 'Reconfigure the FPGA' without blocking.
        Raises if an earlier load of ``name`` failed (no silent retry)."""
        self.raise_if_failed(name)
        with self._lock:
            if name in self._resident:
                return
            t = self._loading.get(name)
            if t is not None and t.is_alive():
                return
            thread = threading.Thread(target=self._do_load, args=(name,),
                                      daemon=True)
            self._loading[name] = thread
            thread.start()

    def load_sync(self, name: str) -> None:
        self.load_async(name)
        t = self._loading.get(name)
        if t is not None:
            t.join()
        self.raise_if_failed(name)

    def _do_load(self, name: str) -> None:
        t0 = self.clock()
        try:
            payload = self.load_fn(name)
        except BaseException as e:          # noqa: BLE001 — re-raised on use
            with self._lock:
                self._failed[name] = e
                self._loading.pop(name, None)
            return
        elapsed = self.clock() - t0
        if elapsed < self.min_load_seconds:
            time.sleep(self.min_load_seconds - elapsed)
        with self._lock:
            if len(self._resident) >= self.slots:
                victim = min(self._resident.values(),
                             key=lambda e: e.last_used)
                del self._resident[victim.name]
                self.stats["evictions"] += 1
            now = self.clock()
            self._resident[name] = BankEntry(name=name, loaded_at=now,
                                             last_used=now, payload=payload)
            self.stats["loads"] += 1
            self._loading.pop(name, None)


def partition(kernel_areas: dict[str, float], image_budget: float,
              pinned: Optional[dict[str, int]] = None) -> list[list[str]]:
    """XCLBIN partitioning (step E): greedy first-fit-decreasing grouping
    of kernels into configuration images under an area budget.

    ``pinned`` optionally maps kernel -> image index (the paper's manual
    priority assignment path).
    """
    images: list[list[str]] = []
    loads: list[float] = []
    pinned = pinned or {}
    for name, idx in pinned.items():
        while len(images) <= idx:
            images.append([])
            loads.append(0.0)
        images[idx].append(name)
        loads[idx] += kernel_areas[name]
        if loads[idx] > image_budget:
            raise ValueError(f"pinned image {idx} exceeds budget")
    for name, area in sorted(
            ((n, a) for n, a in kernel_areas.items() if n not in pinned),
            key=lambda kv: -kv[1]):
        if area > image_budget:
            raise ValueError(f"kernel {name} larger than an image budget")
        for i, load in enumerate(loads):
            if load + area <= image_budget:
                images[i].append(name)
                loads[i] += area
                break
        else:
            images.append([name])
            loads.append(area)
    return images
