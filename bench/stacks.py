"""Host time by layer: a sampler of the main thread's Python stack.

Every `interval` seconds a background thread reads the frames of the
thread that drives the simulation and charges the sample to the layer of
the innermost frame that lies in the program's package (`src/repro`).
C functions have no frame of their own, so a `heapq` call is charged to
the module that made it.  Samples with no program frame on the stack
(the harness, JAX's host side) go to `None`.
"""

from __future__ import annotations

import sys
import threading
from collections import Counter
from pathlib import Path
from typing import Optional

# module path inside the package -> layer; the first matching prefix wins
LAYER_OF_MODULE = (
    ("core/sim.py", "event_core"),
    ("core/cluster.py", "client"),
    ("core/ranges.py", "client"),
    ("core/replica.py", "protocol"),
    ("core/wal.py", "protocol"),
    ("core/storage.py", "protocol"),
    ("core/txn.py", "protocol"),
    ("core/types.py", "protocol"),
    ("core/node.py", "node"),
    ("core/coordination.py", "node"),
    ("workload/", "workload"),
)
OTHER = "other_program"


def layer_of(filename: str, package_dir: str) -> Optional[str]:
    """Layer of a frame's file: one of `LAYER_OF_MODULE`'s layers, `OTHER`
    for another module of the package, None outside the package."""
    prefix = package_dir.rstrip("/") + "/"
    if not filename.startswith(prefix):
        return None
    rel = filename[len(prefix):]
    for module, layer in LAYER_OF_MODULE:
        if rel.startswith(module):
            return layer
    return OTHER


class StackSampler:
    """Counts samples per layer of one thread while started."""

    def __init__(self, package_dir: Path, thread_id: int,
                 interval: float = 1e-3):
        self.package_dir = str(package_dir)
        self.thread_id = thread_id
        self.interval = interval
        self.counts: Counter = Counter()
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="stack-sampler")
        self._layer_cache: dict[str, Optional[str]] = {}

    def _layer(self, filename: str) -> Optional[str]:
        try:
            return self._layer_cache[filename]
        except KeyError:
            layer = layer_of(filename, self.package_dir)
            self._layer_cache[filename] = layer
            return layer

    def sample(self, frame) -> Optional[str]:
        while frame is not None:
            layer = self._layer(frame.f_code.co_filename)
            if layer is not None:
                return layer
            frame = frame.f_back
        return None

    def _run(self) -> None:
        while not self._stop.wait(self.interval):
            frame = sys._current_frames().get(self.thread_id)
            self.counts[self.sample(frame)] += 1

    def start(self) -> None:
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        self._thread.join(timeout=10.0)
        if self._thread.is_alive():
            raise RuntimeError("stack sampler did not stop")


def us_per_op(obs, layer: str) -> Optional[float]:
    """Host microseconds per completed op charged to `layer`."""
    total = sum(obs.layer_samples.values())
    if not total or not obs.ops_ok:
        return None
    return obs.layer_samples.get(layer, 0) / total * obs.window_s \
        / obs.ops_ok * 1e6
