"""Reduction of a JAX profiler trace to device busy time, kernel time and
the longest idle gaps.

A traced run wraps its measured window in a host annotation named
``WINDOW``; the reduction keeps what lies inside it.  Device busy time is
the union of the intervals in which an operation ran on a device's op
line, averaged over the devices the run used; a kernel's time is the sum
of the durations of its events.
"""
from __future__ import annotations

import dataclasses
import glob
import os
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

from bench import intervals as iv

WINDOW = "bench_window"
# device lines: one event per executed operation, one per program run
OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"

Event = Tuple[str, float, float]      # (name, start s, end s)


@dataclasses.dataclass
class DeviceTrace:
    window: Tuple[float, float]        # on the profiler's clock, seconds
    devices: List[List[Event]]         # op events per device
    modules: List[List[Event]]         # program (XLA module) runs per device
    host: List[Event]                  # host annotations (all threads)

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def busy_intervals(self, dev: int) -> List[iv.Interval]:
        return iv.merge(iv.clip([(a, b) for _, a, b in self.devices[dev]],
                                *self.window))

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        if not self.devices:
            return 0.0
        return sum(iv.covered([(a, b) for _, a, b in d], *self.window)
                   for d in self.devices) / len(self.devices)

    def _sum(self, per_device, match: str) -> Tuple[float, int]:
        tot, n = 0.0, 0
        lo, hi = self.window
        for d in per_device:
            for name, a, b in d:
                if match in name and a >= lo and b <= hi:
                    tot += b - a
                    n += 1
        k = max(1, len(per_device))
        return tot / k, n // k

    def op_seconds(self, match: str) -> Tuple[float, int]:
        """(summed duration, count) of the window's device operations whose
        name contains ``match``, averaged over the devices."""
        return self._sum(self.devices, match)

    def module_runs(self, match: str) -> Tuple[float, int]:
        """(summed duration, count) of the window's program runs whose
        name contains ``match``, averaged over the devices."""
        return self._sum(self.modules, match)

    def top_ops(self, n: int = 10) -> List[List]:
        """The ``n`` programs that took most device time."""
        acc: Dict[str, float] = defaultdict(float)
        lo, hi = self.window
        for d in self.modules:
            for name, a, b in d:
                if b > lo and a < hi:
                    acc[name] += min(b, hi) - max(a, lo)
        k = max(1, len(self.modules))
        return [[name, t / k] for name, t in
                sorted(acc.items(), key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, labels: Sequence[Event], n: int = 10
                  ) -> List[List]:
        """The ``n`` longest idle gaps of device 0, each named by the
        innermost labelled host interval around its midpoint."""
        if not self.devices:
            return []
        longest = sorted(iv.gaps(self.busy_intervals(0), *self.window),
                         key=lambda g: g[0] - g[1])[:n]
        out = []
        for a, b in longest:
            mid = 0.5 * (a + b)
            around = [(e - s, name) for name, s, e in labels if s <= mid < e]
            out.append([min(around)[1] if around else "other", b - a])
        return out


def _xplane(profile_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {profile_dir}")
    return found[-1]


def load(path: str, n_devices: int = 1) -> Optional[DeviceTrace]:
    """Read a profile (an ``.xplane.pb`` or a directory holding one).
    None when it holds no ``WINDOW`` annotation."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        path = _xplane(path)
    pd = ProfileData.from_file(path)
    devices, modules, host = [], [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            ops, mods = [], []
            for line in plane.lines:
                if line.name == OP_LINE:
                    ops.extend((short_name(e.name), e.start_ns * 1e-9,
                                (e.start_ns + e.duration_ns) * 1e-9)
                               for e in line.events)
                elif line.name == MODULE_LINE:
                    mods.extend((e.name.split("(")[0], e.start_ns * 1e-9,
                                 (e.start_ns + e.duration_ns) * 1e-9)
                                for e in line.events)
            devices.append(ops)
            modules.append(mods)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns * 1e-9,
                             (e.start_ns + e.duration_ns) * 1e-9)
                            for e in line.events)
    wins = [e for e in host if e[0] == WINDOW]
    if not wins:
        return None
    _, lo, hi = max(wins, key=lambda e: e[2] - e[1])
    return DeviceTrace(window=(lo, hi), devices=devices[:n_devices],
                       modules=modules[:n_devices], host=host)


def short_name(hlo: str) -> str:
    """``%name.3 = f32[..] op(..)`` -> ``name.3``."""
    return hlo.split(" = ")[0].lstrip("%")
