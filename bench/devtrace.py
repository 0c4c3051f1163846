"""Reduction of a JAX profiler trace to device busy time, program times
and labelled idle gaps.

``load`` reads the ``.xplane.pb`` the profiler wrote into plain event
lists; ``reduce`` works on those lists only, so the self-check can feed
it a small recorded trace.  Times are nanoseconds from the profile's
start.  On a TPU each ``/device:TPU:<n>`` plane has an ``XLA Modules``
line with one event per program execution, named ``jit_<fn>(<id>)``;
busy time is the union of those intervals inside the window.
"""
from __future__ import annotations

import bisect
import glob
import os
from typing import Dict, List, Optional, Tuple

from stats import gaps, union_length

Event = Tuple[str, float, float]            # (name, start ns, duration ns)

DEVICE_PREFIX = "/device:TPU:"
MODULE_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
#: host events that only wait on the device, never the cause of a gap
WAITS = ("ReadSyncFlag", "CompleteCallbacks", "tpu::System::Execute=>Done",
         "Release semaphore", "MemoryDeallocation")


def latest_xplane(log_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    return found[-1] if found else None


def load(path: str) -> Dict:
    """Device program events, host events and the profile's start time
    (epoch ns) from an ``.xplane.pb``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: Dict[str, List[Event]] = {}
    host: Dict[str, List[Event]] = {}
    start_ns = None
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX) and \
                plane.name[len(DEVICE_PREFIX):].isdigit():
            for line in plane.lines:
                if line.name == MODULE_LINE:
                    devices[plane.name] = [
                        (e.name, e.start_ns, e.duration_ns)
                        for e in line.events]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host[line.name] = [(e.name, e.start_ns, e.duration_ns)
                                   for e in line.events
                                   if e.name not in WAITS]
        elif plane.name == "Task Environment":
            for k, v in plane.stats:
                if k == "profile_start_time":
                    start_ns = int(v)
    return dict(devices=devices, host=host, profile_start_ns=start_ns)


def module_base(name: str) -> str:
    """``jit_eval_one(1302...)`` -> ``jit_eval_one``."""
    return name.split("(", 1)[0]


def _label(gap: Tuple[float, float], host: Dict[str, List[Event]],
           starts: Dict[str, List[float]]) -> str:
    """What the host was doing in a device-idle gap: the traced host
    event that overlaps it most, or untraced host work (the Python of
    the fleet driver and the search methods is not traced)."""
    lo, hi = gap
    best, best_ov = None, 0.0
    for line, evs in host.items():
        i = bisect.bisect_left(starts[line], lo)
        # events starting before lo may still overlap: look back a little
        for name, s, d in evs[max(0, i - 64):]:
            if s >= hi:
                break
            ov = min(s + d, hi) - max(s, lo)
            if ov > best_ov:
                best, best_ov = name, ov
    if best is None or best_ov < 0.1 * (hi - lo):
        return "untraced host work"
    return f"host {best}"


def reduce(trace: Dict, lo_ns: float, hi_ns: float,
           top: int = 10) -> Optional[Dict]:
    """Device busy seconds (mean over devices), seconds per program,
    and the longest idle gaps with their labels, inside [lo_ns, hi_ns).
    None when no device program ran in the window."""
    devs = trace["devices"]
    if not devs or not any(devs.values()):
        return None
    busy = []
    per_module: Dict[str, float] = {}
    all_gaps: List[Tuple[float, float]] = []
    for evs in devs.values():
        spans = [(s, s + d) for _, s, d in evs]
        busy.append(union_length(spans, lo_ns, hi_ns))
        for name, s, d in evs:
            ov = min(s + d, hi_ns) - max(s, lo_ns)
            if ov > 0:
                base = module_base(name)
                per_module[base] = per_module.get(base, 0.0) + ov
        all_gaps += gaps(spans, lo_ns, hi_ns)
    if not any(busy):
        return None
    host = trace.get("host", {})
    starts = {ln: [s for _, s, _ in evs] for ln, evs in host.items()}
    longest = sorted(all_gaps, key=lambda g: g[0] - g[1])[:top]
    idle = {}
    for g in longest:
        lab = _label(g, host, starts)
        idle[lab] = idle.get(lab, 0.0) + (g[1] - g[0]) / 1e9
    ops = sorted(per_module.items(), key=lambda kv: -kv[1])[:top]
    return dict(
        busy_s=sum(busy) / len(busy) / 1e9,
        window_s=(hi_ns - lo_ns) / 1e9,
        devices=len(devs),
        module_s={k: v / 1e9 for k, v in per_module.items()},
        device_ops=[[k, v / 1e9] for k, v in ops],
        idle_gaps=sorted(([k, v] for k, v in idle.items()),
                         key=lambda kv: -kv[1]))
