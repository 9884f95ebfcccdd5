"""Reduce a profiler trace (``*.xplane.pb``) to the benchmark's numbers.

A device plane is one named ``/device:TPU:<k>``; its operations are the
events of its ``XLA Ops`` line, each named by its HLO instruction
(``%name = shape op(...)``; the reduction keeps ``name``).  An event that
contains others (a ``while`` loop around its body's operations) is not an
operation of its own: the device is busy only while a leaf operation
runs.  A Pallas kernel is a ``tpu_custom_call`` whose name the chip takes
from the jitted function around it (``jvp_jit__deep_apply_impl__.1``).
From them:

* ``busy_s``: the union of the leaf operations' intervals, averaged over
  the devices;
* ``ops``: seconds per operation name, summed over devices;
* ``kernels``: the durations of every Pallas kernel call, by name with
  its ``.<n>`` instance number dropped;
* ``idle_gaps``: each stretch of at least ``MIN_GAP_S`` in which a device
  ran nothing, charged to the Python-thread host event that overlaps it
  most (JAX's dispatch and transfer events, the benchmark's own spans);
  shorter stretches are summed under one entry.

``window_s`` is the traced window as the benchmark's clock measured it.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import pathlib
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
KERNEL = 'custom_call_target="tpu_custom_call"'
#: idle stretches shorter than this are summed, not charged one by one
MIN_GAP_S = 10e-6
SHORT_GAPS = "gaps under 10 us"


@dataclasses.dataclass
class Reduced:
    busy_s: float
    window_s: float
    n_devices: int
    ops: dict            # op name -> seconds, summed over devices
    kernels: dict        # kernel name -> list of call durations (s)
    idle_gaps: dict      # host event name -> seconds, summed over devices

    def breakdown(self) -> dict:
        top = sorted(self.ops.items(), key=lambda kv: -kv[1])[:10]
        gaps = sorted(self.idle_gaps.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[k, v] for k, v in top],
                "idle_gaps": [[k, v] for k, v in gaps]}


def op_name(hlo: str) -> str:
    """``%name.3 = f32[...] op(...)`` -> ``name.3``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def kernel_name(name: str) -> str:
    """An op name without its instance number: ``name.3`` -> ``name``."""
    return re.sub(r"\.\d+$", "", name)


def leaves(events):
    """Events (start, end, ...) sorted by start, without those that
    contain a later one."""
    events = sorted(events, key=lambda e: (e[0], -e[1]))
    out = []
    for k, ev in enumerate(events):
        if k + 1 < len(events) and events[k + 1][0] < ev[1] \
                and events[k + 1][1] <= ev[1]:
            continue
        out.append(ev)
    return out


def union(intervals) -> tuple[float, list]:
    """Length of the union of sorted ``(start, end, ...)`` intervals, and
    the gaps between them."""
    total, gaps, end = 0.0, [], None
    for ev in intervals:
        s, e = ev[0], ev[1]
        if end is None or s > end:
            if end is not None:
                gaps.append((end, s))
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total, gaps


def _host_events(planes, longest_ns: float) -> list:
    out = []
    for plane in planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            if not line.name.startswith("python"):
                continue
            for ev in line.events:
                if 0 < ev.duration_ns <= longest_ns:
                    out.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                ev.name))
    out.sort()
    return out


def charge(gaps, host) -> dict:
    """Seconds of each gap charged to the host event overlapping it most;
    gaps shorter than ``MIN_GAP_S`` are summed under one name."""
    charged = collections.defaultdict(float)
    starts = [h[0] for h in host]
    for g0, g1 in gaps:
        seconds = (g1 - g0) * 1e-9
        if seconds < MIN_GAP_S:
            charged[SHORT_GAPS] += seconds
            continue
        best, name = 0.0, "no host event"
        hi = bisect.bisect_right(starts, g1)
        for s, e, n in host[max(0, hi - 256):hi]:
            overlap = min(e, g1) - max(s, g0)
            if overlap > best:
                best, name = overlap, n
        charged[name] += seconds
    return dict(charged)


def reduce_trace(trace_dir, *, window_s: float, n_devices: int) -> Reduced:
    """Reduce the newest ``*.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    return reduce_profile(ProfileData.from_file(str(files[-1])),
                          window_s=window_s, n_devices=n_devices)


def reduce_profile(data, *, window_s: float, n_devices: int) -> Reduced:
    planes = list(data.planes)
    devices = sorted((p for p in planes if DEVICE_PLANE.match(p.name)),
                     key=lambda p: p.name)[:n_devices]
    host = _host_events(planes, 0.5 * window_s * 1e9)
    busy = 0.0
    ops = collections.defaultdict(float)
    kernels = collections.defaultdict(list)
    idle = collections.defaultdict(float)
    for plane in devices:
        events = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                hlo = ev.name
                events.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                               op_name(hlo), KERNEL in hlo))
        events = leaves(events)
        for s, e, name, is_kernel in events:
            ops[name] += (e - s) * 1e-9
            if is_kernel:
                kernels[kernel_name(name)].append((e - s) * 1e-9)
        total, gaps = union(events)
        busy += total * 1e-9
        for k, v in charge(gaps, host).items():
            idle[k] += v
    n = max(1, len(devices))
    return Reduced(busy_s=busy / n, window_s=window_s, n_devices=len(devices),
                   ops=dict(ops), kernels=dict(kernels), idle_gaps=dict(idle))
