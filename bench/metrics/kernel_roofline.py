"""Share of their rooflines that the cell's Pallas kernels reach.

For every kernel the cell's ``work`` names, ``bench/counts/<kernel>.py``
gives the names the trace prints for its calls and each call's
operations and bytes; the trace gives each call's device time.  A
call's least time is the larger of operations over the peak FLOP/s and
bytes over the HBM bandwidth (``bench/peaks.py``).  The share is the sum
of the least times over the sum of the device times, in percent.
Nothing is returned when no such kernel ran in the traced window.
"""

from __future__ import annotations

import importlib

import peaks


def read(ctx, metric) -> float | None:
    red = ctx.observed["trace"]
    least = busy = 0.0
    for kernel, dims in ctx.observed["work"].items():
        counts = importlib.import_module(f"counts.{kernel}")
        calls = [d for name in counts.TRACE_NAMES
                 for d in red.kernels.get(name, [])]
        if not calls:
            continue
        peak = peaks.for_device(ctx.devices()[0].device_kind)
        flops, nbytes = counts.count(dims)
        least += len(calls) * max(flops / peak.flops_per_s,
                                  nbytes / peak.hbm_bytes_per_s)
        busy += sum(calls)
    return 100.0 * least / busy if busy > 0 else None
