"""The whole step's share of the chip's peak: model operations per sample
(``bench/counts/model.py``) times samples (or requests) per second in the
traced window, over the chips' peak FLOP/s, in percent.  Nothing is
returned where no device trace was taken (a run on the CPU)."""

from __future__ import annotations

from counts import model

import peaks


def read(ctx, metric) -> float | None:
    kind, dims = ctx.observed["model"]
    rate = ctx.observed.get("samples_per_s",
                            ctx.observed.get("served_per_s"))
    if not rate or not ctx.observed["trace"].n_devices:
        return None
    peak = peaks.for_device(ctx.devices()[0].device_kind)
    return (100.0 * getattr(model, kind)(dims) * rate
            / (peak.flops_per_s * len(ctx.devices())))
