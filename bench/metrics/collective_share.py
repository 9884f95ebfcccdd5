"""Share of the devices' busy time spent in collectives: the device time
of every operation named ``all-reduce*``, ``all-gather*``,
``reduce-scatter*``, ``collective-permute*`` or ``all-to-all*`` (their
``-start``/``-done`` halves included), summed over the chips, over the
chips' summed busy time, in percent.  Nothing is returned where no
device trace was taken."""

from __future__ import annotations

import re

COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)")


def read(ctx, metric) -> float | None:
    red = ctx.observed["trace"]
    if not red.n_devices or red.busy_s <= 0:
        return None
    seconds = sum(s for name, s in red.ops.items() if COLLECTIVE.match(name))
    return 100.0 * seconds / (red.busy_s * red.n_devices)
