"""The deep-grid inference kernel: forward, detected output, no residuals."""

from counts import deepgrid as g

#: the names a v5e trace gives this kernel's calls (the jitted function
#: around the ``pallas_call``, as the chip compiler names the custom call)
TRACE_NAMES = ('_deep_apply_impl',)


def count(d) -> tuple[float, float]:
    b = d["batch"]
    return (b * g.forward_flops_per_row(d),
            b * g.io_bytes_per_row(d) + g.weight_bytes(d))
