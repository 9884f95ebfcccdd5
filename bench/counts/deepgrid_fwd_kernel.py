"""The deep-grid forward kernel of training: the forward and its
residual stage planes."""

from counts import deepgrid as g

#: the names a v5e trace gives this kernel's calls (the jitted function
#: around the ``pallas_call``, as the chip compiler names the custom call)
TRACE_NAMES = ('jvp_jit__deep_apply_impl__',)


def count(d) -> tuple[float, float]:
    b = d["batch"]
    return (b * g.forward_flops_per_row(d),
            b * (g.io_bytes_per_row(d) + g.stage_bytes_per_row(d))
            + g.weight_bytes(d))
