"""The deep-grid backward kernel: cotangents through every layer and the
coefficient and gain gradients, from the saved stage planes.  Reads the
inverse and adjoint coefficients, writes the coefficient gradients."""

from counts import deepgrid as g

#: the names a v5e trace gives this kernel's calls (the jitted function
#: around the ``pallas_call``, as the chip compiler names the custom call)
TRACE_NAMES = ('transpose_jvp_jit__deep_apply_impl___',)


def count(d) -> tuple[float, float]:
    b = d["batch"]
    return (b * g.backward_flops_per_row(d),
            b * (2 * g.io_bytes_per_row(d) + g.stage_bytes_per_row(d))
            + 3 * g.weight_bytes(d))
