"""The mesh backward kernel: reads the output state and its cotangent and
the inverse and adjoint coefficients; writes the input cotangent and the
coefficient gradients."""

from counts import mesh as m

#: the names a v5e trace gives this kernel's calls (the jitted function
#: around the ``pallas_call``, as the chip compiler names the custom call)
TRACE_NAMES = ('transpose_jvp_jit__mesh_apply_impl___',)


def count(d) -> tuple[float, float]:
    b = d["batch"]
    return (b * 60 * d["cells"],
            b * 3 * m.state_bytes_per_row(d) + 3 * m.weight_bytes(d))
