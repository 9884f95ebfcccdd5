"""The mesh forward kernel."""

from counts import mesh as m

#: the names a v5e trace gives this kernel's calls (the jitted function
#: around the ``pallas_call``, as the chip compiler names the custom call)
TRACE_NAMES = ('jvp_jit__mesh_apply_impl__', '_mesh_apply_impl')


def count(d) -> tuple[float, float]:
    b = d["batch"]
    return (b * 28 * d["cells"],
            b * 2 * m.state_bytes_per_row(d) + m.weight_bytes(d))
