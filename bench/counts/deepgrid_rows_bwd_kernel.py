"""The row-blocked deep-grid backward kernel: one layer of one chip's
tile rows, a block of tile rows at a time.  Its work is the resident
backward's (``deepgrid_bwd_kernel``), counted per call with that chip's
dims: ``layers`` 1, ``to`` its tile rows, ``batch`` its rows."""

from counts.deepgrid_bwd_kernel import count  # noqa: F401

#: the names a v5e trace gives this kernel's calls (the ``name=`` of its
#: ``pallas_call``)
TRACE_NAMES = ('deepgrid_rows_bwd_kernel',)
