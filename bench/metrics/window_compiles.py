"""Executables built or loaded in the traced window: the number of
``repro.compile`` spans the program's compilation counter left on the
trace.  It should read 0.  Nothing is returned where the program keeps
no such counter."""

import spans


def read(ctx, metric):
    from repro.runtime import compile_cache

    if not hasattr(compile_cache, "COMPILES"):
        return None
    return sum(1 for s in spans.of_cell(ctx) if s.name == "repro.compile")
