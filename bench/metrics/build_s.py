"""Seconds that ``lower_deep`` took in set-up, on the benchmark's clock,
to turn the seeded tile programs into the served program (packing every
tile's coefficients and gains)."""


def read(ctx, metric):
    return ctx.observed.get("build_s")
