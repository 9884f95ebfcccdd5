"""How late the open-loop generator submitted: the 95th percentile of
submit time minus due time over the window's requests, in milliseconds."""


def read(ctx, metric):
    lag = ctx.observed.get("lag_p95_s")
    return None if lag is None else 1e3 * lag
