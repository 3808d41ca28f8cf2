"""95th percentile of how late the load generator submitted a frame after
it was due, in milliseconds."""


def read(r):
    return r.window.get("gen_lag_p95_ms")
