"""Set-up: seconds from process start to the first counted request
(weights, cluster, warm-up of every bucket, ramp)."""


def read(ctx):
    return ctx.setup_s
