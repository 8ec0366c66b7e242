"""Set-up: seconds from process start to the end of warm-up (making and
loading the catalog, and the warm-up that fetches or compiles every
program the traffic uses)."""


def read(ctx):
    return ctx.setup_s
