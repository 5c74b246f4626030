"""Process start -> window start: imports, cluster registration, compile or
cache load, warm-up."""


def read(ctx):
    return ctx["setup_s"]
