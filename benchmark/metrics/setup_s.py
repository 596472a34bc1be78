"""Process start to the opening of the window (host clock): imports, data
and weights from the seed, the program's own set-up and the followed
rounds, the first of which compiles or loads from the cache."""


def read(ctx):
    return ctx["setup_s"]
