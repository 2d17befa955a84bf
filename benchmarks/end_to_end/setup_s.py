"""Process start -> window start: imports, weights, the engine, loading or
compiling every program, the warm-up requests and the settling traffic."""


def read(run):
    return run.setup_s
