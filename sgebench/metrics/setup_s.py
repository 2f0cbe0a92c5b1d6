"""From process start to the start of the window: JAX start, target and
pattern generation, index build, compiles (or loads from the compile
cache) and warm-up packs."""


def read(run):
    return run.setup_s
