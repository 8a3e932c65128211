"""Set-up: process start to the window's start (engine build, compiles or
cache loads, warm-up traffic). Host clock."""


def read(run):
    return run.setup_s
