"""Set-up: process start to the window's opening (data generation, server
build, dataset registration, warm-up and any compilation)."""


def read(run):
    return run.setup_s
