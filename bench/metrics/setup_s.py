"""Set-up: process start to the window's start (loading, generating data
and weights, compiling or reading the compile cache, warming up)."""


def read(run):
    return run.setup_s if run.setup_s > 0 else None
