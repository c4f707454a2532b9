"""Seconds of backend compiles JAX reported during set-up (none where every
program came from the persistent compile cache)."""


def read(run):
    return run.setup_compile_s
