"""Backend-compile seconds during set-up, persistent-cache reads included
(JAX monitoring events)."""


def read(run):
    return run.compile_setup["compile_s"]
