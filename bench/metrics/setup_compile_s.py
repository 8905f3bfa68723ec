"""Backend compile seconds during set-up, from JAX monitoring events (s)."""


def read(run):
    return run.setup_compile["compile_s"]
