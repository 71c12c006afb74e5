"""setup_s: process start to the first timed request (host clock): JAX
start-up, the scan pool, compilation or loading from the persistent
cache, and the warm-up request."""


def read(run):
    return run.setup_s
