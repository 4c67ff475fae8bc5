"""Programs JAX built (lowered) during the measured window; each one is
a compile or a cache read that the warm-up missed."""


def read(rec):
    return rec["compiles"]
