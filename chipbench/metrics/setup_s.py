"""Seconds from process start to the first timed query: generation,
packing, placement, compilation or cache reads, and warm-up."""


def read(rec):
    return rec["setup_s"]
