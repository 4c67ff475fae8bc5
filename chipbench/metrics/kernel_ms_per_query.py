"""Device time (ms) of the Pallas kernels (Mosaic custom calls) per
query, per chip, from the device trace."""


def read(rec):
    t = rec["trace"]
    if t is None or not rec["queries"] or not t["kernel_n"]:
        return None
    return t["kernel_s"] / rec["queries"] * 1e3
