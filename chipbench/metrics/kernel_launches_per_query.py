"""Pallas kernel launches per query on each chip: Mosaic custom-call
operations in the device trace over the queries of the window."""


def read(rec):
    t = rec["trace"]
    if t is None or not rec["queries"] or not t["kernel_n"]:
        return None
    return t["kernel_n"] / rec["queries"]
