"""Device time (ms) of the mask-repack kernel (`repack_mask_packed`) per
query, per chip, from the device trace: the cost of a predicate mask
meeting operators at another code width. None where no query repacks."""

KERNEL = "repack_mask_packed"


def read(rec):
    t = rec["trace"]
    if t is None or not rec["queries"]:
        return None
    s = dict(t["device_ops"]).get(KERNEL)
    return None if s is None else s / rec["queries"] * 1e3
