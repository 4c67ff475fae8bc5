"""Device time (ms) of cross-chip collectives (the psum combine) per
query, per chip, from the device trace."""


def read(rec):
    t = rec["trace"]
    if t is None or not rec["queries"] or not t["collective_n"]:
        return None
    return t["collective_s"] / rec["queries"] * 1e3
