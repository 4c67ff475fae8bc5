"""GB/s the window read: the bytes each completed query needs (packed
words of the columns it references, chipbench.tpch.needed_bytes) summed
over the window and divided by its length."""


def read(rec):
    if not rec["queries"]:
        return None
    return rec["needed_bytes"] / rec["window_s"] / 1e9
