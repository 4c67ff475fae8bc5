"""Host time (ms) per query inside QueryEngine.run while the device is
idle: the run spans minus the device busy time inside them, from the
trace (sharded dispatch, host finalize)."""


def read(rec):
    t = rec["trace"]
    if t is None or not rec["queries"]:
        return None
    return (t["run_span_s"] - t["busy_in_run_s"]) / rec["queries"] * 1e3
