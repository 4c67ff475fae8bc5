"""Share (%) of the HBM roofline that the device work of the scan
reaches: the least time the needed bytes take at the chip's peak
bandwidth (peaks.json), over the union of device-busy intervals in the
window, per chip."""


def read(rec):
    t = rec["trace"]
    if t is None or not t["busy_s"]:
        return None
    per_chip = rec["needed_bytes"] / rec["chips"]
    floor_s = per_chip / rec["peaks"]["hbm_bytes_per_s"]
    return 100.0 * floor_s / t["busy_s"]
