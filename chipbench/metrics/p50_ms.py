"""Median latency (ms) of every query of the window, from the moment it
was due to its exact answer on the host."""
import statistics


def read(rec):
    lat = rec["latency_s"]
    return statistics.median(lat) * 1e3 if lat else None
