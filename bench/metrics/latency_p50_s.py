"""Median latency over all requests of the run: from when a request was
due to when its output was on the host."""

import statistics


def read(run):
    lat = run["latencies"]
    return statistics.median(lat) if lat else None
