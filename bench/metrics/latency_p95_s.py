"""95th percentile latency over all requests of the run, by nearest rank
(with fewer than 20 requests, their maximum)."""

import math


def read(run):
    lat = sorted(run["latencies"])
    return lat[math.ceil(0.95 * len(lat)) - 1] if lat else None
