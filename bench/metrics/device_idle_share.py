"""Share of the traced window in which no operation ran on the device,
averaged over the cell's chips."""


def read(run):
    t = run["trace"]
    if t is None:
        return None
    devs = list(t["devices"].values())[:run["chips"]]
    busy = sum(d["busy_s"] for d in devs) / len(devs)
    return 100.0 * (1.0 - busy / t["window_s"])
