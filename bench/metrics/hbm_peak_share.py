"""Peak device memory in use over the run, as a share of what the device
lets the process use (fullest chip)."""


def read(run):
    m = run["memory"]
    return 100.0 * m["peak_bytes"] / m["bytes_limit"] if m["bytes_limit"] else None
