"""Set-up: from process start to the first timed request (weights made on
the device, the engine built, one pod of the cell's shape served as
warm-up, compile-cache loads)."""


def read(run):
    return run["setup_s"]
