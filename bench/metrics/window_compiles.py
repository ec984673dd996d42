"""JAX compiles in the window, every stage together: the change over the
window in the sum of the engine's ``compiles/<label>`` counters, one per
backend compile request (persistent-cache hits included) charged to the
program span open when it was made (``repro.telemetry.compiles``), as
``run["stage_compiles"]`` holds them by label."""


def read(run):
    c = run.get("stage_compiles")
    return None if c is None else sum(v["compiles"] for v in c.values())
