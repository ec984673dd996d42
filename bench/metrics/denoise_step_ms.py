"""Device milliseconds of one denoising step of one pod: the device time of
the programs launched inside the program's ``serve/stage/denoise`` spans
(``attribution.attribute``) over pods served times DDIM steps (a step is
one UNet call on the pod's batch)."""

from attribution import stage_seconds


def read(run):
    s = stage_seconds(run, "denoise")
    if s is None or not run["pods"]:
        return None
    return 1e3 * s / (len(run["pods"]) * run["config"]["denoise_steps"])
