"""Device milliseconds of the VAE decoder a served image: the device time of
the programs launched inside the program's ``serve/stage/vae`` spans
(``attribution.attribute``) over the images served."""

from attribution import stage_seconds


def read(run):
    s = stage_seconds(run, "vae")
    if s is None or not run["completed"]:
        return None
    return 1e3 * s / run["completed"]
