"""Set-up time: from process start (imports, device, data or weights,
warm-up and, in a run that compiles, compilation) to the window's start."""


def read(rec):
    return rec.get("setup_s")
