"""Programs the backend compiled inside the measured window (the compile
meter's count); warm-up should leave none."""


def read(r):
    return float(r.window_compiles)
