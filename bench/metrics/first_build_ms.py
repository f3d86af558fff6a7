"""Device time of the window call's first neighbor build (ms): the busy
device time inside the program's ``md.first_build`` host span, in which
the host waits for the build's overflow flag. Nothing to read where the
program has no such span."""

from bench import scopes


def read(record):
    t_ns = scopes.span_device_ns(record["trace"], "md.first_build")
    return None if t_ns is None else t_ns * 1e-6
