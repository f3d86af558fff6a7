"""Device time of the force scatter (forward operations under the
program's ``dp.scatter`` scope: pair forces onto atoms, the self term and
the virial) in the traced window, per MD step (ms)."""

from bench import scopes


def read(record):
    return scopes.layer_metrics(record["trace"], record["steps"],
                                record["counters"])["scatter_ms_per_step"]
