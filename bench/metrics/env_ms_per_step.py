"""Device time of the environment matrix (forward operations under the
program's ``dp.env`` scope: pair vectors, R~ and its normalization) in the
traced window, per MD step (ms)."""

from bench import scopes


def read(record):
    return scopes.layer_metrics(record["trace"], record["steps"],
                                record["counters"])["env_ms_per_step"]
