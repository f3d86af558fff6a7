"""Device time of the potential's backward (every operation under a
``transpose(`` of a ``dp.*`` scope: autodiff's gradient of the energy with
respect to the pair vectors) in the traced window, per MD step (ms)."""

from bench import scopes


def read(record):
    return scopes.layer_metrics(record["trace"], record["steps"],
                                record["counters"])["force_backward_ms_per_step"]
