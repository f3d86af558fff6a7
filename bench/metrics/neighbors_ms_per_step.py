"""Device time of the neighbor builds (forward operations under the
program's ``md.neighbors`` scope) in the traced window, per MD step (ms):
the host-path first build and every build inside the chunks."""

from bench import scopes


def read(record):
    return scopes.layer_metrics(record["trace"], record["steps"],
                                record["counters"])["neighbors_ms_per_step"]
