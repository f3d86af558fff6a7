"""Device time of the dp_fused kernels (forward and backward) in the
traced window, per MD step (ms)."""

from bench import trace
from bench.kernels import is_dp_fused


def read(record):
    tr = record["trace"]
    if tr is None:
        return None
    t_ns = trace.op_ns(tr, is_dp_fused)
    if t_ns <= 0.0:
        return None
    return t_ns * 1e-6 / record["steps"]
