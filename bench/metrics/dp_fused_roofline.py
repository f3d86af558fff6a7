"""The dp_fused kernels' share of their roofline (%): the least time the
chip could take for the kernels' operations and bytes in the window
(``bench/flops.dp_fused_cost_per_step``, over real neighbors), the larger
of operations over peak FLOP/s and bytes over peak bandwidth, divided by
the kernels' summed device time in the trace. Nothing to read where the
window ran no dp_fused kernel."""

from bench import trace
from bench.kernels import is_dp_fused


def read(record):
    tr = record["trace"]
    if tr is None:
        return None
    t_ns = trace.op_ns(tr, is_dp_fused)
    if t_ns <= 0.0:
        return None
    cost, peaks = record["dp_fused_per_eval"], record["peaks"]
    evals = record["force_evals"]
    bound_s = max(cost["flops"] * evals / peaks["flops_per_s"],
                  cost["bytes"] * evals / peaks["bytes_per_s"])
    return 100.0 * bound_s / (t_ns * 1e-9)
