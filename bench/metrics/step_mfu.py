"""Whole-step share of the chips' peak (%): the model operations of every
force evaluation in the window (``bench/flops.model_flops_per_step``, a
lower bound that leaves out the embedding) over the window's wall time,
chips and peak FLOP/s."""


def read(record):
    work = record["model_flops_per_eval"] * record["force_evals"]
    return 100.0 * work / (record["window_s"] * record["chips"]
                           * record["peaks"]["flops_per_s"])
