"""Whole-step program: the model's needed FLOPs (perfbench/flops.py,
recompute not counted) of every completed step over what the cell's chips
could do in the window's seconds at the bf16 peak, in %."""


def read(window, trace, config, peaks):
    if not window["steps"] or peaks is None:
        return None
    done = window["flops_per_step"] * window["steps"]
    return 100.0 * done / (window["seconds"] * window["chips"]
                           * peaks["bf16_flops_per_s"])
