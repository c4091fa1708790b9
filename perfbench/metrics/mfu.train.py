"""Percent of the chips' bf16 peak that the rounds of the traced window
reach, counting only the matmul FLOPs the algorithm needs (the model's
counter in perfbench/models, from shapes) per round finished on the
device, over the window's length."""


def read(run):
    r = run.reduced
    if r is None or r.window_s <= 0:
        return None
    _, n, _ = r.program(run.cell.traffic["round_program"])
    if not n:
        return None
    flops = run.counters["flops_per_round"] * n
    peak = run.peaks["bf16_flops"] * len(run.devices)
    return 100.0 * flops / r.window_s / peak
