"""Percent of the bf16 peak that the routed experts' matmuls reach: the
held experts' FLOPs at the expected load (the model's counter in
perfbench/models, from shapes) of the round programs run in the traced
window, over the device time of the operations that compute them. On a
v5e those are the Mosaic kernels that ``jax.lax.ragged_dot`` lowers to,
named ``ragged-dot-…`` in the trace (forward, and the two products of
its gradient; the forward again under remat, which is not counted)."""

KERNEL = "ragged-dot"


def read(run):
    r = run.reduced
    if r is None or "expert_flops_per_round" not in run.counters:
        return None
    _, n, _ = r.program(run.cell.traffic["round_program"])
    busy = sum(v for k, v in r.op_self_s.items() if k.startswith(KERNEL))
    if not n or busy <= 0:
        return None
    flops = run.counters["expert_flops_per_round"] * n
    return 100.0 * flops / busy / (run.peaks["bf16_flops"] * len(run.devices))
