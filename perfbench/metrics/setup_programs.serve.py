"""Programs JAX lowered during a serving cell's set-up: the restore, and
one sampler for every summed ``n`` a (kind, silo) group can reach with
its slices, since the endpoint keys its samplers on that sum. A sampler
that took every sum would shrink it, and ``setup_s`` with it."""


def read(run):
    if run.cell.traffic["kind"] != "serve":
        return None
    return run.counters.get("programs_warmed")
