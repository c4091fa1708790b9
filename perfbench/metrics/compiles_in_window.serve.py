"""Programs JAX lowered inside the measured window of a serving cell;
0 when set-up warmed every query shape of the mix."""


def read(run):
    if run.cell.traffic["kind"] != "serve":
        return None
    return run.counters.get("compiles_in_window")
