"""Programs JAX lowered inside the measured window of a training cell;
0 when set-up warmed every shape the rounds use."""


def read(run):
    if run.cell.traffic["kind"] != "train":
        return None
    return run.counters.get("compiles_in_window")
