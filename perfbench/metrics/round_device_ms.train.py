"""Device milliseconds of one execution of the jitted round program
(local silo steps, upload, silo gather, combine and server update are
one program today), from the traced window."""


def read(run):
    if run.reduced is None:
        return None
    dev_s, n, _ = run.reduced.program(run.cell.traffic["round_program"])
    return dev_s / n * 1e3 if n else None
