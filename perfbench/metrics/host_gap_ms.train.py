"""Device idle milliseconds between two consecutive executions of the
round program, from the traced window: the time the host control plane
(masks, keys, metering, the ELBO pull) keeps the chip waiting."""


def read(run):
    if run.reduced is None:
        return None
    _, n, between_s = run.reduced.program(run.cell.traffic["round_program"])
    return between_s / (n - 1) * 1e3 if n > 1 else None
