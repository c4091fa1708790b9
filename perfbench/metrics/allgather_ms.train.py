"""Device milliseconds a round that the silo gather is in flight, from
the traced window: the all-gather's own spans, or, where the TPU compiler
split it into an async start, overlapped continuation and done, the
span from each ``async-collective-start`` to its done
(``pbench.trace.async_collective_spans``), per round program run.

On a v5e the silo4 cell's compiled round holds one such async pair, the
fusions around the wire's all-gather (``f32[64,100354]``); its only
other collective is the ELBO's scalar ``psum``, a synchronous
``all-reduce`` that neither term counts (so the round compiled for a
described v5e:2x2 shows, and a traced run of the cell on four v5e)."""
from pbench import trace


def read(run):
    r = run.reduced
    if r is None:
        return None
    _, n, _ = r.program(run.cell.traffic["round_program"])
    s = max(r.collective_s.get("all-gather", 0.0),
            r.collective_s.get(trace.ASYNC, 0.0))
    return s / n * 1e3 if n and s > 0 else None
