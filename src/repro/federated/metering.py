"""Byte accounting for federated exchanges — the single metering path.

Leaf module (imports only jax/numpy): the compiled
:class:`~repro.federated.runtime.Server` and the backbone driver
(``launch/train.py``) bill their rounds into a :class:`CommMeter`.
:func:`tree_bytes` is the one primitive every native-dtype byte figure
in the repo is computed with.

The meter is topology-independent: it bills ALGORITHM-level bytes
(what each silo ships), so its figures are identical on a 1-device
mesh, a 2-D (silo x model) mesh, or a multi-process world — and every
process of a ``jax.distributed`` run meters the same totals, since the
control plane is replicated. The compiled-HLO cross-check
(``Server.compiled_collective_bytes``) is the per-topology view: on a
2-D mesh it additionally counts the model-axis rejoin gather
(``docs/federated.md`` §Sharding layout), while the silo gather's
result bytes still equal J x the per-silo upload metered here
(asserted end to end in ``tests/test_multiprocess.py``).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

PyTree = Any


def is_array(x: Any) -> bool:
    """True for the leaves that occupy wire bytes (device or host arrays).

    Scalars and static leaves (treedefs, python numbers) ride along in
    message pytrees but never cross the wire as payload.
    """
    return isinstance(x, (jax.Array, np.ndarray))


def tree_bytes(tree: PyTree) -> int:
    """Metered size of a message pytree in bytes (Σ elements × itemsize)."""
    return sum(
        int(np.prod(x.shape)) * jnp.dtype(x.dtype).itemsize
        for x in jax.tree_util.tree_leaves(tree)
        if is_array(x)
    )


@dataclasses.dataclass
class CommMeter:
    """Algorithm-level bytes-on-wire accounting (host side, per round).

    ``sim_seconds`` accumulates the *simulated* wall-clock of the async
    engine's deterministic latency model (zero on synchronous runs) —
    the quantity buffered-asynchronous execution trades bytes against.
    """

    rounds: int = 0
    bytes_up: int = 0  # silo -> server (post-compression)
    bytes_down: int = 0  # server -> silo broadcast
    sim_seconds: float = 0.0  # simulated wall-clock (async latency model)

    def record(self, up: int, down: int, sim_seconds: float = 0.0) -> None:
        """Log one round's realized (up, down) bytes [+ simulated time]."""
        self.rounds += 1
        self.bytes_up += int(up)
        self.bytes_down += int(down)
        self.sim_seconds += float(sim_seconds)

    @property
    def total(self) -> int:
        return self.bytes_up + self.bytes_down

    @property
    def per_round(self) -> float:
        return self.total / max(self.rounds, 1)

    def state_dict(self) -> Dict[str, Any]:
        """Serializable counters (checkpointed by ``federated.api``)."""
        return {"rounds": self.rounds, "bytes_up": self.bytes_up,
                "bytes_down": self.bytes_down,
                "sim_seconds": self.sim_seconds}

    def load_state(self, state: Dict[str, Any]) -> None:
        """Restore counters saved by :meth:`state_dict`."""
        self.rounds = int(state["rounds"])
        self.bytes_up = int(state["bytes_up"])
        self.bytes_down = int(state["bytes_down"])
        # Pre-async checkpoints lack the key; they are sync runs (0.0).
        self.sim_seconds = float(state.get("sim_seconds", 0.0))
