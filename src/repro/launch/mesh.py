"""Mesh topology: one declarative ``MeshSpec``, one ``build_mesh`` factory.

Two mesh families share this module:

  * the **federated** mesh — axes ``(silo, model)``. Silo rows of the
    stacked federation ride the ``silo`` axis (the runtime pads J up to
    a multiple of its size with masked dummy silos); each row's P wire
    parameters are sharded along ``model`` so one silo's upload never
    has to fit on a single device. ``model=1`` degenerates to the
    historical 1-D ``(silo,)`` mesh — same axis name, same compiled
    graph.
  * the **production** mesh (TPU v5e target) — 256 chips as
    (data=16, model=16), or (pod=2, data=16, model=16) for two pods.
    Silos ride the data-parallel axes; the model axis is ordinary
    tensor/expert parallelism inside each silo's shard (DESIGN.md §3/§5).

``MeshSpec`` is the JSON-native description the experiment spec carries
(:class:`repro.federated.api.ExperimentSpec` — ``spec.runtime.mesh``);
``build_mesh`` is the only construction path for the federated mesh.

Everything is a function — importing this module never touches jax
device state (device count is locked at first jax init).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import numpy as np


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    """Published per-chip peaks for the roofline model."""

    flops_bf16: float  # FLOP/s
    hbm_bw: float  # bytes/s
    ici_bw: float  # bytes/s per link


# Keyed by ``jax.Device.device_kind``. TPU v5e: Google Cloud documentation,
# "TPU v5e" — 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of
# chip-to-chip interconnect over 4 links.
CHIP_PEAKS: Dict[str, ChipPeaks] = {
    "TPU v5 lite": ChipPeaks(flops_bf16=197e12, hbm_bw=819e9, ici_bw=50e9),
}

# The chip the production mesh (``make_production_mesh``) describes.
PRODUCTION_DEVICE_KIND = "TPU v5 lite"


def chip_peaks(device_kind: str) -> ChipPeaks:
    """Peaks of one chip of ``device_kind``; an unlisted kind is an error."""
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r} "
            f"(known: {sorted(CHIP_PEAKS)})") from None



@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Declarative federated mesh topology (JSON-native, spec-carried).

    Attributes:
      silo: devices on the ``silo`` axis. ``None`` (default) spans
        ``min(num_silos, available // model)`` devices — the historical
        auto rule, now per model-column.
      model: devices each silo row's P wire parameters shard across
        (tensor parallelism of the wire). 1 keeps the 1-D mesh.
      multiprocess: build over the GLOBAL device list of a
        ``jax.distributed`` run (every process constructs the same mesh;
        each owns the silo rows living on its local devices). False
        restricts the mesh to this process's devices.
    """

    silo: Optional[int] = None
    model: int = 1
    multiprocess: bool = False

    def __post_init__(self):
        if self.model < 1:
            raise ValueError(f"MeshSpec.model must be >= 1, got {self.model}")
        if self.silo is not None and self.silo < 1:
            raise ValueError(f"MeshSpec.silo must be >= 1, got {self.silo}")

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "MeshSpec":
        return cls(silo=d.get("silo"), model=d.get("model", 1),
                   multiprocess=d.get("multiprocess", False))

    @classmethod
    def parse(cls, text: str) -> "MeshSpec":
        """CLI form: ``"silo=8,model=2[,multiprocess]"`` (any subset)."""
        kwargs: Dict[str, Any] = {}
        for part in filter(None, (p.strip() for p in text.split(","))):
            if part == "multiprocess":
                kwargs["multiprocess"] = True
                continue
            key, _, value = part.partition("=")
            if key not in ("silo", "model", "multiprocess"):
                raise ValueError(
                    f"unknown mesh axis {key!r} in {text!r} "
                    "(silo=N,model=N,multiprocess)")
            kwargs[key] = (value.lower() in ("1", "true", "yes")
                           if key == "multiprocess" else int(value))
        return cls(**kwargs)


def _mk_mesh(devices, axes):
    """A Mesh with Auto axis types everywhere (GSPMD picks the layouts)."""
    return jax.sharding.Mesh(
        np.asarray(devices), axes,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def build_mesh(spec: Optional[MeshSpec] = None, *,
               num_silos: Optional[int] = None, devices=None):
    """The single federated-mesh factory: ``MeshSpec`` → ``Mesh``.

    The ``silo`` axis spans ``spec.silo`` devices when pinned, else
    ``min(num_silos, available // model)`` — unconditionally, not the
    largest divisor of J. A divisor rule collapses catastrophically for
    prime federations (J=7 on 4 devices ran the whole federation on ONE
    device); instead the runtime pads its stacked silo axis up to a
    multiple of the mesh size with masked dummy silos (``Server``
    handles the padding), so every device is used for any J. On the
    single-device CPU container this degenerates to a 1-device mesh
    (all silos stacked, collectives become local no-ops) — the compiled
    graph is identical in structure to the multi-host lowering.

    ``model=1`` returns the historical 1-D ``(silo,)`` mesh; ``model>1``
    returns a 2-D ``(silo, model)`` mesh whose rows each hold one silo
    block and whose columns shard the block's P wire parameters.

    ``spec.multiprocess`` builds over the global ``jax.devices()`` of a
    ``jax.distributed`` run (identical on every process); otherwise the
    mesh is restricted to this process's addressable devices so a
    single-process build never spans hosts by accident.
    """
    spec = spec or MeshSpec()
    if devices is None:
        devices = (jax.devices() if spec.multiprocess
                   else jax.local_devices())
    devices = list(devices)
    mw = spec.model
    if mw > len(devices):
        raise ValueError(
            f"MeshSpec.model={mw} needs at least {mw} devices, "
            f"have {len(devices)}")
    if spec.silo is not None:
        n = spec.silo
        if n * mw > len(devices):
            raise ValueError(
                f"MeshSpec(silo={n}, model={mw}) needs {n * mw} devices, "
                f"have {len(devices)}")
    else:
        n = max(min(len(devices) // mw,
                    num_silos if num_silos is not None else len(devices)), 1)
    if mw == 1:
        return _mk_mesh(devices[: n], ("silo",))
    grid = np.asarray(devices[: n * mw]).reshape(n, mw)
    return _mk_mesh(grid, ("silo", "model"))


def make_production_mesh(*, multi_pod: bool = False):
    """Production TPU mesh: (data=16, model=16), ×2 pods when asked.

    Its chips are ``PRODUCTION_DEVICE_KIND``, whatever devices stand in
    for them in a dry run."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk_mesh(np.asarray(jax.devices()[: int(np.prod(shape))])
                    .reshape(shape), axes)


def make_silo_mesh(num_silos: int, devices=None):
    """Back-compat wrapper: the 1-D federated mesh via :func:`build_mesh`."""
    return build_mesh(MeshSpec(), num_silos=num_silos, devices=devices)


def data_axes(mesh) -> tuple:
    """Mesh axes that carry silos / the batch (the 'federation' axes).

    On the production mesh these are (pod, data); on the federated mesh
    the ``silo`` axis itself — the axis the stacked (J, ...) state and
    the (J, P) wire rows shard over.
    """
    return tuple(a for a in mesh.axis_names if a in ("pod", "data", "silo"))


def data_world(mesh) -> int:
    n = 1
    for a in data_axes(mesh):
        n *= mesh.shape[a]
    return n


def model_world(mesh) -> int:
    """Devices sharding each row's parameters (1 on a 1-D mesh)."""
    return mesh.shape.get("model", 1)


def mesh_process_count(mesh) -> int:
    """Distinct jax processes the mesh spans (1 = single-process)."""
    return len({d.process_index for d in np.asarray(mesh.devices).flat})
