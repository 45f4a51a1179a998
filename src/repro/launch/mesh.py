"""Mesh builders for single- and multi-controller runs.

Everything here is a function, never a module-level constant, so importing
this module never touches jax device state.

`make_global_mesh` is the multi-controller entry point: it builds the
agent mesh from the *global* process view (`jax.process_count()` > 1 when
`jax.distributed` is initialized — each controller contributes its local
devices and the "pod" axis follows the process boundary) and falls back
to the local devices of a single process.  `validate_agent_tiling` is the
one place that decides whether an agent count fits a mesh, with an error
that says what would fit.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = [
    "make_production_mesh",
    "make_global_mesh",
    "make_sharded_mesh",
    "validate_agent_tiling",
    "agent_axes",
    "num_agents",
]


def _auto_mesh(shape, axes, devices=None):
    """`jax.make_mesh` with every axis `AxisType.Auto`: this repo's steps
    place arrays with `NamedSharding` and leave propagation to GSPMD
    (`spmd_axis_name` vmaps, sharding constraints).  Explicit axes (the
    `jax.make_mesh` default) put the sharding into the array types, which
    the agent vmap then refuses for inputs placed differently."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 256 chips (16 data x 16 model).  Multi-pod: 2 pods = 512
    chips with a leading "pod" axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_global_mesh(*, model_parallel: int = 1, agents: int | None = None):
    """Build the agent mesh over every device in the global process view.

    With `jax.process_count() == P > 1` (a jax.distributed multi-controller
    job) the devices of all processes participate and the leading "pod"
    axis has extent P, so one process owns exactly one pod row of the
    agent torus — process boundary == pod boundary, which is what keeps
    each controller's Λ-keys on its own host.  A single process (the
    common CPU/dev case) gets a flat ("data", "model") mesh over its local
    devices.

    `model_parallel` carves a trailing "model" axis out of the device
    count; the remaining extent hosts the agents.  When `agents` is given
    the tiling is validated immediately (see `validate_agent_tiling`).
    """
    devices = jax.devices()
    n = len(devices)
    if model_parallel < 1 or n % model_parallel:
        raise ValueError(
            f"model_parallel={model_parallel} does not divide the "
            f"{n} visible devices")
    slots = n // model_parallel
    procs = jax.process_count()
    if procs > 1:
        if slots % procs:
            raise ValueError(
                f"{slots} agent slots do not split over {procs} processes; "
                f"each controller must own the same number of agents")
        shape = (procs, slots // procs, model_parallel)
        axes = ("pod", "data", "model")
    else:
        shape = (slots, model_parallel)
        axes = ("data", "model")
    mesh = _auto_mesh(shape, axes, devices=devices)
    if agents is not None:
        validate_agent_tiling(mesh, agents)
    return mesh


def make_sharded_mesh(*, agents: int | None = None, fsdp: int = 1,
                      tensor: int = 1):
    """Agent x fsdp x tensor factorization: ("data", "fsdp", "model").

    The leading "data" axis hosts the decentralized agents (it is the
    `agent_axes` answer for this mesh); each agent owns an fsdp x tensor
    block of devices, inside which params shard FSDP-style over "fsdp"
    (TRAIN_RULES: "embed"/"batch") and tensor-parallel over "model"
    (TRAIN_RULES: "mlp"/"heads"/"vocab").  The per-agent group size must
    divide the visible device count; the remaining extent becomes agent
    slots.  A (1, 1, 1) mesh on a single device is the trivially-sharded
    case the bit-parity tests pin against the dense path.
    """
    if fsdp < 1 or tensor < 1:
        raise ValueError(f"fsdp={fsdp} and tensor={tensor} must be >= 1")
    devices = jax.devices()
    n = len(devices)
    group = fsdp * tensor
    if n % group:
        raise ValueError(
            f"per-agent group fsdp*tensor={group} does not divide the "
            f"{n} visible devices")
    slots = n // group
    mesh = _auto_mesh((slots, fsdp, tensor), ("data", "fsdp", "model"),
                      devices=devices)
    if agents is not None:
        validate_agent_tiling(mesh, agents)
    return mesh


def validate_agent_tiling(mesh, agents: int) -> int:
    """Require `agents` to tile the mesh's agent axes exactly.

    Returns agents-per-slot (1 for the one-agent-per-device deployments;
    >1 means each mesh slot time-multiplexes that many agents, which the
    dense fallback supports but the ppermute ring does not).  Raises
    ValueError with the fitting counts spelled out otherwise.
    """
    slots = num_agents(mesh)
    shape = dict(mesh.shape)
    if agents < 1:
        raise ValueError(f"agent count must be positive, got {agents}")
    if agents % slots:
        fits = sorted({slots * k for k in (1, 2, 4, 8)})
        raise ValueError(
            f"{agents} agents do not tile the {shape} mesh: its agent axes "
            f"{agent_axes(mesh)} provide {slots} slots, so the agent count "
            f"must be a multiple of {slots} (e.g. {fits})")
    return agents // slots


def agent_axes(mesh) -> tuple[str, ...]:
    """Mesh axes that host the decentralized agents (paper's m)."""
    return tuple(a for a in ("pod", "data") if a in mesh.shape)


def num_agents(mesh) -> int:
    n = 1
    for a in agent_axes(mesh):
        n *= mesh.shape[a]
    return n
