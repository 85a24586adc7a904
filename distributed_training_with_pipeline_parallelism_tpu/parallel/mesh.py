"""Device-mesh construction for pipeline (+ data) parallelism.

TPU-native replacement for the reference's process-group lifecycle
(``dist.init_process_group('gloo')`` with env-var rendezvous,
``LLMsDistributedTrainingHelper.py:168-178`` — SURVEY.md §2.4): a
``jax.sharding.Mesh`` over the slice's devices. Axis order is
('data', 'pipe') so pipeline ppermute hops ride the fastest (innermost,
ICI-adjacent) axis; multi-host DCN is handled transparently by
``jax.distributed`` + XLA.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh

from ..utils.profiling import annotate, backend_is_up

DATA_AXIS = "data"
PIPE_AXIS = "pipe"
MODEL_AXIS = "model"  # tensor_parallel.TP_AXIS aliases this
SEQ_AXIS = "seq"
EXPERT_AXIS = "expert"


def _make_1d_mesh(n: int, axis_name: str, devices=None) -> "Mesh":
    devices = list(devices if devices is not None else jax.devices())
    if len(devices) < n:
        raise ValueError(f"need {n} devices, have {len(devices)}")
    return Mesh(np.asarray(devices[:n]), (axis_name,))


def make_sp_mesh(n_seq: int, devices=None) -> "Mesh":
    """1-D sequence-parallel mesh for ring attention."""
    return _make_1d_mesh(n_seq, SEQ_AXIS, devices)


def make_ep_mesh(n_expert: int, devices=None) -> "Mesh":
    """1-D expert-parallel mesh for MoE all-to-all dispatch."""
    return _make_1d_mesh(n_expert, EXPERT_AXIS, devices)


def make_mesh(n_pipe: int, n_data: int = 1, n_model: int = 1, n_seq: int = 1,
              n_expert: int = 1,
              devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """Build the pipeline mesh: ('data', 'pipe'), growing a 'model' axis
    (tensor parallelism inside stages), a 'seq' axis (ring-attention
    sequence parallelism inside stages), and/or an 'expert' axis (MoE
    expert parallelism inside stages) when those sizes exceed 1. Extra
    axes are innermost — the highest-traffic collectives ride the shortest
    ICI hops.

    Kept as the host span ``setup/mesh``. Without ``devices`` this is often
    a process's first call that needs them, so the span then holds the
    backend's initialisation: its notes say ``backend_was_up=False``."""
    with annotate("setup/mesh", backend_was_up=backend_is_up()):
        devices = list(devices if devices is not None else jax.devices())
        sizes = [("n_data", DATA_AXIS, n_data), ("n_pipe", PIPE_AXIS, n_pipe)]
        if n_model > 1:
            sizes.append(("n_model", MODEL_AXIS, n_model))
        if n_seq > 1:
            sizes.append(("n_seq", SEQ_AXIS, n_seq))
        if n_expert > 1:
            sizes.append(("n_expert", EXPERT_AXIS, n_expert))
        need = int(np.prod([n for _, _, n in sizes]))
        if len(devices) < need:
            detail = ", ".join(f"{name[2:]}={n}" for name, _, n in sizes)
            raise ValueError(
                f"need {need} devices for mesh ({detail}), have "
                f"{len(devices)}; for CPU simulation set "
                f"XLA_FLAGS=--xla_force_host_platform_device_count=N before "
                f"importing jax (the JAX analog of the reference's "
                f"gloo-on-localhost trick)")
        grid = np.asarray(devices[:need]).reshape([n for _, _, n in sizes])
        return Mesh(grid, tuple(axis for _, axis, _ in sizes))


def init_multihost(coordinator_address: Optional[str] = None,
                   num_processes: Optional[int] = None,
                   process_id: Optional[int] = None) -> None:
    """Initialize JAX's multi-host runtime for pod slices.

    TPU-native replacement for the reference's env-var rendezvous +
    ``init_process_group`` (``LLMsDistributedTrainingHelper.py:168-175``): on
    Cloud TPU the arguments auto-detect from the metadata server; elsewhere
    pass coordinator ``host:port``, world size, and this process's rank.
    After this, ``jax.devices()`` spans the slice and meshes built by
    :func:`make_mesh` place inter-host edges on DCN transparently (XLA
    routes collectives ICI-first).
    """
    jax.distributed.initialize(coordinator_address=coordinator_address,
                               num_processes=num_processes,
                               process_id=process_id)


def simulate_cpu_devices(n: int = 8) -> None:
    """Force an n-device simulated CPU backend (the JAX analog of the
    reference's gloo-on-localhost fake cluster, SURVEY.md §4).

    Must run before the first backend initialization in the process (after
    it, nothing here has any effect). Two traps this helper handles
    centrally (callers should not hand-roll it):

    - Duplicate ``--xla_force_host_platform_device_count`` flags: the *last*
      occurrence wins, so the requested count is appended — a pre-existing
      count in ``XLA_FLAGS`` (e.g. from the shell) is overridden, not
      silently kept.
    - ``JAX_PLATFORMS`` is read when jax is imported, which this module has
      already done: the platform is set through ``jax.config`` instead.
    """
    import os

    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={n}")
    jax.config.update("jax_platforms", "cpu")
