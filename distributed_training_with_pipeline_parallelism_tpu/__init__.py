"""TPU-native pipeline-parallel training framework.

A brand-new JAX/XLA/pjit framework with the capabilities of
``aa5490/Distributed-Training-with-Pipeline-Parallelism``: decoder-only
transformer LM training under GPipe / 1F1B / Interleaved-1F1B pipeline
schedules, expressed as single-program SPMD over a device mesh with
``jax.lax.ppermute`` rings instead of torch.distributed P2P over gloo.

Import alias convention: ``import distributed_training_with_pipeline_parallelism_tpu as dtpp``.
"""

# First, before anything else of the package: the module stamps the clock at
# its own top (before its ``import jax``), and ``import_done`` at the bottom of
# this file closes the ``setup/import`` span (docs/observability.md §2).
from .utils.profiling import import_done as _import_done
from .utils.config import (MeshConfig, ModelConfig, RunConfig, ScheduleConfig,
                           virtual_stages_for)

# Lazy top-level re-exports of the main builders, so the one-import surface
# (``import ... as dtpp``) covers the whole workflow without eagerly pulling
# every subsystem at package import:
#   dtpp.make_mesh(...)              device meshes (data/pipe/model/seq/expert)
#   dtpp.make_pipeline_step(...)     jitted (params, x, y) -> (loss, grads)
#   dtpp.make_pipeline_loss_fn(...)  forward-only eval loss, any dense mesh
#   dtpp.make_pipeline_forward(...)  pipelined batch inference logits
#   dtpp.fsdp_shard_params(...)      pp x fsdp resting placement
#   dtpp.fit(...)                    training loop (optax + orbax)
#   dtpp.ServingEngine(...)          continuous-batching serving (docs/serving.md)
#   dtpp.CheckpointManager(...)      crash-safe checkpoints (docs/resilience.md)
#   dtpp.AnomalyGuard / FaultPlan    anomaly guard + fault injection
_LAZY = {
    "make_mesh": ("parallel.mesh", "make_mesh"),
    "init_multihost": ("parallel.mesh", "init_multihost"),
    "simulate_cpu_devices": ("parallel.mesh", "simulate_cpu_devices"),
    "make_pipeline_step": ("parallel.pipeline", "make_pipeline_step"),
    "make_pipeline_grad_fn": ("parallel.pipeline", "make_pipeline_grad_fn"),
    "make_pipeline_loss_fn": ("parallel.pipeline", "make_pipeline_loss_fn"),
    "make_pipeline_forward": ("parallel.pipeline", "make_pipeline_forward"),
    "make_pipeline_generate_fn": ("parallel.pipelined_decode",
                                  "make_pipeline_generate_fn"),
    "fsdp_shard_params": ("parallel.pipeline", "fsdp_shard_params"),
    "register_schedule": ("parallel.schedules", "register_schedule"),
    "compile_schedule": ("parallel.schedules", "compile_schedule"),
    "fit": ("utils.train", "fit"),
    "evaluate": ("utils.train", "evaluate"),
    "make_eval_fn": ("utils.train", "make_eval_fn"),
    "run_all_experiments": ("utils.sweep", "run_all_experiments"),
    "run_one_experiment": ("utils.sweep", "run_one_experiment"),
    "MoEConfig": ("models.moe", "MoEConfig"),
    "AnomalyGuard": ("utils.resilience", "AnomalyGuard"),
    "CheckpointManager": ("utils.resilience", "CheckpointManager"),
    "FaultPlan": ("utils.resilience", "FaultPlan"),
    "Request": ("serving", "Request"),
    "ServingEngine": ("serving", "ServingEngine"),
    "make_serving_step_fn": ("serving", "make_serving_step_fn"),
    "run_serve_bench": ("serving.bench", "run_serve_bench"),
    "run_paged_bench": ("serving.bench", "run_paged_bench"),
    # static analysis (docs/static_analysis.md)
    "check_table": ("analysis", "check_table"),
    "TableReport": ("analysis", "TableReport"),
    "audit_fn": ("analysis", "audit_fn"),
    "lint_repo": ("analysis", "lint_repo"),
    "run_checks": ("analysis", "run_checks"),
}


def __getattr__(name):
    if name in _LAZY:
        import importlib
        mod, attr = _LAZY[name]
        value = getattr(importlib.import_module(f".{mod}", __name__), attr)
        globals()[name] = value  # cache: next access skips __getattr__
        return value
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def __dir__():
    return sorted(set(globals()) | set(__all__))  # completion sees lazy names


__all__ = [
    "ModelConfig",
    "MeshConfig",
    "ScheduleConfig",
    "RunConfig",
    "virtual_stages_for",
    *sorted(_LAZY),
]

__version__ = "0.2.0"

_import_done()
