"""Train a model-ladder config under a pipeline schedule.

Examples:
    # tiny smoke run on 4 simulated devices
    python scripts/train.py --model gpt2-small --layers 8 --pipe 4 \
        --schedule 1F1B --microbatches 8 --steps 20 --simulate-devices 4 \
        --dim 128 --heads 4 --seq 64 --batch 16

    # Llama-debug, interleaved, with checkpointing
    python scripts/train.py --model llama-debug --pipe 2 --virtual 2 \
        --schedule Interleaved1F1B --steps 100 --ckpt /tmp/ckpt
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from distributed_training_with_pipeline_parallelism_tpu.utils.config import (  # noqa: E402
    SCHEDULE_NAMES)


def main(argv=None):
    """Parse ``argv`` (default ``sys.argv[1:]``), train, and return
    ``(params, history)`` so in-process callers (``chip_smoke.py``) drive
    exactly the command-line path."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="gpt2-small",
                    help="gpt2-{small,medium,large,xl}, llama2-7b, llama3-8b, "
                         "llama-debug, nemotron-h-{stage,debug,joyai-stage,"
                         "joyai-debug,lfm2-stage,lfm2-debug} (a patterned "
                         "stack of Mamba-2 / attention / expert layers, of "
                         "latent attention / MLP / experts, or of short "
                         "convolutions / attention / MLP / experts: --pipe 1, "
                         "no --tp/--sp/--ep), or ref (the reference parity "
                         "model)")
    ap.add_argument("--schedule", default="1F1B", choices=list(SCHEDULE_NAMES))
    ap.add_argument("--pipe", type=int, default=2)
    ap.add_argument("--data", type=int, default=1)
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel (model-axis) size; composes with "
                         "--pipe/--data into a 3-D mesh")
    ap.add_argument("--sp", type=int, default=1,
                    help="sequence-parallel (seq-axis) size: ring/Ulysses "
                         "attention inside pipeline stages; composes with "
                         "the other axes (4-D with --tp)")
    ap.add_argument("--sp-attn", default="ring", choices=["ring", "ulysses"],
                    help="sequence-parallel attention transport")
    ap.add_argument("--zero1", action="store_true",
                    help="ZeRO-1: shard optimizer state over the data axis")
    ap.add_argument("--fsdp", action="store_true",
                    help="pp x fsdp (ZeRO-3 in-pipeline): layer params rest "
                         "pipe x data sharded with just-in-time chunk "
                         "gathers; grads/moments inherit the sharding "
                         "(needs --data > 1; dense meshes only)")
    ap.add_argument("--vocab-parallel", action="store_true",
                    help="Megatron parallel cross-entropy: vocab-shard the "
                         "head over the --tp model axis (logits never "
                         "materialize full-size)")
    ap.add_argument("--backward", default="auto",
                    choices=["auto", "remat", "stored"],
                    help="pipeline backward policy. auto (default): the "
                         "unrolled stored program at --pipe 1, the "
                         "rematerializing backward at --pipe > 1 (the "
                         "measured-fastest choice per config, "
                         "docs/performance.md). remat: always recompute "
                         "each stage forward (minimal activation memory). "
                         "stored: never recompute (banked activations; "
                         "not valid for ZB schedules or --fsdp)")
    ap.add_argument("--virtual", type=int, default=1)
    ap.add_argument("--microbatches", type=int, default=4)
    ap.add_argument("--grad-accum", type=int, default=1,
                    help="accumulate gradients over k batches per optimizer "
                         "update (on top of per-step microbatching)")
    ap.add_argument("--param-dtype", default="",
                    help="master-weight dtype; 'float32' with "
                         "--dtype bfloat16 is the mixed-precision recipe "
                         "(bf16 compute, fp32 weights/grads/moments)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--weight-decay", type=float, default=0.01)
    ap.add_argument("--warmup-steps", type=int, default=100)
    ap.add_argument("--max-grad-norm", type=float, default=1.0)
    ap.add_argument("--dropout", type=float, default=0.0,
                    help="train-mode dropout rate (torch's "
                         "TransformerDecoderLayer default is 0.1); masks are "
                         "seeded from --seed and independent of the mesh")
    ap.add_argument("--dtype", default="float32")
    ap.add_argument("--flash", default=None, nargs="?", const="on",
                    choices=["on", "off", "auto"],
                    help="Pallas fused flash attention (bare --flash means "
                         "on; default auto: on for causal seq>=256 on TPU, "
                         "where the whole step measures faster; see "
                         "docs/performance.md)")
    ap.add_argument("--fused-xent", action="store_true",
                    help="Pallas fused cross-entropy loss")
    ap.add_argument("--ckpt", default="",
                    help="checkpoint dir: step-numbered saves + final")
    ap.add_argument("--ckpt-every", type=int, default=0,
                    help="save every N steps (default: final only)")
    ap.add_argument("--auto-resume", action="store_true",
                    help="resume from the newest checkpoint in --ckpt")
    ap.add_argument("--keep-last", type=int, default=0,
                    help="retention GC: keep only the newest K committed "
                         "checkpoints in --ckpt (0 keeps everything)")
    ap.add_argument("--anomaly-guard", action="store_true",
                    help="jitted finite-check on loss/grad-norm each step; "
                         "non-finite steps are skipped (params/opt state "
                         "held) instead of poisoning the run")
    ap.add_argument("--anomaly-budget", type=int, default=3,
                    help="abort (after a final checkpoint) once this many "
                         "CONSECUTIVE steps are non-finite")
    ap.add_argument("--preemption-safe", action="store_true",
                    help="catch SIGTERM/SIGINT, finish the in-flight step, "
                         "write a sync checkpoint to --ckpt, exit resumable")
    ap.add_argument("--stall-timeout", type=float, default=0.0,
                    help="wall-clock watchdog: log stall diagnostics when no "
                         "step completes for this many seconds (0 disables)")
    ap.add_argument("--dynamics", action="store_true",
                    help="training-dynamics observatory: per-stage grad "
                         "stats, gradient-noise scale, and loss-spike "
                         "forensics (bundles need --report-dir); stats ride "
                         "the existing log syncs (docs/observability.md §7)")
    ap.add_argument("--report-dir", default="",
                    help="write a structured RunReport (events.jsonl + "
                         "report.json manifest, plus any forensic bundles) "
                         "into this dir")
    ap.add_argument("--metrics", default="",
                    help="append per-log-point JSON lines here")
    ap.add_argument("--profile", default="",
                    help="capture a jax.profiler trace of 3 steady-state "
                         "steps into this dir (view in XProf/TensorBoard)")
    ap.add_argument("--resume", default="", help="params checkpoint to load")
    ap.add_argument("--simulate-devices", type=int, default=0)
    # overrides to scale models down for smoke runs
    ap.add_argument("--dim", type=int, default=0,
                    help="override model width; ffn_dim rescales "
                         "proportionally unless --ffn is also given")
    ap.add_argument("--ffn", type=int, default=0)
    ap.add_argument("--layers", type=int, default=0)
    ap.add_argument("--heads", type=int, default=0)
    ap.add_argument("--vocab", type=int, default=0,
                    help="override vocab size (e.g. 256 for byte-level "
                         "corpora from encode_text_file)")
    ap.add_argument("--tie-embeddings", action="store_true",
                    help="tie the output head to the token embedding "
                         "(GPT-2-upstream / Llama-3.2 style)")
    ap.add_argument("--pad-id", type=int, default=-1,
                    help="ignore-index: target positions with this id are "
                         "excluded from the loss (right-padded batches); "
                         "-1 disables")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--data-file", default="",
                    help="flat binary token file (uint16 ids); default is "
                         "the reference's synthetic random-token regime")
    ap.add_argument("--eval-file", default="",
                    help="held-out token file; with --eval-every, score "
                         "eval loss + perplexity on it during training")
    ap.add_argument("--eval-every", type=int, default=0,
                    help="evaluate every N steps (and at the end)")
    ap.add_argument("--eval-batches", type=int, default=8)
    ap.add_argument("--native-loader", action="store_true",
                    help="read --data-file through the C++ prefetching "
                         "loader (csrc/data_loader.cpp)")
    ap.add_argument("--loader-threads", type=int, default=1,
                    help="native-loader worker threads; 1 (default) keeps "
                         "the batch stream deterministic in --seed, which "
                         "--auto-resume's data replay depends on")
    ap.add_argument("--prefetch", type=int, default=2,
                    help="device-prefetch depth (0 disables)")
    ap.add_argument("--moe-experts", type=int, default=0,
                    help="train a Mixture-of-Experts LM with this many "
                         "experts (MoE blocks replace dense FFNs)")
    ap.add_argument("--moe-topk", type=int, default=2)
    ap.add_argument("--moe-capacity", type=float, default=1.25,
                    help="capacity factor (slots per expert scale)")
    ap.add_argument("--moe-aux", type=float, default=0.01,
                    help="load-balancing aux loss weight")
    ap.add_argument("--ep", type=int, default=1,
                    help="expert-parallel (expert-axis) size; requires "
                         "--moe-experts divisible by it")
    args = ap.parse_args(argv)
    if args.native_loader and not args.data_file:
        ap.error("--native-loader requires --data-file")
    if args.ep > 1 and not args.moe_experts:
        ap.error("--ep requires --moe-experts")
    # --moe-experts composes with --tp (round 3: Megatron-split expert
    # matmuls) and --sp (round 5: seq-sharded MoE stages, incl. dropout);
    # the library's _check_moe_mesh validates shape/arch contracts loudly
    if args.moe_experts and not args.model.startswith("gpt2-"):
        ap.error("--moe-experts uses gpt2-style blocks; pick a gpt2-* model")
    # --sp-attn ulysses composes with --tp since round 5 (the Megatron
    # head shard all-to-alls over 'seq' within each model column); the
    # library validates head-count divisibility
    if args.vocab_parallel and args.tp <= 1:
        ap.error("--vocab-parallel requires --tp > 1")
    if args.auto_resume and not args.ckpt:
        ap.error("--auto-resume requires --ckpt (the dir holding step_N/)")
    if args.keep_last and not args.ckpt:
        ap.error("--keep-last requires --ckpt")
    if args.preemption_safe and not args.ckpt:
        ap.error("--preemption-safe requires --ckpt (it must have somewhere "
                 "to save the resumable state)")

    if args.simulate_devices:
        from distributed_training_with_pipeline_parallelism_tpu.parallel.mesh import (
            simulate_cpu_devices)
        simulate_cpu_devices(args.simulate_devices)
    from distributed_training_with_pipeline_parallelism_tpu.utils.compile_cache import (
        enable_compile_cache)
    enable_compile_cache()
    import jax

    import distributed_training_with_pipeline_parallelism_tpu as dtpp
    from distributed_training_with_pipeline_parallelism_tpu.models.gpt2 import gpt2_config
    from distributed_training_with_pipeline_parallelism_tpu.models.llama import llama_config
    from distributed_training_with_pipeline_parallelism_tpu.parallel.mesh import make_mesh
    from distributed_training_with_pipeline_parallelism_tpu.utils import train
    from distributed_training_with_pipeline_parallelism_tpu.utils.checkpoint import (
        restore_checkpoint)
    from distributed_training_with_pipeline_parallelism_tpu.utils.resilience import (
        AnomalyGuard)

    def build_cfg(**overrides):
        if args.model.startswith("gpt2-"):
            return gpt2_config(args.model.removeprefix("gpt2-"), **overrides)
        if args.model.startswith(("llama", "mistral", "qwen2", "gemma")):
            return llama_config(args.model, **overrides)
        if args.model.startswith("nemotron-h-"):
            from distributed_training_with_pipeline_parallelism_tpu.models.nemotron_h import (
                nemotron_h_config)
            return nemotron_h_config(args.model.removeprefix("nemotron-h-"),
                                     **overrides)
        if args.model == "ref":
            return dtpp.ModelConfig(**overrides)
        raise SystemExit(f"unknown model {args.model}")

    overrides = {k: v for k, v in dict(
        dim=args.dim, ffn_dim=args.ffn, n_layers=args.layers,
        n_heads=args.heads, vocab_size=args.vocab,
    ).items() if v}
    overrides["dtype"] = args.dtype
    if args.pad_id >= 0:
        overrides["pad_token_id"] = args.pad_id
    if args.tie_embeddings:
        overrides["tie_embeddings"] = True
    if args.param_dtype:
        overrides["param_dtype"] = args.param_dtype
    if args.dropout:
        overrides["dropout"] = args.dropout
    if args.flash is not None:
        overrides["use_flash_attention"] = {
            "on": True, "off": False, "auto": "auto"}[args.flash]
    if args.fused_xent:
        overrides["use_fused_xent"] = True
    if args.dim and not args.ffn:
        # keep the family's FFN:dim ratio when scaling width down/up
        base = build_cfg()
        overrides["ffn_dim"] = max(1, round(base.ffn_dim * args.dim / base.dim))
    cfg = build_cfg(**overrides)

    moe = None
    if args.moe_experts:
        from distributed_training_with_pipeline_parallelism_tpu.models.moe import (
            MoEConfig)
        moe = MoEConfig(n_experts=args.moe_experts, top_k=args.moe_topk,
                        capacity_factor=args.moe_capacity,
                        aux_loss_weight=args.moe_aux)

    mesh = make_mesh(n_pipe=args.pipe, n_data=args.data, n_model=args.tp,
                     n_seq=args.sp, n_expert=args.ep)
    sched = dtpp.ScheduleConfig(name=args.schedule,
                                n_microbatches=args.microbatches,
                                n_virtual=args.virtual)
    moe_desc = f" MoE E={args.moe_experts}" if moe else ""
    print(f"model={args.model}{moe_desc} {cfg.dim}d x {cfg.n_layers}L x "
          f"{cfg.n_heads}H, mesh=(data={args.data}, pipe={args.pipe}, "
          f"model={args.tp}, seq={args.sp}, expert={args.ep}), "
          f"{args.schedule} M={args.microbatches} V={args.virtual}", flush=True)

    optimizer = train.adamw(
        learning_rate=args.lr, weight_decay=args.weight_decay,
        warmup_steps=args.warmup_steps, max_grad_norm=args.max_grad_norm,
        total_steps=max(1, args.steps // args.grad_accum))

    def init_params(key):
        # born in the layout they rest in: no device holds the whole model
        return train.init_params(cfg, mesh, key, moe=moe, fsdp=args.fsdp,
                                 tp_vocab_parallel=args.vocab_parallel)

    if args.resume:
        import jax.numpy as jnp
        params_t = jax.eval_shape(init_params, jax.random.key(args.seed))
        # Accept either layout: a fit()-style dir of step_N/ trees
        # ({'params','opt_state','step'}), a single step_N dir, or a bare
        # params checkpoint (e.g. converted HF weights).
        path = args.resume
        latest = train._latest_step_dir(path)
        if latest is not None:
            path = latest[1]
        if os.path.basename(os.path.normpath(path)).startswith("step_"):
            # fit()-style full training state. The saved opt_state reflects
            # fit's own wrapping: --grad-accum > 1 checkpoints a
            # MultiStepsState, so the template must mirror it.
            import optax
            tmpl_opt = (optax.MultiSteps(optimizer,
                                         every_k_schedule=args.grad_accum)
                        if args.grad_accum > 1 else optimizer)
            state = restore_checkpoint(path, template={
                "params": params_t,
                "opt_state": jax.eval_shape(tmpl_opt.init, params_t),
                "step": jnp.asarray(0)})
            params = state["params"]
        else:  # bare params checkpoint (e.g. converted HF weights)
            params = restore_checkpoint(path, template=params_t)
        print(f"loaded params from {path}", flush=True)
    else:
        params = init_params(jax.random.key(args.seed))

    from distributed_training_with_pipeline_parallelism_tpu.utils.data import (
        TokenFileDataset, batch_sharding, prefetch_to_device,
        token_file_dtype)
    import numpy as np
    if (args.data_file and args.native_loader
            and token_file_dtype(args.data_file) != np.uint16):
        raise SystemExit("--native-loader reads uint16 token files; this "
                         "corpus's .meta.json sidecar says otherwise — "
                         "drop --native-loader for it")
    if args.data_file and args.native_loader:
        from distributed_training_with_pipeline_parallelism_tpu.utils.data_native import (
            NativeTokenLoader)
        data = NativeTokenLoader(args.data_file, args.seq, args.batch,
                                 seed=args.seed,
                                 n_threads=args.loader_threads).batches()
    elif args.data_file:
        data = TokenFileDataset(args.data_file, args.seq,
                                seed=args.seed).batches(args.batch)
    else:
        data = train.synthetic_data(cfg, args.batch, args.seq, seed=args.seed)
    if args.prefetch > 0:
        data = prefetch_to_device(data, depth=args.prefetch,
                                  sharding=batch_sharding(mesh))

    eval_data = None
    if args.eval_every:
        # --eval-file if given; else the training file (NOT held out — still
        # useful as a fixed-batch progress probe); synthetic only when
        # training is synthetic too (scoring a real-text model on random
        # tokens would read as a huge, meaningless loss)
        eval_src = args.eval_file or args.data_file
        if eval_src:
            eval_data = lambda: TokenFileDataset(  # noqa: E731
                eval_src, args.seq, seed=123).batches(args.batch)
        else:
            eval_data = lambda: train.synthetic_data(  # noqa: E731
                cfg, args.batch, args.seq, seed=123)

    on_log = None
    if cfg.arch == "nemotron_h" and "E" in cfg.hybrid_override_pattern:
        from distributed_training_with_pipeline_parallelism_tpu.models.nemotron_h import (
            describe_routing, routing_stats)

        def on_log(i, params, tokens):  # one more forward pass a log point
            print(f"step {i} routing: "
                  f"{describe_routing(routing_stats(cfg, params, tokens))}",
                  flush=True)

    params, history = train.fit(
        cfg, mesh, sched, params, data, args.steps, optimizer=optimizer,
        log_every=max(1, args.steps // 20),
        checkpoint_dir=args.ckpt or None,
        checkpoint_every=(args.ckpt_every or args.steps) if args.ckpt else 0,
        resume=args.auto_resume, metrics_path=args.metrics or None, moe=moe,
        sp_attn_impl=args.sp_attn, tp_vocab_parallel=args.vocab_parallel,
        zero1=args.zero1, fsdp=args.fsdp,
        remat_backward={"auto": None, "remat": True,
                        "stored": False}[args.backward],
        dropout_seed=args.seed,
        eval_data=eval_data, eval_every=args.eval_every,
        eval_batches=args.eval_batches,
        profile_dir=args.profile or None, grad_accum=args.grad_accum,
        keep_last=args.keep_last or None,
        guard=(AnomalyGuard(max_consecutive=args.anomaly_budget)
               if args.anomaly_guard else None),
        handle_preemption=args.preemption_safe,
        stall_timeout_s=args.stall_timeout or None,
        report_dir=args.report_dir or None,
        dynamics=args.dynamics or None, on_log=on_log)
    if args.ckpt:
        print(f"checkpoints in {args.ckpt}", flush=True)
    if history:
        print(f"final loss: {history[-1][1]:.4f}", flush=True)
    return params, history


if __name__ == "__main__":
    main()
