"""Score a checkpoint's loss/perplexity on a token file.

    python scripts/eval.py --model gpt2-small --ckpt /tmp/ckpt \
        --data-file corpus.bin --batches 32

``--ckpt`` accepts the layouts scripts/train.py --resume does (fit() step
dirs or a bare params checkpoint); only the params subtree is read. Eval
runs the forward-only pipelined loss over a ``--pipe``-stage mesh
(default 1 — the whole model on one chip).
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", required=True,
                    help="gpt2-*, llama*, mistral*, qwen2-*, gemma-*, or ref")
    ap.add_argument("--ckpt", required=True)
    ap.add_argument("--data-file", required=True)
    ap.add_argument("--batches", type=int, default=32)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--pipe", type=int, default=1)
    ap.add_argument("--data", type=int, default=1)
    ap.add_argument("--tp", type=int, default=1,
                    help="model-axis size (Megatron TP inside stages)")
    ap.add_argument("--sp", type=int, default=1,
                    help="seq-axis size (ring/Ulysses sequence parallelism)")
    ap.add_argument("--sp-attn", default="ring", choices=["ring", "ulysses"])
    ap.add_argument("--virtual", type=int, default=1,
                    help="virtual chunks per device (wrap placement)")
    ap.add_argument("--vocab-parallel", action="store_true",
                    help="Megatron vocab-parallel CE over the model axis")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--dim", type=int, default=0)
    ap.add_argument("--ffn", type=int, default=0)
    ap.add_argument("--layers", type=int, default=0)
    ap.add_argument("--heads", type=int, default=0)
    ap.add_argument("--vocab", type=int, default=0)
    ap.add_argument("--dtype", default="float32")
    ap.add_argument("--param-dtype", default=None,
                    help="storage dtype of the checkpoint's params (mirror "
                         "scripts/train.py --param-dtype for mixed-precision "
                         "checkpoints, e.g. --dtype bfloat16 "
                         "--param-dtype float32)")
    ap.add_argument("--simulate-devices", type=int, default=0)
    args = ap.parse_args()

    if args.simulate_devices:
        from distributed_training_with_pipeline_parallelism_tpu.parallel.mesh import (
            simulate_cpu_devices)
        simulate_cpu_devices(args.simulate_devices)
    from distributed_training_with_pipeline_parallelism_tpu.utils.compile_cache import (
        enable_compile_cache)
    enable_compile_cache()
    import jax

    import distributed_training_with_pipeline_parallelism_tpu as dtpp
    from distributed_training_with_pipeline_parallelism_tpu.models import (
        transformer as tfm)
    from distributed_training_with_pipeline_parallelism_tpu.models.gpt2 import (
        gpt2_config)
    from distributed_training_with_pipeline_parallelism_tpu.models.llama import (
        llama_config)
    from distributed_training_with_pipeline_parallelism_tpu.parallel.mesh import (
        make_mesh)
    from distributed_training_with_pipeline_parallelism_tpu.utils import train
    from distributed_training_with_pipeline_parallelism_tpu.utils.checkpoint import (
        restore_checkpoint, restore_subtree)
    from distributed_training_with_pipeline_parallelism_tpu.utils.data import (
        TokenFileDataset)

    def build_cfg(**overrides):
        if args.model.startswith("gpt2-"):
            return gpt2_config(args.model.removeprefix("gpt2-"), **overrides)
        if args.model.startswith(("llama", "mistral", "qwen2", "gemma")):
            return llama_config(args.model, **overrides)
        if args.model == "ref":
            return dtpp.ModelConfig(**overrides)
        raise SystemExit(f"unknown model {args.model}")

    overrides = {k: v for k, v in dict(
        dim=args.dim, ffn_dim=args.ffn, n_layers=args.layers,
        n_heads=args.heads, vocab_size=args.vocab).items() if v}
    overrides["dtype"] = args.dtype
    if args.param_dtype:
        overrides["param_dtype"] = args.param_dtype
    if args.dim and not args.ffn:
        base = build_cfg()
        overrides["ffn_dim"] = max(1, round(base.ffn_dim * args.dim / base.dim))
    cfg = build_cfg(**overrides)

    params_t = jax.eval_shape(
        lambda: tfm.transformer_init(jax.random.key(0), cfg))
    path = args.ckpt
    latest = train._latest_step_dir(path)
    if latest is not None:
        path = latest[1]
    if os.path.basename(os.path.normpath(path)).startswith("step_"):
        params = restore_subtree(path, "params", params_t)
    else:
        params = restore_checkpoint(path, template=params_t)
    print(f"loaded {path}", flush=True)

    mesh = make_mesh(n_pipe=args.pipe, n_data=args.data, n_model=args.tp,
                     n_seq=args.sp)
    # the checkpoint's arrays carry their TRAINING-time placement (e.g. a
    # 2-device pipe mesh); re-place onto the eval mesh so the jitted loss
    # accepts them whatever mesh it spans. Under --tp the layer matrices go
    # straight to their Megatron shards (no full per-device replica spike —
    # the point of TP eval for models that don't fit one chip); otherwise
    # replicated.
    from jax.sharding import NamedSharding, PartitionSpec
    if args.tp > 1:
        from distributed_training_with_pipeline_parallelism_tpu.parallel.tensor_parallel import (
            param_specs)
        specs = param_specs(cfg)
        if cfg.tie_embeddings:
            specs["head"].pop("out")  # tied head has no out leaf
        params = jax.tree.map(
            lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
            params, specs, is_leaf=lambda x: isinstance(x, PartitionSpec))
    else:
        params = jax.tree.map(
            lambda x: jax.device_put(x, NamedSharding(mesh, PartitionSpec())),
            params)
    sched = dtpp.ScheduleConfig(name="GPipe",
                                n_microbatches=args.microbatches,
                                n_virtual=args.virtual)
    eval_fn = train.make_eval_fn(cfg, mesh, sched, sp_attn_impl=args.sp_attn,
                                 tp_vocab_parallel=args.vocab_parallel)
    data = TokenFileDataset(args.data_file, args.seq, seed=123).batches(
        args.batch)
    metrics = train.evaluate(eval_fn, params, data, args.batches)
    print(json.dumps({"model": args.model, **metrics}))


if __name__ == "__main__":
    main()
