"""Model-FLOP utilisation: FLOPs a trained token (``benchmark/flops/model.py``:
3 x forward, causal attention at half, recomputation not counted) x the
untraced window's tokens a second / (chips x the bf16 peak of the device
kind). A fixed multiple of ``train.tokens_per_s`` within a cell."""

LAYER = "optimizer step"
UNIT = "%"
BETTER = "higher"
MOVES = "train.tokens_per_s"
SOURCE = "host_clock"


def read(run):
    from benchmark.harness.peaks import peak
    flops = run["family"].train_flops_per_token(
        run["config"]["sizes"], run["workload"]["seq"])
    full = run["chips"] * peak(run["device_kind"])["bf16_flops_per_s"]
    return 100.0 * flops * run["tokens_per_s"] / full
