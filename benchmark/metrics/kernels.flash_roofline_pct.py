"""Flash attention against its roofline: the least time the chip could take
for the calls the trace holds - per call max(FLOPs / peak FLOP/s, bytes /
peak bytes/s), from the call's shapes (``benchmark/flops/flash.py``) - over
the time those calls took on the device. Which instruction is which kernel,
and how many rows a call takes, is read from the compiled step
(``harness/kernels.py``). Prints which peak bounds."""

LAYER = "kernels"
UNIT = "%"
BETTER = "higher"
MOVES = "train.tokens_per_s"
SOURCE = "device_trace"


COSTS = (("_flash_fwd_kernel", "fwd"), ("_flash_bwd_kernel", "bwd"))


def read(run):
    from benchmark.flops import flash
    from benchmark.harness.peaks import peak
    from benchmark.harness.trace_reduce import label_seconds
    trace = run["trace"]
    if trace is None:
        return None
    rows1, seq, heads, head_dim = run["family"].flash_call_shape(
        run["config"]["sizes"], 1, run["workload"]["seq"])
    chip = peak(run["device_kind"])
    least = took = 0.0
    for name, call in run["pallas_calls"].items():
        cost = next((c for k, c in COSTS if call["kernel"].startswith(k)), None)
        seconds, calls = label_seconds(trace, f"^{name}$", "by_call")
        if cost is None or not calls:
            continue
        rows, rest = divmod(call["out_elements"], seq * heads * head_dim)
        if rest or not rows:
            raise ValueError(f"{name} ({call['kernel']}): {call['out_elements']} "
                             f"output elements are no whole rows of "
                             f"{seq} x {heads} x {head_dim}")
        floor, bound = flash.least_seconds(
            *getattr(flash, cost)(rows, seq, heads, head_dim), chip)
        run["log"](f"{name} = {call['kernel']}: {calls} calls of {rows} rows, "
                   f"{seconds / calls * 1e6:.1f} us each, {floor * 1e6:.1f} us "
                   f"at the roofline ({bound}-bound): "
                   f"{100 * floor * calls / seconds:.1f}%")
        least += floor * calls
        took += seconds
    return 100.0 * least / took if took else None
