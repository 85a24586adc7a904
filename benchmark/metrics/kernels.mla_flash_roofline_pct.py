"""The flash kernels at latent attention's two widths against their
roofline: the least time the chip could take for the calls the trace holds -
per call max(FLOPs / peak FLOP/s, bytes / peak bytes/s), counted at the
queries' and keys' width and at the values' (``benchmark/flops/
flash_two_widths.py``) - over the time those calls took on the device. Which
instruction is which kernel, and how many rows a call takes, is read from the
compiled step (``harness/kernels.py``): the forward's first output is O, at
the values' width, the backward's dQ, at the queries'. Prints which peak
bounds. Nothing to read where the configuration names no two widths or the
program ran no such call."""

LAYER = "kernels"
UNIT = "%"
BETTER = "higher"
MOVES = "train.tokens_per_s"
SOURCE = "device_trace"


# kernel-name prefix -> (the count, which width its first output has)
COSTS = (("_flash_fwd_kernel", "fwd", "v"), ("_flash_bwd_kernel", "bwd", "qk"))


def read(run):
    from benchmark.flops import flash_two_widths as flash
    from benchmark.flops.flash import least_seconds
    from benchmark.harness.peaks import peak
    from benchmark.harness.trace_reduce import label_seconds
    trace, sizes = run.get("trace"), run["config"]["sizes"]
    if trace is None or "qk_nope_head_dim" not in sizes:
        return None
    seq, heads = run["workload"]["seq"], sizes["num_attention_heads"]
    width = {"qk": sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"],
             "v": sizes["v_head_dim"]}
    chip = peak(run["device_kind"])
    least = took = 0.0
    for name, call in run["pallas_calls"].items():
        cost = next((c for c in COSTS if call["kernel"].startswith(c[0])), None)
        seconds, calls = label_seconds(trace, f"^{name}$", "by_call")
        if cost is None or not calls:
            continue
        rows, rest = divmod(call["out_elements"], seq * heads * width[cost[2]])
        if rest or not rows:
            raise ValueError(f"{name} ({call['kernel']}): {call['out_elements']}"
                             f" output elements are no whole rows of {seq} x "
                             f"{heads} x {width[cost[2]]}")
        floor, bound = least_seconds(*getattr(flash, cost[1])(
            rows, seq, heads, width["qk"], width["v"]), chip)
        run["log"](f"{name} = {call['kernel']}: {calls} calls of {rows} rows "
                   f"at {width['qk']} | {width['v']}, "
                   f"{seconds / calls * 1e6:.1f} us each, {floor * 1e6:.1f} us "
                   f"at the roofline ({bound}-bound): "
                   f"{100 * floor * calls / seconds:.1f}%")
        least += floor * calls
        took += seconds
    return 100.0 * least / took if took else None
