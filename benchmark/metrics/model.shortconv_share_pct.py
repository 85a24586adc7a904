"""Share of the device's busy time spent in the gated short-convolution
layers, all phases (region ``model/shortconv``, ``models/nemotron_h.py``'s
``C`` kind: the layer's norm, ``ops/shortconv.py``'s two projections, both
gates and the depthwise causal convolution). Union seconds over the planes'
summed busy seconds (``harness/scopes.py``); nothing to read, and no metric,
where the program names no such region. A place to look, not a verdict: only
``train.tokens_per_s`` says a change helped."""

LAYER = "model"
UNIT = "%"
BETTER = "lower"
MOVES = "train.tokens_per_s"
SOURCE = "device_trace"

REGION = "model/shortconv"


def read(run):
    from benchmark.harness.scopes import share_pct
    regions = run.get("regions")
    if not regions or not any(REGION in p["regions"]
                              for p in regions["planes"]):
        return None
    return share_pct(run, "regions", REGION)
