"""Share of the device's busy time spent between a latent-attention layer's
normed input and the attention core's operands, all phases (region
``model/mla_latent``, ``ops/attention.py:mla_project``: both down-projections,
the latent norms, both up-projections, the rotary turn, building ``k`` from
its 128-wide part and the one rotary key all heads share). Union seconds over
the planes' summed busy seconds (``harness/scopes.py``); nothing to read, and
no metric, where the program names no such region. A place to look, not a
verdict: only ``train.tokens_per_s`` says a change helped."""

LAYER = "model"
UNIT = "%"
BETTER = "lower"
MOVES = "train.tokens_per_s"
SOURCE = "device_trace"

REGION = "model/mla_latent"


def read(run):
    from benchmark.harness.scopes import share_pct
    regions = run.get("regions")
    if not regions or not any(REGION in p["regions"]
                              for p in regions["planes"]):
        return None
    return share_pct(run, "regions", REGION)
