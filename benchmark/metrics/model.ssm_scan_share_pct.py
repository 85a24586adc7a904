"""Share of the device's busy time spent in the chunked state-space scan, all phases (region
``model/ssm_scan``, ``ops/mamba2.py:ssd_chunked``: the masked ``C B^T`` products
inside a chunk and the scan over chunk states; what a kernel would replace). Union seconds over the planes'
summed busy seconds (``harness/scopes.py``); nothing to read, and no metric,
where the program names no such region. A place to look, not a verdict: only
``train.tokens_per_s`` says a change helped."""

LAYER = "model"
UNIT = "%"
BETTER = "lower"
MOVES = "train.tokens_per_s"
SOURCE = "device_trace"


def read(run):
    from benchmark.harness.scopes import share_pct
    return share_pct(run, "regions", "model/ssm_scan")
