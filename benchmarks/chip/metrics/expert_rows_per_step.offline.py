"""Mean rows each held expert computes per expert layer of a decode step:
the held (token, choice) pairs of the window's decode steps
(``repro_moe_decode_held_pairs_total``) over its decode layer-steps (one
sample of ``repro_moe_max_expert_rows`` each) and the held experts (the
family's ``Spec.held``). Only the rows of live slots count, so at
uniform routing it is the live slots x top_k / n_experts. The counters are the scheduler's ``ExpertRouting``
(observability on, ``--trace 1``); a program without them reads None."""
from stats import hist_delta

PAIRS = "repro_moe_decode_held_pairs_total"


def read(run):
    a = run["status0"].get("metrics", {}).get("counters", {}).get(PAIRS)
    b = run["status1"].get("metrics", {}).get("counters", {}).get(PAIRS)
    layers = hist_delta(run, "repro_moe_max_expert_rows")
    held = getattr(run["spec"], "held", None)
    if a is None or b is None or layers is None or not held:
        return None
    return (b - a) / layers[0] / held
