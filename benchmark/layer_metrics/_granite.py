"""What the readers of model ``granite_hybrid``'s cell share. The traced
span's steps come from the program's own spans: a step's rows ``(new,
context)`` and its state rows (``ssm_slots``, ``ssm_fresh``,
``ssm_decode_rows``, ``ssm_prefill_rows``) from ``serving.dispatch``, the
tokens it chose from ``serving.commit`` (``_smallthinker.span_steps``). A
program that writes none of it leaves the metrics out."""
from benchmark.layer_metrics._smallthinker import (  # noqa: F401
    kernel_roofline, span_rows)

STATE_ARGS = ("ssm_slots", "ssm_fresh", "ssm_decode_rows",
              "ssm_prefill_rows")


def sizes(run):
    import benchmark.weights_granite_hybrid as W
    return W.sizes(run["cfg"])


def state_itemsize(run) -> int:
    """Bytes a number of the state rows holds: the model's dtype's."""
    import numpy as np
    return np.dtype(run["cfg"].get("dtype", "bfloat16")).itemsize


def state_steps(run):
    """``[{ssm_slots, ssm_fresh, ssm_decode_rows, ssm_prefill_rows}]``, a
    step of the traced span whose dispatch span carries them; None where
    none does (a program without state rows, or no trace)."""
    from benchmark import spans
    path = spans.find_path(run) if run.get("traced") else None
    if not path:
        return None
    steps = [{k: float(stats[k]) for k in STATE_ARGS}
             for name, _, _, stats in spans.load(path).host
             if name == "serving.dispatch"
             and all(k in stats for k in STATE_ARGS)]
    return steps or None
