"""What the readers of model ``phi4flash``'s cell share. The traced span's
steps come from the program's own spans (``_kexaone.span_steps``): a step's
rows ``(new, context)``, its state rows (``ssm_slots``) and the rows that
ran the cross-decoder and the keys they read there (``cross_rows``,
``cross_keys``) from ``serving.dispatch``, the tokens it chose from
``serving.commit``. A program that writes none of it (one without the
cross-decoder's skip) leaves the metrics that need it out."""
from benchmark.layer_metrics._kexaone import span_steps  # noqa: F401
from benchmark.layer_metrics._smallthinker import (  # noqa: F401
    kernel_roofline, pool_used_pct)


def cross_steps(run):
    """The span's steps whose dispatch carries its rows and its cross
    rows; None where none does."""
    if not run.get("traced"):
        return None
    steps = [s for s in (span_steps(run) or {}).values()
             if "rows" in s and "cross_rows" in s and "cross_keys" in s]
    return steps or None


def rows_of(steps):
    return [r for s in steps for r in s["rows"]]
