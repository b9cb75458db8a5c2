def read(run):
    """Prompt tokens served from the prefix cache over prompt tokens the
    window took in (cached + prefilled), from the engine's counters."""
    c = run["counters"]
    total = c["prefix_hit_tokens"] + c["prompt_tokens"]
    return 100.0 * c["prefix_hit_tokens"] / total if total > 0 else None
