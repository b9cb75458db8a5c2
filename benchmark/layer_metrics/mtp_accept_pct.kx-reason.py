def read(run):
    """Drafts the model's own choice confirmed over drafts verified, over
    the whole run (``engine.stats()["drafts"]``), in percent. With seeded
    weights the drafter agrees with the model at chance."""
    from benchmark.layer_metrics._kexaone import drafts
    d = drafts(run)
    if not d or d.get("drafted", 0) <= 0:
        return None
    return 100.0 * d["accepted"] / d["drafted"]
