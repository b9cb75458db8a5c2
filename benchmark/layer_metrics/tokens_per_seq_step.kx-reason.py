def read(run):
    """Tokens the decoding sequences emitted over the steps they took (one
    a sequence and step, two where its draft was accepted), over the whole
    run (``engine.stats()["drafts"]``): 1.0 to 2.0."""
    from benchmark.layer_metrics._kexaone import drafts
    d = drafts(run)
    if not d or d.get("decode_seqs", 0) <= 0:
        return None
    return d["emitted"] / d["decode_seqs"]
