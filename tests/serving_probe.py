"""The logits behind an engine's sampled tokens, for tests that hold a served
model to a reference. The compiled step samples its tokens itself and its
logits never leave the program, so a test that wants them rebuilds the
engine's step around a ``project`` that hands each step's logits to the host
(``jax.debug.callback``) and files, at each harvest, the row of every
sampled token under its request. Serial drives only (``run_until_idle``):
one step is in flight at a harvest."""
import jax
import jax.numpy as jnp
import numpy as np


def keep_logits(engine) -> dict:
    """``{req_id: [logits row of each sampled token, in order]}``, filled as
    ``engine`` runs; installed once an engine, before its first step."""
    kept = getattr(engine, "_kept_logits", None)
    if kept is not None:
        return kept
    assert engine.step_traces == 0, "install before the engine's first step"
    kept, steps = {}, []
    project, harvest = engine._project, engine._harvest

    def keep(hidden):
        out = project(hidden)
        jax.debug.callback(lambda x: steps.append(np.array(x)),
                           out.data[:, 0].astype(jnp.float32))
        return out

    def record():
        if engine._flights:
            jax.effects_barrier()
            flight = engine._flights[0]
            for i, (seq, _, _, samples, _) in enumerate(flight.entries):
                if samples and not seq.done:
                    kept.setdefault(seq.req_id, []).append(steps[-1][i])
        return harvest()

    engine._project = keep
    engine._step = engine._build_step()
    engine._harvest = record
    engine._kept_logits = kept
    return kept
