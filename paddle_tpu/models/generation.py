"""Shared autoregressive decoding loop (the paddle-ecosystem
``model.generate`` surface) used by the zoo's causal LMs.

A model plugs in two hooks:
  * ``prefill(ids)   -> (logits_last [B,1,V], caches)``
  * ``decode(tok, caches) -> (logits [B,1,V], caches)``
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.core import generator as G
from paddle_tpu.core.autograd import no_grad
from paddle_tpu.core.tensor import Tensor

__all__ = ["sample_token", "sample_rows", "generate_loop", "compiled_generate",
           "decode_surfaces"]


def decode_surfaces(model, state):
    """The zoo family seam shared by every compiled decode path
    (``compiled_generate`` and ``serving.ServingEngine``): returns
    ``(backbone, project, dtype)``. Llama keeps the trunk at
    ``model.model`` plus a ``_logits`` projector; the MoE LM's cached
    forward lives on the top Layer with an ``lm_head``. ``dtype`` is
    sniffed from the embedding weight (the KV-cache dtype)."""
    embed_name = next(n for n in state if "embed_tokens" in n
                      and n.endswith("weight"))
    dtype = state[embed_name].dtype
    backbone = getattr(model, "model", None)
    if backbone is None or not callable(backbone):
        backbone = model
    project = model._logits if hasattr(model, "_logits") else model.lm_head
    return backbone, project, dtype

# max live compiled_generate executables per model (LRU-evicted)
_COMPILED_CACHE_CAP = 16


def sample_token(step_logits, temperature: float, top_k: int,
                 top_p: float, key=None):
    """[B, V] logits -> [B] token ids (greedy when temperature == 0).
    ``key`` makes the draw explicit (the compiled loop threads its own
    split chain); default pulls from the global generator stream."""
    if temperature == 0:
        return jnp.argmax(step_logits, -1)
    sl = step_logits / temperature
    if top_k > 0:
        kth = jnp.sort(sl, -1)[:, -top_k][:, None]
        sl = jnp.where(sl < kth, -jnp.inf, sl)
    if top_p < 1.0:
        srt = jnp.sort(sl, -1)[:, ::-1]
        probs = jax.nn.softmax(srt, -1)
        cum = jnp.cumsum(probs, -1)
        cutoff_idx = jnp.sum(cum < top_p, -1)
        cutoff = jnp.take_along_axis(srt, cutoff_idx[:, None], -1)
        sl = jnp.where(sl < cutoff, -jnp.inf, sl)
    return jax.random.categorical(G.next_key() if key is None else key, sl)


def sample_rows(logits, temperature, top_k, top_p, keys):
    """``sample_token`` a row, for traced per-row settings: ``logits``
    [B, V] float32, ``temperature`` / ``top_p`` [B] float32, ``top_k``
    [B] int32 and one key a row -> [B] int32. A row at temperature 0 is
    its argmax (first index on ties); every other row draws what
    ``sample_token(logits[i:i + 1], ..., key=keys[i])`` draws. The sort
    sits under a ``lax.cond`` on "any row samples": a batch of greedy
    rows pays the argmax and nothing else (the serving step's sampler)."""
    greedy = jnp.argmax(logits, -1).astype(jnp.int32)
    V = logits.shape[-1]

    def sampled(_):
        sl = logits / jnp.where(temperature > 0, temperature, 1.0)[:, None]
        # (unstable: values alone are sorted, and the TPU compiler takes
        # 6-9 s over it where the stable sort of a vocabulary takes 20-24)
        asc = jax.lax.sort(sl, dimension=1, is_stable=False)
        kth = jnp.take_along_axis(
            asc, (V - jnp.clip(top_k, 1, V))[:, None], -1)
        cut_k = (top_k > 0)[:, None]
        sl = jnp.where(cut_k & (sl < kth), -jnp.inf, sl)
        # the masked row sorted again is the sorted row masked: one sort
        desc = jnp.where(cut_k & (asc < kth), -jnp.inf, asc)[:, ::-1]
        cum = jnp.cumsum(jax.nn.softmax(desc, -1), -1)
        cutoff = jnp.take_along_axis(
            desc, jnp.sum(cum < top_p[:, None], -1)[:, None], -1)
        sl = jnp.where((top_p < 1.0)[:, None] & (sl < cutoff), -jnp.inf, sl)
        drawn = jax.vmap(lambda k, row: jax.random.categorical(
            k, row[None, :])[0])(keys, sl)
        return jnp.where(temperature > 0, drawn.astype(jnp.int32), greedy)

    return jax.lax.cond(jnp.any(temperature > 0), sampled,
                        lambda _: greedy, None)


def generate_loop(prefill, decode, input_ids, max_new_tokens: int = 32,
                  temperature: float = 1.0, top_k: int = 0,
                  top_p: float = 1.0, eos_token_id=None) -> Tensor:
    """Returns the full sequence [B, S + new] including the prompt.

    The loop EXITS EARLY once every row has emitted ``eos_token_id`` —
    ``new`` is then the step count actually taken, not the full budget,
    and no decode forward runs past the last useful step (rows that
    finish first keep padding with eos until the stragglers catch up;
    guarded by tests/test_serving.py::test_generate_loop_breaks_on_all_eos).
    """
    with no_grad():
        logits, caches = prefill(input_ids)
        out_np = np.asarray(input_ids.data)
        finished = np.zeros(out_np.shape[0], bool)
        for i in range(max_new_tokens):
            step_logits = jnp.squeeze(logits.data, 1)
            nxt_np = np.asarray(sample_token(step_logits, temperature,
                                             top_k, top_p))
            if eos_token_id is not None:
                nxt_np = np.where(finished, eos_token_id, nxt_np)
                finished |= (nxt_np == eos_token_id)
            out_np = np.concatenate([out_np, nxt_np[:, None]], 1)
            if (eos_token_id is not None and finished.all()) or \
                    i == max_new_tokens - 1:
                break  # budget spent: skip the unused final forward
            tok = Tensor(jnp.asarray(nxt_np[:, None]))
            logits, caches = decode(tok, caches)
        return Tensor(jnp.asarray(out_np))


def compiled_generate(model, input_ids, max_new_tokens: int = 32,
                      temperature: float = 0.0, top_k: int = 0,
                      top_p: float = 1.0, eos_token_id=None,
                      prefill_chunk: int = 0,
                      attention_mask=None) -> Tensor:
    """The WHOLE generate loop as one compiled program.

    Prefill + ``max_new_tokens`` decode steps run inside a single jit:
    static-shape KV buffers ([B, S+new, n_kv, hd], written in place with
    ``dynamic_update_slice``), a ``lax.scan`` over decode steps, and an
    explicit split-chain RNG. This is the TPU serving answer to the
    reference's AnalysisPredictor inference path
    (``paddle/fluid/inference/api/analysis_predictor.cc``): no per-token
    python dispatch, no shape churn (the eager loop's growing concat cache
    recompiles nothing here — every step is the same program).

    Token-for-token equal to ``generate_loop`` under greedy decoding
    (``temperature=0``). Early-exit on EOS is not possible inside a
    compiled loop — finished rows keep emitting ``eos_token_id`` and the
    full budget always runs (pass a sensible ``max_new_tokens``).
    Compiled executables are cached on the model per
    (batch, prompt_len, budget, sampling-config) signature.

    ``prefill_chunk > 0`` processes the prompt in chunks of that size
    through the same static KV cache (the attention's offset-causal mask
    covers chunked prefill natively): peak prefill attention memory drops
    from O(S·L) scores to O(chunk·L) — the long-prompt serving shape. The
    prompt length must divide evenly; outputs are identical to one-shot
    prefill.

    ``attention_mask`` ([B, S], 1 real / 0 pad) serves a batch of UNEQUAL
    prompts — the standard serving shape. Prompts must be LEFT-padded
    (pads then tokens; validated eagerly): rows stay right-aligned so
    every row appends generated tokens at the same buffer index, per-row
    RoPE offsets put each row's first real token at position 0, and a
    key-liveness mask keeps pads out of every attention window
    (reference mask threading: ``nn/layer/transformer.py:84``
    ``_convert_attention_mask``). Each row's output is token-for-token
    equal to generating its prompt alone. The mask is a traced INPUT:
    serving batches with different pad patterns reuse one executable.
    """
    from paddle_tpu.jit.functional import functional_state, swap_state

    cfg = model.cfg
    train, frozen, buffers = functional_state(model)
    st = {**train, **frozen, **buffers}
    ids_arr = input_ids.data if isinstance(input_ids, Tensor) \
        else jnp.asarray(input_ids)
    B, S = int(ids_arr.shape[0]), int(ids_arr.shape[1])
    mnt = int(max_new_tokens)
    if mnt <= 0:
        raise ValueError("max_new_tokens must be positive")
    L = S + mnt
    nl = cfg.num_hidden_layers
    n_kv = cfg.num_key_value_heads
    hd = cfg.hidden_size // cfg.num_attention_heads
    backbone, project, dtype = decode_surfaces(model, st)

    ragged = attention_mask is not None
    if ragged:
        am_arr = np.asarray(attention_mask.data
                            if isinstance(attention_mask, Tensor)
                            else attention_mask).astype(bool)
        if am_arr.shape != (B, S):
            raise ValueError(
                f"attention_mask shape {am_arr.shape} != ids {(B, S)}")
        if not am_arr[:, -1].all() or \
                (np.diff(am_arr.astype(np.int8), axis=1) < 0).any():
            raise ValueError(
                "attention_mask must be LEFT-padded (0s then 1s per row, "
                "last column all real) — right-align the prompts")
        pad_counts = (S - am_arr.sum(1)).astype(np.int32)

    def run_model(stt, toks, caches, km=None, po=None):
        tens = [tuple(Tensor(a) for a in c) for c in caches]
        kw = {} if km is None else {
            "attention_mask": Tensor(km), "pos_offsets": Tensor(po)}
        with no_grad(), swap_state(model, stt, collect_buffers=False):
            h, new_c = backbone(Tensor(toks), caches=tens, **kw)
            logits = project(h[:, -1:, :])
        return logits.data, [tuple(t.data for t in c) for c in new_c]

    def pick(logits, finished, key):
        nxt = sample_token(logits[:, -1, :].astype(jnp.float32),
                           temperature, top_k, top_p, key=key)
        nxt = nxt.astype(ids_arr.dtype)
        if eos_token_id is not None:
            nxt = jnp.where(finished, eos_token_id, nxt)
            finished = finished | (nxt == eos_token_id)
        return nxt, finished

    if prefill_chunk:
        if prefill_chunk <= 0 or S % prefill_chunk:
            raise ValueError(
                f"prefill_chunk {prefill_chunk} must divide the prompt "
                f"length {S}")
        if prefill_chunk >= S:
            prefill_chunk = 0  # one-shot: share that executable

    def whole(stt, ids, key, *rag):
        caches = [(jnp.zeros((B, L, n_kv, hd), dtype),
                   jnp.zeros((B, L, n_kv, hd), dtype),
                   jnp.zeros((), jnp.int32)) for _ in range(nl)]
        if ragged:
            am, po = rag
            # key-liveness over the WHOLE buffer: prompt pads stay dead
            # forever; generated slots turn live as they are written
            km = jnp.concatenate([am.astype(bool),
                                  jnp.zeros((B, mnt), bool)], 1)
        else:
            km = po = None
        if prefill_chunk:
            # chunked prefill: same static cache, offset-causal per chunk
            # (scan keeps the program O(1) in chunk count)
            n_chunks = S // prefill_chunk
            chunks = jnp.swapaxes(
                ids.reshape(B, n_chunks, prefill_chunk), 0, 1)

            def pre(cc, chunk):
                lg, cc = run_model(stt, chunk, cc, km, po)
                return cc, lg

            caches, lgs = jax.lax.scan(pre, caches, chunks)
            logits = lgs[-1]
        else:
            logits, caches = run_model(stt, ids, caches, km, po)
        key, sub = jax.random.split(key)
        finished = jnp.zeros((B,), bool)
        tok, finished = pick(logits, finished, sub)
        out = jnp.zeros((B, mnt), ids.dtype)
        out = jax.lax.dynamic_update_slice(out, tok[:, None], (0, 0))

        def body(carry, i):
            caches, tok, finished, key, out, km = carry
            if ragged:
                # the token decoded at step i-1 was written to buffer
                # index S+i-1: it becomes a live key for this step
                km = jax.lax.dynamic_update_slice(
                    km, jnp.ones((B, 1), bool), (0, S + i - 1))
            logits, caches = run_model(stt, tok[:, None], caches, km, po)
            key, sub = jax.random.split(key)
            nxt, finished = pick(logits, finished, sub)
            out = jax.lax.dynamic_update_slice(out, nxt[:, None], (0, i))
            return (caches, nxt, finished, key, out, km), None

        if mnt > 1:
            (caches, tok, finished, key, out, km), _ = jax.lax.scan(
                body, (caches, tok, finished, key, out, km),
                jnp.arange(1, mnt))
        return jnp.concatenate([ids, out], axis=1)

    sig = (B, S, mnt, float(temperature), int(top_k), float(top_p),
           eos_token_id, str(dtype), int(prefill_chunk), ragged,
           tuple(sorted(st)))
    # LRU-capped executable cache: a serving loop over naturally varying
    # prompt lengths would otherwise retain one executable per length for
    # the model's lifetime. Callers with many distinct lengths should pad
    # to fixed buckets (prefill_chunk makes bucketing cheap); the cap
    # bounds memory either way.
    from collections import OrderedDict
    cache = model.__dict__.setdefault("_compiled_generate", OrderedDict())
    if sig in cache:
        cache.move_to_end(sig)
    else:
        cache[sig] = jax.jit(whole)
        while len(cache) > _COMPILED_CACHE_CAP:
            cache.popitem(last=False)
    # greedy decoding draws nothing: leave the global RNG stream untouched
    # (eager generate doesn't advance it either — pipeline reproducibility)
    key = jax.random.PRNGKey(0) if temperature == 0 else G.next_key()
    rag_args = (jnp.asarray(am_arr), jnp.asarray(pad_counts)) if ragged \
        else ()
    seq = cache[sig](st, ids_arr, key, *rag_args)
    return Tensor(seq)
