"""The system under test, as the benchmark builds it: ``paddle_tpu``'s
``LlamaForCausalLM`` at a configuration file's sizes with the benchmark's
seeded weights, wrapped in ``jit.TrainStep`` or ``serving.ServingEngine``.

Everything here goes through the program's public entry points. Two places
read the program's state where it has no accessor: the trainer's flat
optimizer buffers (moments and master weights, for the output check) and a
request's ``slot_time`` (the engine's own queue-wait reading).
"""
from __future__ import annotations

import gc

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import weights as W

#: benchmark leaf -> the program's parameter suffix
_LAYER_NAMES = {
    "ln1": "input_layernorm.weight", "wq": "self_attn.q_proj.weight",
    "wk": "self_attn.k_proj.weight", "wv": "self_attn.v_proj.weight",
    "wo": "self_attn.o_proj.weight", "ln2": "post_attention_layernorm.weight",
    "w_gate": "mlp.gate_proj.weight", "w_up": "mlp.up_proj.weight",
    "w_down": "mlp.down_proj.weight"}
_GLOBAL_NAMES = {"embed": "model.embed_tokens.weight",
                 "norm": "model.norm.weight", "head": "lm_head.weight"}


def program_name(leaf: str) -> str:
    """``layers.3.wq`` -> ``model.layers.3.self_attn.q_proj.weight``."""
    if leaf in _GLOBAL_NAMES:
        return _GLOBAL_NAMES[leaf]
    _, i, n = leaf.split(".")
    return f"model.layers.{i}.{_LAYER_NAMES[n]}"


def leaf_names(cfg: dict):
    return list(W.GLOBAL_LEAVES) + [
        f"layers.{i}.{n}" for i in range(W.sizes(cfg)["layers"])
        for n in W.LAYER_LEAVES]


def _llama_config(cfg: dict):
    from paddle_tpu.models.llama import LlamaConfig
    z = W.sizes(cfg)
    if z["hd"] * z["heads"] != z["d"]:
        raise ValueError("models/llama.py ties the head size to hidden/heads")
    if cfg.get("sliding_window"):
        raise ValueError("models/llama.py has no sliding window")
    return LlamaConfig(
        vocab_size=z["vocab"], hidden_size=z["d"],
        intermediate_size=z["ffn"], num_hidden_layers=z["layers"],
        num_attention_heads=z["heads"], num_key_value_heads=z["kv"],
        max_position_embeddings=int(cfg["max_position_embeddings"]),
        rope_theta=z["theta"], rms_norm_eps=z["eps"],
        initializer_range=z["std"],
        tie_word_embeddings=bool(cfg.get("tie_word_embeddings", False)))


def build_model(cfg: dict, seed: int, dtype="bfloat16"):
    """The program's model at ``cfg``'s sizes holding the seeded weights.

    ``LlamaForCausalLM(cfg)`` births every leaf in float32 on the device
    (15 GB for the 16-layer serving configuration), so the model is built
    with one layer, and the other layers are built, narrowed and appended
    one at a time; each leaf's storage is dropped as soon as its shape is
    known. The seeded leaves then arrive from one jitted call, in ``dtype``.
    """
    from paddle_tpu.models.llama import LlamaDecoderLayer, LlamaForCausalLM
    lcfg = _llama_config(cfg)
    n_layers = lcfg.num_hidden_layers
    lcfg.num_hidden_layers = 1
    model = LlamaForCausalLM(lcfg)
    placeholder = jnp.zeros((), jnp.dtype(dtype))

    def release(layer):
        for _, p in layer.named_parameters():
            p._data = placeholder
    release(model)
    for _ in range(n_layers - 1):
        layer = LlamaDecoderLayer(lcfg)
        release(layer)
        model.model.layers.append(layer)
    lcfg.num_hidden_layers = n_layers
    tree = W.all_weights(seed, cfg, dtype)
    params = dict(model.named_parameters())
    for leaf in leaf_names(cfg):
        if "." in leaf:
            _, i, n = leaf.split(".")
            arr = tree["layers"][int(i)][n]
        else:
            arr = tree[leaf]
        p = params[program_name(leaf)]
        p._data = arr
        p._version += 1
    from paddle_tpu.core.dtype import convert_dtype
    for layer in model.sublayers(include_self=True):
        layer._dtype = convert_dtype(dtype)      # what ``.bfloat16()`` sets
    return model


def free_device_memory():
    """Collect what Python no longer holds, so the device lets it go."""
    gc.collect()
    jax.clear_caches()
    gc.collect()


def memory_peak_bytes() -> int:
    """The peak on the fullest chip, as the allocator reports it."""
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in jax.local_devices()]
    return max(peaks)


# ---------------------------------------------------------------- trainer --
class Trainer:
    """``jit.TrainStep`` over AdamW as ``bench.py``'s full-model step builds
    it (f32 master weights and moments, global-norm clip)."""

    def __init__(self, cfg: dict, opt: dict, seed: int):
        import paddle_tpu as pt
        from paddle_tpu.jit.train_step import TrainStep
        self.cfg, self.opt_cfg = cfg, opt
        self.model = build_model(cfg, seed, cfg.get("dtype", "bfloat16"))
        self.model.train()
        self.opt = pt.optimizer.AdamW(
            learning_rate=opt["learning_rate"], beta1=opt["beta1"],
            beta2=opt["beta2"], epsilon=opt["epsilon"],
            weight_decay=opt["weight_decay"],
            parameters=self.model.parameters(), multi_precision=True,
            grad_clip=pt.nn.ClipGradByGlobalNorm(opt["clip_norm"]))
        self.step = TrainStep(self.model, lambda m, x: m(x, labels=x)[1],
                              self.opt)
        self._pt = pt

    def feed(self, batch: np.ndarray):
        """Host batch -> the tensor the step takes (int64 ids, as the
        repo's own training entry feeds them)."""
        return self._pt.to_tensor(np.asarray(batch, np.int64))

    def __call__(self, x):
        return self.step(x)

    def compiles(self) -> int:
        """Executables the step has built (expected: 1)."""
        return sum(fn._cache_size() for fn in self.step._cache.values())

    def mosaic_calls(self, x) -> int:
        return self.step.compiled_hlo(x).count(
            'custom_call_target="tpu_custom_call"')

    # the optimizer's flat buffers: {state key: 1-D array} per bucket
    def _flat_segments(self, key):
        _, layout, flats, _ = self.step._flat_cache
        params = dict(self.model.named_parameters())
        for b, f in zip(layout.buckets, flats):
            for name, off, size in zip(b.names, b.offsets, b.sizes):
                if key == "master_weight" and not b.master:
                    # a float32 configuration keeps no master copy
                    yield name, params[name]._data.reshape(-1), 0, size
                else:
                    yield name, f[key], off, size
        if layout.residue:
            raise RuntimeError(
                f"leaves outside the fused buckets: {layout.residue}")

    def first_grad_norms(self) -> dict:
        """Per-leaf norm of the first gradient as the optimizer got it:
        after one step ``moment1 = (1 - beta1) * g``."""
        b1 = self.opt_cfg["beta1"]
        out = {}
        for name, flat, off, size in self._flat_segments("moment1"):
            out[name] = _segment_norm(flat, off, size) / (1.0 - b1)
        return {leaf: float(out[program_name(leaf)])
                for leaf in leaf_names(self.cfg)}

    def first_grad_sketches(self, seed: int) -> dict:
        """Per-leaf count sketch of the first gradient (``sketch.py``)."""
        from benchmark import sketch
        b1 = self.opt_cfg["beta1"]
        segs = {name: (flat, off, size) for name, flat, off, size
                in self._flat_segments("moment1")}
        out = {}
        for i, leaf in enumerate(leaf_names(self.cfg)):
            flat, off, size = segs[program_name(leaf)]
            out[leaf] = np.asarray(sketch.sketch(
                jax.lax.slice(flat, (off,), (off + size,)),
                sketch.leaf_key(seed, i), 1.0 / (1.0 - b1)))
        return out

    def change_norms(self, seed: int) -> dict:
        """Per-leaf norm of (master weights now - the seeded start); the
        start is regenerated leaf by leaf, not kept."""
        key = W.seed_key(seed)
        shapes, std = W.leaf_shapes(self.cfg), W.sizes(self.cfg)["std"]
        dtype = jnp.dtype(self.cfg.get("dtype", "bfloat16"))
        segs = {name: (flat, off, size) for name, flat, off, size
                in self._flat_segments("master_weight")}
        out = {}
        for leaf in leaf_names(self.cfg):
            flat, off, size = segs[program_name(leaf)]
            short = leaf.split(".")[-1]
            slot = int(leaf.split(".")[1]) + 1 if "." in leaf else 0
            out[leaf] = float(_segment_change(
                flat, key, off=off, size=size, slot=slot, name=short,
                shape=shapes[short], std=std, dtype=dtype))
        return out

    def release(self):
        """Drop the state without the flush ``TrainStep.__del__`` makes
        (it would rebuild 12 B a parameter beside the flats)."""
        self.step._flat_cache = None
        self.step.clear_cache()
        self.opt._state.clear()
        for _, p in self.model.named_parameters():
            p._data = jnp.zeros((), jnp.float32)
        self.step = self.opt = self.model = None
        free_device_memory()


@jax.jit
def _norm(x):
    return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))


def _segment_norm(flat, off, size):
    return _norm(jax.lax.slice(flat, (off,), (off + size,)))


@jax.jit
def _diff_norm(a, b):
    return jnp.sqrt(jnp.sum(jnp.square(
        a.astype(jnp.float32) - b.astype(jnp.float32).reshape(-1))))


def _segment_change(flat, key, *, off, size, slot, name, shape, std, dtype):
    start = W._leaf(key, slot, name, shape, std, dtype)
    return _diff_norm(jax.lax.slice(flat, (off,), (off + size,)), start)


# ----------------------------------------------------------------- server --
def build_engine(cfg: dict, seed: int, overrides=None):
    """``ServingEngine`` at the configuration's deployment settings (the
    ``engine`` group of the file), weights in place before the pool is."""
    from paddle_tpu.serving import ServingEngine
    model = build_model(cfg, seed, cfg.get("dtype", "bfloat16"))
    model.eval()
    kw = dict(cfg["engine"])
    kw.update(overrides or {})
    return ServingEngine(model, **kw)
