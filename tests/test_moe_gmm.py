"""The held experts' grouped products (``ops/pallas/moe_gmm.py``): both
kernels in interpret mode, and the XLA form that stands in for them off a
TPU, against a float32 per-expert reference; the layer against the
``jax.lax.ragged_dot`` form it replaced; and the names the kernels compile
to for a described TPU v5e (nothing runs there)."""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.distributed.fleet import HeldExpertsLayer
from paddle_tpu.ops.pallas import moe_gmm


def _reference(x, slot, wg, wu, wd, gate):
    """Each assignment's expert output, float64 per expert: ``[R, d]``,
    nought for an assignment no held expert takes."""
    x, wg, wu, wd = (np.asarray(a, np.float64) for a in (x, wg, wu, wd))
    out = np.zeros((slot.shape[0], wd.shape[2]))
    for a, e in enumerate(slot):
        if e < wg.shape[0]:
            h = gate(x[a] @ wg[e]) * (x[a] @ wu[e])
            out[a] = h @ wd[e]
    return out


def _silu(v):
    return v / (1.0 + np.exp(-v))


def _relu(v):
    return np.maximum(v, 0.0)


# (experts, d, f, tm, tn up, tn down, the rows each expert takes, gate)
CASES = {
    # experts without rows between and at both ends, one over a tile
    "uneven": (6, 128, 256, 16, 128, None, [0, 3, 0, 21, 1, 0], "silu"),
    # widths no multiple of 128: one column block each
    "narrow": (3, 40, 72, 16, None, None, [5, 16, 17], "relu"),
    # three column tiles of the gate and up blocks, two of the down's
    "tiled": (2, 256, 384, 32, 128, 128, [40, 7], "silu"),
    "all_masked": (4, 128, 128, 16, None, None, [0, 0, 0, 0], "relu"),
}


MODES = ["interpret", "xla"]


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("case", sorted(CASES))
def test_both_kernels_against_a_per_expert_reference(case, mode,
                                                     monkeypatch):
    monkeypatch.setattr(moe_gmm, "_mode", lambda: mode)
    n, d, f, tm, tn_up, tn_down, per, activation = CASES[case]
    rng = np.random.default_rng(len(case))
    absent = 5
    slot = rng.permutation(np.concatenate(
        [np.full(s, e) for e, s in enumerate(per)]
        + [np.full(absent, n)]).astype(np.int32))
    x = rng.normal(size=(slot.shape[0], d)).astype(np.float32)
    wg, wu = (rng.normal(size=(n, d, f)).astype(np.float32) / np.sqrt(d)
              for _ in range(2))
    wd = rng.normal(size=(n, f, d)).astype(np.float32) / np.sqrt(f)
    sizes, starts, tiles, dest = moe_gmm.layout(jnp.asarray(slot), n, tm)
    np.testing.assert_array_equal(sizes, per)
    np.testing.assert_array_equal(np.asarray(starts) % tm, 0)
    ends = np.asarray(starts) + np.asarray(tiles) * tm
    assert (ends[:-1] <= np.asarray(starts)[1:]).all()     # no shared tile
    rows = moe_gmm.laid_rows(slot.shape[0], n, tm)
    assert rows % tm == 0 and rows >= slot.shape[0] + n * (tm - 1)
    assert ends.max() <= rows and np.asarray(dest).max() < rows
    assert len(set(np.asarray(dest).tolist())) == slot.shape[0]
    laid = np.zeros((rows, d), np.float32)
    laid[np.asarray(dest)] = x
    act = moe_gmm.gate_up(jnp.asarray(laid), jnp.asarray(wg),
                          jnp.asarray(wu), starts, tiles, tm=tm,
                          activation=activation, tn=tn_up)
    out = np.asarray(moe_gmm.down(act, jnp.asarray(wd), starts, tiles,
                                  tm=tm, tn=tn_down))
    want = _reference(x, slot, wg, wu, wd,
                      {"silu": _silu, "relu": _relu}[activation])
    live = slot < n
    np.testing.assert_allclose(out[np.asarray(dest)][live], want[live],
                               rtol=2e-5, atol=2e-5)


def test_tiles_come_from_the_shapes():
    """The three configurations' layers: K-EXAONE (384 rows a step, top 8
    of 128), openPangu (1,040, top 8 of 256), SmallThinker (2,112, top 6 of
    64); a column tile's two double-buffered blocks fit the budget."""
    assert moe_gmm.row_tile(384 * 8 / 128) == 16
    assert moe_gmm.row_tile(1040 * 8 / 256) == 32
    assert moe_gmm.row_tile(2112 * 6 / 64) == 64
    assert moe_gmm.row_tile(0.4) == 16
    assert moe_gmm.column_tile(2048, 6144, 2, 2) == 1024
    assert moe_gmm.column_tile(6144, 2048, 1, 2) == 6144
    assert moe_gmm.column_tile(2048, 7680, 2, 2) == 512
    assert moe_gmm.column_tile(7680, 2048, 1, 2) == 3840
    assert moe_gmm.column_tile(768, 2560, 2, 2) == 768
    assert moe_gmm.column_tile(2560, 768, 1, 2) == 2560
    assert moe_gmm.column_tile(72, 40, 2, 4) == 72


def test_a_backward_pass_is_refused():
    x = jnp.ones((16, 8))
    w = jnp.ones((1, 8, 8))
    one = jnp.ones((1,), jnp.int32)

    def loss(w):
        return moe_gmm.down(x, w, one * 0, one, tm=16).sum()
    with pytest.raises(NotImplementedError, match="forward only"):
        jax.grad(loss)(w)


def _ragged_dot_form(layer, x, mask):
    """The layer's products as they were: the assignments sorted by held
    expert, three ``jax.lax.ragged_dot`` calls, the same routing."""
    E, K, n = layer.num_experts, layer.top_k, len(layer.held)
    xt = jnp.asarray(x.data).reshape(-1, layer.d_model)
    T = xt.shape[0]
    logits = xt @ jnp.asarray(layer.router.data)
    if layer.score == "softmax":
        top_s, top_i = jax.lax.top_k(logits, K)
        w = jax.nn.softmax(top_s, axis=-1)
    else:
        s = jax.nn.sigmoid(logits)
        top_s, top_i = jax.lax.top_k(s, K)
        w = top_s * layer.routed_scaling_factor \
            / (top_s.sum(-1, keepdims=True) + 1e-20)
    local = np.full((E,), n, np.int32)
    local[list(layer.held)] = np.arange(n)
    slot = jnp.where(jnp.asarray(mask).reshape(T, 1),
                     jnp.asarray(local)[top_i], n)
    flat = slot.reshape(-1)
    order = jnp.argsort(flat, stable=True)
    sizes = jnp.bincount(flat, length=n + 1)[:n].astype(jnp.int32)
    rows = xt[order // K]
    gate = jax.nn.relu if layer.activation == "relu" else jax.nn.silu
    act = gate(jax.lax.ragged_dot(rows, jnp.asarray(layer.w_gate.data),
                                  sizes)) \
        * jax.lax.ragged_dot(rows, jnp.asarray(layer.w_up.data), sizes)
    out = jax.lax.ragged_dot(act, jnp.asarray(layer.w_down.data), sizes)
    where = jnp.zeros_like(flat).at[order].set(
        jnp.arange(flat.shape[0])).reshape(T, K)
    y = sum(jnp.where(slot[:, k:k + 1] < n, out[where[:, k]] * w[:, k:k + 1],
                      0.0) for k in range(K))
    return np.asarray(y).reshape(x.shape), np.asarray(sizes)


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("kind", ["sigmoid_silu", "softmax_relu"])
def test_the_layer_against_the_ragged_dot_form(kind, mode, monkeypatch):
    monkeypatch.setattr(moe_gmm, "_mode", lambda: mode)
    pt.seed(11)
    if kind == "sigmoid_silu":
        layer = HeldExpertsLayer(32, 48, 12, 3, held=[2, 5, 7, 11],
                                 routed_scaling_factor=2.5, init_std=0.2)
    else:
        layer = HeldExpertsLayer(32, 48, 8, 2, init_std=0.2,
                                 score="softmax", activation="relu")
    rng = np.random.default_rng(5)
    x = pt.to_tensor(rng.normal(size=(3, 7, 32)).astype(np.float32))
    mask = np.arange(21).reshape(3, 7) % 5 != 4
    got = np.asarray(layer(x, token_mask=pt.to_tensor(mask)).data)
    want, sizes = _ragged_dot_form(layer, x, mask)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(np.asarray(layer.last_rows.data), sizes)


# ------------------------------------------- names on a described chip --
@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no compiler for the chip here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def mosaic(monkeypatch):
    """Compile the kernels as the chip gets them, with the persistent
    compile cache off (it cannot read such an entry back)."""
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.setattr(moe_gmm, "_mode", lambda: "mosaic")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def test_each_call_compiles_to_the_name_the_benchmark_reads(one_chip, mosaic):
    """K-EXAONE's expert shapes (16 held of 6144 x 2048, 384 tokens of 8
    choices): two Mosaic calls, ``moe_gmm_up`` and ``moe_gmm_down``."""
    from benchmark import xplane
    from benchmark.kernels import moe_gmm as yardstick
    n, d, f, R, tm = 16, 6144, 2048, 3072, 16

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def products(rows, wg, wu, wd, starts, tiles):
        act = moe_gmm.gate_up(rows, wg, wu, starts, tiles, tm=tm)
        return moe_gmm.down(act, wd, starts, tiles, tm=tm)

    compiled = jax.jit(products).lower(
        arr((moe_gmm.laid_rows(R, n, tm), d), jnp.bfloat16),
        arr((n, d, f), jnp.bfloat16), arr((n, d, f), jnp.bfloat16),
        arr((n, f, d), jnp.bfloat16), arr((n,), jnp.int32),
        arr((n,), jnp.int32)).compile()
    names = [xplane.short_name(re.sub(r"^(ROOT )?", "", line.strip()))
             for line in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert [nm.split(".")[0] for nm in names] == ["moe_gmm_up",
                                                  "moe_gmm_down"], names
    for name in names:
        assert re.search(yardstick.TRACE_PATTERN, name), names
