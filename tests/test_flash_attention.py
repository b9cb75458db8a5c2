"""Flash-attention kernel tests (Pallas interpret mode on the CPU mesh) —
numeric parity vs the naive composite, forward and backward, across the
kernel's full capability matrix: causal (with kv/q length offset), cross
attention, native GQA, segment ids (varlen/padding), streamed additive
bias, and tape integration through the Tensor API."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as pt
from paddle_tpu.ops.pallas.flash_attention import (flash_attention_bhsd,
                                                   flash_attention_bshd)

_NEG = -0.7 * float(np.finfo(np.float32).max)


def naive(q, k, v, causal=False, bias=None, qseg=None, kseg=None):
    """Oracle for [B, Hq, Sq, D] q with [B, Hkv, Sk, D] kv (GQA broadcast),
    mirroring the kernel's fully-masked-row → 0 convention."""
    if q.ndim == 3:
        q, k, v = q[:, None], k[:, None], v[:, None]
        squeeze = True
    else:
        squeeze = False
    B, Hq, Sq, D = q.shape
    Sk = k.shape[2]
    rep = Hq // k.shape[1]
    kf = jnp.repeat(k, rep, axis=1)
    vf = jnp.repeat(v, rep, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, kf) / np.sqrt(D)
    if bias is not None:
        s = s + bias
    live = jnp.ones((B, 1, Sq, Sk), bool)
    if qseg is not None:
        live = live & (qseg[:, None, :, None] == kseg[:, None, None, :])
    if causal:
        qi = jnp.arange(Sq)[:, None] + (Sk - Sq)
        live = live & (qi >= jnp.arange(Sk)[None, :])[None, None]
    s = jnp.where(live, s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    if qseg is not None:
        p = jnp.where(live.any(-1, keepdims=True), p, 0.0)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, vf)
    return out[:, 0] if squeeze else out


@pytest.fixture()
def qkv():
    rng = np.random.RandomState(0)
    BH, S, D = 3, 256, 64
    mk = lambda: jnp.asarray(rng.randn(BH, S, D), jnp.float32)
    return mk(), mk(), mk()


def rand4(rng, *shape):
    return jnp.asarray(rng.randn(*shape), jnp.float32)


class TestForward:
    @pytest.mark.parametrize("causal", [False, True])
    def test_parity(self, qkv, causal):
        q, k, v = qkv
        out = flash_attention_bhsd(q, k, v, causal=causal, block_q=64,
                                   block_k=64)
        ref = naive(q, k, v, causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)

    def test_uneven_blocks(self, qkv):
        q, k, v = qkv
        out = flash_attention_bhsd(q, k, v, causal=True, block_q=128,
                                   block_k=64)
        ref = naive(q, k, v, True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)

    def test_bhsd_4d(self, qkv):
        q, k, v = qkv
        q4 = q.reshape(1, 3, 256, 64)
        out = flash_attention_bhsd(q4, k.reshape(1, 3, 256, 64),
                                   v.reshape(1, 3, 256, 64), block_q=64,
                                   block_k=64)
        assert out.shape == (1, 3, 256, 64)

    def test_indivisible_seq_raises(self):
        q = jnp.zeros((1, 100, 64))
        with pytest.raises(ValueError):
            flash_attention_bhsd(q, q, q, block_q=64, block_k=64)

    @pytest.mark.parametrize("causal", [False, True])
    def test_cross_attention(self, causal):
        # kv_len != q_len, reference flash_attn with differing seqlen_k
        rng = np.random.RandomState(3)
        q = rand4(rng, 2, 2, 128, 32)
        k = rand4(rng, 2, 2, 320, 32)
        v = rand4(rng, 2, 2, 320, 32)
        out = flash_attention_bhsd(q, k, v, causal=causal, block_q=64,
                                   block_k=64)
        ref = naive(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)

    def test_decode_single_query(self):
        # Sq=1 against a long KV (the decode step shape)
        rng = np.random.RandomState(4)
        q = rand4(rng, 2, 4, 1, 32)
        k = rand4(rng, 2, 4, 256, 32)
        v = rand4(rng, 2, 4, 256, 32)
        out = flash_attention_bhsd(q, k, v, causal=True)
        ref = naive(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)

    @pytest.mark.parametrize("hkv", [1, 2])
    def test_gqa(self, hkv):
        # KV heads < Q heads served by index maps, not replication
        rng = np.random.RandomState(5)
        q = rand4(rng, 2, 4, 128, 32)
        k = rand4(rng, 2, hkv, 128, 32)
        v = rand4(rng, 2, hkv, 128, 32)
        out = flash_attention_bhsd(q, k, v, causal=True, block_q=64,
                                   block_k=64)
        ref = naive(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)

    def test_gqa_indivisible_heads_raises(self):
        q = jnp.zeros((1, 3, 64, 32))
        k = jnp.zeros((1, 2, 64, 32))
        with pytest.raises(ValueError):
            flash_attention_bhsd(q, k, k)

    def test_segment_ids(self):
        # two documents packed per row + padding tail (id 0 vs real ids)
        rng = np.random.RandomState(6)
        B, H, S, D = 2, 2, 256, 32
        q = rand4(rng, B, H, S, D)
        k = rand4(rng, B, H, S, D)
        v = rand4(rng, B, H, S, D)
        ids = np.where(np.arange(S) < 96, 1, np.where(np.arange(S) < 192,
                                                      2, 0))
        seg = jnp.asarray(np.stack([ids, ids]), jnp.int32)
        out = flash_attention_bhsd(q, k, v, q_segment_ids=seg,
                                   kv_segment_ids=seg, block_q=64,
                                   block_k=64)
        ref = naive(q, k, v, qseg=seg, kseg=seg)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)

    def test_segment_fully_masked_rows_zero(self):
        # a query token whose id matches no kv token gets exactly 0 output
        rng = np.random.RandomState(7)
        q = rand4(rng, 1, 1, 128, 32)
        k = rand4(rng, 1, 1, 128, 32)
        v = rand4(rng, 1, 1, 128, 32)
        # boundary deliberately NOT tile-aligned (70 with block_q=64): dead
        # rows sharing tile j=1 with live rows must still emit exact 0
        qseg = jnp.asarray(np.where(np.arange(128) < 70, 1, 9)[None],
                           jnp.int32)
        kseg = jnp.asarray(np.ones((1, 128)), jnp.int32)
        out = np.asarray(flash_attention_bhsd(
            q, k, v, q_segment_ids=qseg, kv_segment_ids=kseg, block_q=64,
            block_k=64))
        assert np.all(out[0, 0, 70:] == 0.0)
        assert np.all(np.isfinite(out))
        # and their gradients are exactly 0 too
        def loss(a):
            o = flash_attention_bhsd(a, k, v, q_segment_ids=qseg,
                                     kv_segment_ids=kseg, block_q=64,
                                     block_k=64)
            return jnp.sum(o.astype(jnp.float32))
        dq = np.asarray(jax.grad(loss)(q))
        assert np.all(dq[0, 0, 70:] == 0.0) and np.all(np.isfinite(dq))

    @pytest.mark.parametrize("bshape", [(256, 256), (2, 1, 256, 256),
                                        (1, 2, 256, 256), (2, 2, 256, 256)])
    def test_bias_broadcast_shapes(self, bshape):
        rng = np.random.RandomState(8)
        q = rand4(rng, 2, 2, 256, 32)
        k = rand4(rng, 2, 2, 256, 32)
        v = rand4(rng, 2, 2, 256, 32)
        bias = jnp.asarray(rng.randn(*bshape) * 2, jnp.float32)
        out = flash_attention_bhsd(q, k, v, bias=bias, block_q=64,
                                   block_k=64)
        bias4 = bias if bias.ndim == 4 else bias[None, None]
        ref = naive(q, k, v, bias=bias4)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)

    def test_bias_key_padding_row_broadcast(self):
        # [B, 1, 1, Sk] key-padding mask: streamed via a one-row BlockSpec,
        # never broadcast to Sq in HBM
        rng = np.random.RandomState(10)
        q = rand4(rng, 2, 2, 128, 32)
        k = rand4(rng, 2, 2, 128, 32)
        v = rand4(rng, 2, 2, 128, 32)
        pad = np.zeros((2, 1, 1, 128), np.float32)
        pad[:, :, :, 96:] = np.finfo(np.float32).min
        bias = jnp.asarray(pad)
        out = flash_attention_bhsd(q, k, v, bias=bias, block_q=64,
                                   block_k=64)
        ref = naive(q, k, v, bias=bias)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)

    def test_bias_as_additive_causal_mask(self):
        # an explicit -inf-style additive mask matches the causal flag
        rng = np.random.RandomState(9)
        q = rand4(rng, 1, 2, 128, 32)
        k = rand4(rng, 1, 2, 128, 32)
        v = rand4(rng, 1, 2, 128, 32)
        mask = jnp.where(jnp.tril(jnp.ones((128, 128), bool)), 0.0,
                         jnp.finfo(jnp.float32).min)
        out = flash_attention_bhsd(q, k, v, bias=mask, block_q=64,
                                   block_k=64)
        ref = flash_attention_bhsd(q, k, v, causal=True, block_q=64,
                                   block_k=64)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)

    def test_sdpa_router(self, monkeypatch):
        # masked, GQA and DROPOUT cases all ROUTE to the kernel now
        import paddle_tpu.nn.functional as F
        import paddle_tpu.ops.pallas.flash_attention as fa_mod
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        calls = []

        def fake_bshd(*a, **kw):
            calls.append(kw)
            return a[0]  # a kernel error would raise, not fall back
        monkeypatch.setattr(fa_mod, "flash_attention_bshd", fake_bshd)

        rng = np.random.RandomState(0)
        B, S, H, D = 1, 64, 2, 32
        q = pt.to_tensor(rng.randn(B, S, H, D).astype(np.float32))
        mask = pt.to_tensor(np.zeros((B, H, S, S), np.float32))
        F.scaled_dot_product_attention(q, q, q, attn_mask=mask)
        assert len(calls) == 1 and calls[0]["bias"] is not None
        F.scaled_dot_product_attention(q, q, q, dropout_p=0.5,
                                       training=True)
        # active dropout reaches the kernel WITH p and a seed
        assert len(calls) == 2 and calls[1]["dropout_p"] == 0.5
        assert calls[1]["dropout_seed"] is not None
        F.scaled_dot_product_attention(q, q, q, dropout_p=0.5,
                                       training=False)
        assert calls[2]["dropout_p"] == 0.0  # eval: dropout off
        F.scaled_dot_product_attention(q, q, q, is_causal=True)
        assert len(calls) == 4  # plain causal reaches the kernel

        # generate_square_subsequent_mask is recognized: kernel sees
        # causal=True and NO bias (S×S mask never streamed)
        from paddle_tpu.nn.layer.transformer import Transformer
        cm = Transformer.generate_square_subsequent_mask(S)
        F.scaled_dot_product_attention(q, q, q, attn_mask=cm)
        assert calls[-1].get("bias") is None
        # composite fallback with the same tagged mask matches causal
        monkeypatch.undo()
        got = F.scaled_dot_product_attention(q, q, q, attn_mask=cm)
        want = F.scaled_dot_product_attention(q, q, q, is_causal=True)
        np.testing.assert_allclose(got.numpy(), want.numpy(), rtol=2e-4,
                                   atol=2e-5)


class TestBackward:
    @pytest.mark.parametrize("causal", [False, True])
    def test_grads_match_naive(self, qkv, causal):
        q, k, v = qkv

        def f(a, b, c):
            return jnp.sum(jnp.sin(flash_attention_bhsd(
                a, b, c, causal=causal, block_q=64, block_k=64)))

        def g(a, b, c):
            return jnp.sum(jnp.sin(naive(a, b, c, causal)))

        got = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
        ref = jax.grad(g, argnums=(0, 1, 2))(q, k, v)
        for ga, ra in zip(got, ref):
            np.testing.assert_allclose(np.asarray(ga), np.asarray(ra),
                                       rtol=2e-3, atol=2e-4)

    def test_grads_gqa_cross_causal(self):
        rng = np.random.RandomState(11)
        q = rand4(rng, 2, 4, 128, 32)
        k = rand4(rng, 2, 2, 256, 32)
        v = rand4(rng, 2, 2, 256, 32)

        def f(a, b, c):
            return jnp.sum(jnp.sin(flash_attention_bhsd(
                a, b, c, causal=True, block_q=64, block_k=64)))

        def g(a, b, c):
            return jnp.sum(jnp.sin(naive(a, b, c, causal=True)))

        got = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
        ref = jax.grad(g, argnums=(0, 1, 2))(q, k, v)
        assert got[1].shape == k.shape  # dk at KV-head resolution
        for ga, ra in zip(got, ref):
            np.testing.assert_allclose(np.asarray(ga), np.asarray(ra),
                                       rtol=2e-3, atol=2e-4)

    def test_grads_segments_bias(self):
        rng = np.random.RandomState(12)
        B, H, S, D = 2, 2, 128, 32
        q = rand4(rng, B, H, S, D)
        k = rand4(rng, B, H, S, D)
        v = rand4(rng, B, H, S, D)
        bias = jnp.asarray(rng.randn(1, H, S, S), jnp.float32)
        ids = np.where(np.arange(S) < 96, 1, 0)
        seg = jnp.asarray(np.stack([ids, ids]), jnp.int32)

        def f(a, b, c):
            return jnp.sum(jnp.sin(flash_attention_bhsd(
                a, b, c, bias=bias, q_segment_ids=seg, kv_segment_ids=seg,
                block_q=64, block_k=64)))

        def g(a, b, c):
            return jnp.sum(jnp.sin(naive(a, b, c, bias=bias, qseg=seg,
                                         kseg=seg)))

        got = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
        ref = jax.grad(g, argnums=(0, 1, 2))(q, k, v)
        for ga, ra in zip(got, ref):
            np.testing.assert_allclose(np.asarray(ga), np.asarray(ra),
                                       rtol=2e-3, atol=2e-4)
            assert np.all(np.isfinite(np.asarray(ga)))


class TestTapeIntegration:
    def test_bshd_tensor_api_backward(self):
        rng = np.random.RandomState(1)
        B, S, H, D = 2, 128, 2, 32
        q = pt.to_tensor(rng.randn(B, S, H, D).astype(np.float32),
                         stop_gradient=False)
        k = pt.to_tensor(rng.randn(B, S, H, D).astype(np.float32),
                         stop_gradient=False)
        v = pt.to_tensor(rng.randn(B, S, H, D).astype(np.float32),
                         stop_gradient=False)
        out = flash_attention_bshd(q, k, v, causal=True, block_q=64,
                                   block_k=64)
        assert out.shape == [B, S, H, D]
        out.mean().backward()
        assert q.grad is not None and np.isfinite(q.grad.numpy()).all()
        assert k.grad is not None and v.grad is not None

        # matches the sdpa composite on the same Tensors
        import paddle_tpu.nn.functional as F
        ref = F.scaled_dot_product_attention(q, k, v, is_causal=True)
        np.testing.assert_allclose(out.numpy(), ref.numpy(), rtol=2e-4,
                                   atol=2e-5)

    def test_gqa_functional_flash(self):
        # F.flash_attention accepts GQA-shaped kv in paddle layout
        import paddle_tpu.nn.functional as F
        rng = np.random.RandomState(2)
        B, S, H, Hkv, D = 2, 128, 4, 2, 32
        q = pt.to_tensor(rng.randn(B, S, H, D).astype(np.float32),
                         stop_gradient=False)
        k = pt.to_tensor(rng.randn(B, S, Hkv, D).astype(np.float32),
                         stop_gradient=False)
        v = pt.to_tensor(rng.randn(B, S, Hkv, D).astype(np.float32),
                         stop_gradient=False)
        out = F.flash_attention(q, k, v, causal=True)
        ref = naive(jnp.swapaxes(q.data, 1, 2), jnp.swapaxes(k.data, 1, 2),
                    jnp.swapaxes(v.data, 1, 2), causal=True)
        np.testing.assert_allclose(out.numpy(),
                                   np.asarray(jnp.swapaxes(ref, 1, 2)),
                                   rtol=2e-4, atol=2e-5)
        out.mean().backward()
        assert k.grad.shape == [B, S, Hkv, D]

    def test_segment_ids_through_functional(self):
        import paddle_tpu.nn.functional as F
        rng = np.random.RandomState(3)
        B, S, H, D = 2, 128, 2, 32
        q = pt.to_tensor(rng.randn(B, S, H, D).astype(np.float32))
        seg = pt.to_tensor(
            np.where(np.arange(S) < 64, 1, 0)[None].repeat(B, 0)
            .astype(np.int32))
        out = F.flash_attention(q, q, q, q_segment_ids=seg,
                                kv_segment_ids=seg)
        ref = naive(jnp.swapaxes(q.data, 1, 2), jnp.swapaxes(q.data, 1, 2),
                    jnp.swapaxes(q.data, 1, 2), qseg=seg.data,
                    kseg=seg.data)
        np.testing.assert_allclose(out.numpy(),
                                   np.asarray(jnp.swapaxes(ref, 1, 2)),
                                   rtol=2e-4, atol=2e-5)


class TestDropout:
    """In-kernel attention dropout: position-hashed keep mask (identical
    in fwd and both bwd kernels), l keeps the raw softmax denominator —
    standard post-softmax dropout semantics."""

    @staticmethod
    def np_keep(seed, bh, Sq, Sk, p):
        """numpy reimplementation of the kernel's murmur-style hash
        (int64 arithmetic masked to 32 bits: identical wrap semantics,
        no numpy scalar-overflow warnings)."""
        M = 0xFFFFFFFF
        qi, ki = np.meshgrid(np.arange(Sq, dtype=np.int64),
                             np.arange(Sk, dtype=np.int64), indexing="ij")
        x = (qi * 0x9E3779B9) & M
        x ^= (ki * 0xC2B2AE35) & M
        x ^= (int(bh) * 0x85EBCA6B) & M
        x ^= np.int64(np.uint32(np.int32(seed)))
        x ^= x >> 16
        x = (x * 0x85EBCA6B) & M
        x ^= x >> 13
        x = (x * 0xC2B2AE35) & M
        x ^= x >> 16
        thr = min(int(p * 2**32), 2**32 - 1)
        return x >= thr

    def oracle_dropout(self, q, k, v, p, seed):
        """Standard attention with the kernel's exact mask."""
        BH, S, D = q.shape
        s = np.einsum("bqd,bkd->bqk", np.asarray(q), np.asarray(k)) / \
            np.sqrt(D)
        w = np.exp(s - s.max(-1, keepdims=True))
        w = w / w.sum(-1, keepdims=True)
        out = np.zeros_like(np.asarray(q))
        for bh in range(BH):
            keep = self.np_keep(seed, bh, S, S, p)
            wd = np.where(keep, w[bh], 0.0) / (1.0 - p)
            out[bh] = wd @ np.asarray(v[bh])
        return out

    def test_p0_matches_plain(self, qkv):
        q, k, v = qkv
        a = flash_attention_bhsd(q, k, v, block_q=64, block_k=64)
        b = flash_attention_bhsd(q, k, v, dropout_p=0.0, block_q=64,
                                 block_k=64)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_matches_hash_oracle_exactly(self, qkv):
        q, k, v = qkv
        p, seed = 0.3, 1234
        out = flash_attention_bhsd(q, k, v, dropout_p=p, dropout_seed=seed,
                                   block_q=64, block_k=64)
        ref = self.oracle_dropout(q, k, v, p, seed)
        np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-4,
                                   atol=2e-5)

    def test_deterministic_and_seed_sensitive(self, qkv):
        q, k, v = qkv
        a = flash_attention_bhsd(q, k, v, dropout_p=0.2, dropout_seed=7,
                                 block_q=64, block_k=64)
        b = flash_attention_bhsd(q, k, v, dropout_p=0.2, dropout_seed=7,
                                 block_q=64, block_k=64)
        c = flash_attention_bhsd(q, k, v, dropout_p=0.2, dropout_seed=8,
                                 block_q=64, block_k=64)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert np.abs(np.asarray(a) - np.asarray(c)).max() > 0

    def test_block_size_invariant(self, qkv):
        # the mask is position-based: tiling must not change the result
        q, k, v = qkv
        a = flash_attention_bhsd(q, k, v, dropout_p=0.25, dropout_seed=3,
                                 block_q=64, block_k=64)
        b = flash_attention_bhsd(q, k, v, dropout_p=0.25, dropout_seed=3,
                                 block_q=128, block_k=64)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)

    def test_grads_match_hash_oracle(self):
        rng = np.random.RandomState(13)
        BH, S, D = 2, 128, 32
        q = jnp.asarray(rng.randn(BH, S, D), jnp.float32)
        k = jnp.asarray(rng.randn(BH, S, D), jnp.float32)
        v = jnp.asarray(rng.randn(BH, S, D), jnp.float32)
        p, seed = 0.3, 99

        keeps = np.stack([self.np_keep(seed, bh, S, S, p)
                          for bh in range(BH)])

        def ref(a, b, c):
            s = jnp.einsum("bqd,bkd->bqk", a, b) / np.sqrt(D)
            w = jax.nn.softmax(s, axis=-1)
            wd = jnp.where(jnp.asarray(keeps), w, 0.0) / (1.0 - p)
            return jnp.sum(jnp.sin(jnp.einsum("bqk,bkd->bqd", wd, c)))

        def got(a, b, c):
            return jnp.sum(jnp.sin(flash_attention_bhsd(
                a, b, c, dropout_p=p, dropout_seed=seed, block_q=64,
                block_k=64)))

        ga = jax.grad(got, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(ref, argnums=(0, 1, 2))(q, k, v)
        for x, y in zip(ga, gr):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                       rtol=2e-3, atol=2e-4)

    def test_grads_gqa_causal_dropout(self):
        """The riskiest path: dkv's _qflat-derived head index must give
        the SAME mask the forward used, under GQA + causal."""
        rng = np.random.RandomState(21)
        B, Hq, Hkv, S, D = 2, 4, 2, 128, 32
        q = jnp.asarray(rng.randn(B, Hq, S, D), jnp.float32)
        k = jnp.asarray(rng.randn(B, Hkv, S, D), jnp.float32)
        v = jnp.asarray(rng.randn(B, Hkv, S, D), jnp.float32)
        p, seed = 0.25, 17
        keeps = np.stack([self.np_keep(seed, bh, S, S, p)
                          for bh in range(B * Hq)]).reshape(B, Hq, S, S)

        def ref(a, b, c):
            G = Hq // Hkv
            kf = jnp.repeat(b, G, axis=1)
            vf = jnp.repeat(c, G, axis=1)
            s_ = jnp.einsum("bhqd,bhkd->bhqk", a, kf) / np.sqrt(D)
            causal = jnp.tril(jnp.ones((S, S), bool))
            s_ = jnp.where(causal, s_, -jnp.inf)
            w = jax.nn.softmax(s_, axis=-1)
            wd = jnp.where(jnp.asarray(keeps), w, 0.0) / (1.0 - p)
            return jnp.sum(jnp.sin(jnp.einsum("bhqk,bhkd->bhqd", wd, vf)))

        def got(a, b, c):
            return jnp.sum(jnp.sin(flash_attention_bhsd(
                a, b, c, causal=True, dropout_p=p, dropout_seed=seed,
                block_q=64, block_k=64)))

        ga = jax.grad(got, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(ref, argnums=(0, 1, 2))(q, k, v)
        for x, y in zip(ga, gr):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                       rtol=2e-3, atol=2e-4)

    def test_drop_rate(self):
        keep = self.np_keep(5, 0, 256, 256, 0.4)
        rate = 1.0 - keep.mean()
        assert abs(rate - 0.4) < 0.01, rate


class TestTrainableMask:
    def test_trainable_additive_mask_gets_grad(self):
        """A learned additive bias (stop_gradient=False float mask) must
        RECEIVE a gradient — the reference's composite adds the mask to the
        logits; its fused kernel emits grad_bias. Constant masks stay
        zero-grad constants on every route."""
        import paddle_tpu as pt
        from paddle_tpu.nn import functional as F

        rng = np.random.RandomState(0)
        q = pt.to_tensor(rng.randn(1, 8, 2, 16).astype(np.float32),
                         stop_gradient=False)
        bias = pt.to_tensor(np.zeros((1, 1, 8, 8), np.float32),
                            stop_gradient=False)
        out = F.scaled_dot_product_attention(q, q, q, attn_mask=bias)
        out.mean().backward()
        assert bias.grad is not None
        g = bias.grad.numpy()
        assert np.isfinite(g).all() and np.abs(g).max() > 0
        # softmax-row structure: per-(row) bias grads sum to ~0 (shift
        # invariance of softmax under the mean loss chain rule is broken
        # by V, so just check the value route actually differentiated)
        q2 = pt.to_tensor(q.numpy(), stop_gradient=False)
        const = pt.to_tensor(np.ones((1, 1, 8, 8), np.float32) * 0.3)
        out2 = F.scaled_dot_product_attention(q2, q2, q2, attn_mask=const)
        out3 = F.scaled_dot_product_attention(
            q2, q2, q2,
            attn_mask=pt.to_tensor(np.ones((1, 1, 8, 8), np.float32) * 0.3,
                                   stop_gradient=False))
        np.testing.assert_allclose(out2.numpy(), out3.numpy(), atol=1e-6)


def test_kernel_shard_maps_itself_under_a_pinned_mesh():
    """GSPMD cannot partition a Mosaic call (jax refuses to lower it), so
    under ``spmd_mesh`` the kernel runs per (dp, mp) shard: same values
    and gradients as the unpinned call, output sharded like the input
    (ISSUE 21; interpret mode on the virtual mesh)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from paddle_tpu.ops.pallas.flash_attention import (
        flash_attention_bshd, spmd_mesh)
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("dp", "mp"))
    rng = np.random.RandomState(0)
    B, S, H, HK, D = 4, 64, 4, 2, 16
    q, k, v = (jnp.asarray(rng.randn(B, S, h, D), jnp.float32)
               for h in (H, HK, HK))
    seg = jnp.asarray(np.repeat(np.arange(2), S // 2)[None].repeat(B, 0))

    def run(pin):
        def loss(q, k, v):
            with spmd_mesh(mesh if pin else None):
                o = flash_attention_bshd(
                    pt.Tensor(q), pt.Tensor(k), pt.Tensor(v), causal=True,
                    q_segment_ids=seg, kv_segment_ids=seg,
                    block_q=32, block_k=32).data
            return (o ** 2).sum(), o
        args = (q, k, v)
        if pin:
            sh = NamedSharding(mesh, P("dp", None, "mp", None))
            args = tuple(jax.device_put(a, sh) for a in args)
        (_, o), g = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True))(*args)
        return o, g
    o0, g0 = run(False)
    o1, g1 = run(True)
    assert o1.sharding.spec == P("dp", None, "mp", None)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o0), atol=1e-6)
    for a, b in zip(g1, g0):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
