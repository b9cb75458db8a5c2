"""Mamba-2's state update over a serving step's ragged packed rows, as one
Pallas TPU kernel that reads each live sequence's recurrent state once and
writes it back in place.

A state-space layer keeps, a sequence, one state ``h`` of ``[H, P, N]``
numbers (heads, head width, state width) in a pool of one row a serving
slot, laid out ``[N, H*P]``: the state width on sublanes, (head, head
width) along lanes. The step's rows are packed back to back on one token
axis (decode rows of one token, prefill chunks of several); entry ``e``
owns rows ``[starts[e], starts[e] + lens[e])`` and the pool's row
``rows[e]``. Over its rows, in order,

    h_t = exp(a_t) h_{t-1} + xd_t (x) B_t        a_t = dt_t A   (a head)
    y_t = h_t C_t                                 xd_t = dt_t x_t

starting from the row's state, or from zero where ``fresh[e]`` (its first
position is 0). The caller adds ``D x_t`` and the gate.

``grid = (n_live,)``, a TRACED bound: one grid step an entry. The entry's
state row comes through a ``BlockSpec`` whose index map reads ``rows[e]``
from SMEM, aliased to the output (``input_output_aliases``), so the
pipeline fetches row ``e + 1`` while entry ``e`` computes and every live
row crosses HBM once each way; a row no entry names is neither read nor
written. Inside a step the update is float32, whatever the pool holds:

- **one row** (a decode row, or a chunk's last token): elementwise work
  over the state's ``[N, 128]`` lane blocks. ``B_t`` and ``C_t`` become one
  ``[N, 128]`` tile each, down the sublanes, built once an entry; the
  token's decay and ``xd`` are lane-dense rows, broadcast over sublanes.
  A block is ``h' = decay h + B_t xd``, and its ``y`` the sum of ``h' C_t``
  down the sublanes, written lane-dense: no lane reduction, no transpose;
- **several rows**: the chunked (SSD) form on the MXU, ``lc`` rows at a
  time in order. For a head, with ``cs`` the rows' running sum of ``a``
  over the chunk: ``y = ((exp(cs_t - cs_s))_{s<=t} * C B^T) xd + exp(cs)
  (C h)`` and ``h' = exp(cs_end) h + B^T (exp(cs_end - cs) xd)``, ``h`` the
  head's ``[N, P]``. The chunk reads its rows from the step's token arrays,
  which stay in VMEM for the whole call (a window of ``lc`` rows at a row
  offset).

The call is ``ssd.N`` in a profiler trace (``benchmark/kernels/ssd.py``).
Off a TPU the same mathematics runs as a sequential scan in XLA
(:func:`_mode` ``"xla"``), which is also the kernel's oracle in the tests
(``"interpret"``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
# the function itself, not ``pl.pallas_call``: the benchmark's tests count a
# serving step's attention kernels by patching that attribute
from jax._src.pallas.pallas_call import pallas_call

__all__ = ["ssd_update"]

_LANES = 128
#: rows of a chunk in the chunked form: its ``[lc, lc]`` decay matrix a head
_CHUNK = 128
_HI = jax.lax.Precision.HIGHEST


def _mode() -> str:
    """``"mosaic"`` on a TPU, else ``"xla"``: the sequential scan (the
    Pallas interpreter would add seconds to each compile of a serving
    step). The kernel's tests ask for ``"interpret"``."""
    return "mosaic" if jax.default_backend() == "tpu" else "xla"


def ssd_update(state, xd, a, B, C, rows, starts, lens, fresh, n_live):
    """``state`` [R, N, H*P] (the pool); ``xd`` [T, H, P] float32
    (``dt x``); ``a`` [T, H] float32 (``dt A``); ``B``, ``C`` [T, N];
    ``rows``, ``starts``, ``lens``, ``fresh`` [E] int32, the first
    ``n_live`` entries live. Returns ``(y [T, H, P] float32, state')``;
    ``y`` is 0 at rows no live entry owns."""
    mode = _mode()
    args = (state, xd.astype(jnp.float32), a.astype(jnp.float32),
            B.astype(jnp.float32), C.astype(jnp.float32),
            rows.astype(jnp.int32), starts.astype(jnp.int32),
            lens.astype(jnp.int32), fresh.astype(jnp.int32),
            jnp.asarray(n_live, jnp.int32))
    if mode == "xla":
        return _scan(*args)
    return _kernel_call(*args, interpret=mode == "interpret")


def _owner(starts, lens, n_live, T):
    """Each row's entry ``[T]`` (``E`` where no live entry owns it)."""
    E = starts.shape[0]
    t = jnp.arange(T, dtype=jnp.int32)[None, :]
    live = (jnp.arange(E) < n_live)[:, None]
    inside = (t >= starts[:, None]) & (t < (starts + lens)[:, None]) & live
    return jnp.where(inside.any(0), jnp.argmax(inside, 0), E).astype(
        jnp.int32)


def _scan(state, xd, a, B, C, rows, starts, lens, fresh, n_live):
    """The recurrence row by row: the oracle and the CPU's path."""
    T, H, P = xd.shape
    E = rows.shape[0]
    owner = _owner(starts, lens, n_live, T)
    h0 = jnp.where(fresh[:, None, None] > 0, 0.0,
                   state[rows].astype(jnp.float32))       # [E, N, H*P]
    h0 = jnp.concatenate([h0, jnp.zeros_like(h0[:1])])   # E: no owner
    dec = jnp.repeat(jnp.exp(a), P, axis=1)               # [T, H*P]

    def row(h, inp):
        e, x, dt, bt, ct = inp
        he = dt * h[e] + bt[:, None] * x
        return h.at[e].set(he), jnp.einsum("nl,n->l", he, ct,
                                           precision=_HI)
    h, y = jax.lax.scan(row, h0, (owner, xd.reshape(T, H * P), dec, B, C))
    y = jnp.where((owner < E)[:, None], y, 0.0)
    live = (jnp.arange(E) < n_live) & (lens > 0)
    dest = jnp.where(live, rows, state.shape[0])         # past the end: dropped
    state = state.at[dest].set(h[:E].astype(state.dtype), mode="drop")
    return y.reshape(T, H, P), state


# =========================== kernel ==========================================
def _kernel(tok_ref, row_ref, len_ref, fresh_ref,
            xd1_ref, dec1_ref, b1_ref, c1_ref,
            xdb_ref, ab_ref, bres_ref, cres_ref, st_ref,
            st_out, ydec_ref, ych_ref, h_sc,
            *, nb, lanes, hpb, hd, lc, T):
    i = pl.program_id(0)
    n, s = len_ref[i], tok_ref[i]
    fresh = fresh_ref[i] > 0
    N = st_ref.shape[0]

    @pl.when(n == 1)
    def _one_row():
        # B_t and C_t down the sublanes: the [1, N] rows stacked and
        # transposed once, each column broadcast along a block's lanes
        stack = jnp.concatenate(
            [b1_ref[...], c1_ref[...],
             jnp.zeros((_LANES - 2, N), jnp.float32)], axis=0)
        cols = stack.T                                          # [N, 128]
        b = jnp.broadcast_to(cols[:, 0:1], (N, lanes))
        c = jnp.broadcast_to(cols[:, 1:2], (N, lanes))
        for j in range(nb):
            at = slice(j * lanes, (j + 1) * lanes)
            h = jnp.where(fresh, 0.0, st_ref[:, at].astype(jnp.float32))
            h = dec1_ref[j:j + 1, :] * h + b * xd1_ref[j:j + 1, :]
            st_out[:, at] = h.astype(st_out.dtype)
            ydec_ref[j:j + 1, :] = jnp.sum(h * c, axis=0, keepdims=True)

    @pl.when(n > 1)
    def _chunks():
        h_sc[...] = jnp.where(fresh, 0.0, st_ref[...].astype(jnp.float32))
        r = jax.lax.broadcasted_iota(jnp.int32, (lc, lc), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (lc, lc), 1)
        causal = r >= col
        tril = causal.astype(jnp.float32)
        # the chunk's whole sum in every row, as many rows as the chunk and
        # the state width need (Mosaic broadcasts a column along lanes, not
        # one number to a matrix)
        ones = jnp.ones((max(lc, N), lc), jnp.float32)
        ri = jax.lax.broadcasted_iota(jnp.int32, (lc, 1), 0)

        def chunk(k, carry):
            r0 = s + k * lc
            m = jnp.minimum(lc, n - k * lc)
            w0 = jnp.minimum(r0, T - lc)          # the window stays in bounds
            off = r0 - w0
            live = (ri >= off) & (ri < off + m)                 # [lc, 1]
            bw = jnp.where(live, bres_ref[pl.ds(w0, lc), :], 0.0)
            bwt = bw.T                                          # [N, lc]
            cw = cres_ref[pl.ds(w0, lc), :]
            cb = jax.lax.dot_general(cw, bw, (((1,), (1,)), ((), ())),
                                     precision=_HI,
                                     preferred_element_type=jnp.float32)

            def block(j, carry2):
                aw = jnp.where(live, ab_ref[j, pl.ds(w0, lc), :], 0.0)
                cs = jnp.dot(tril, aw, precision=_HI,
                             preferred_element_type=jnp.float32)
                tot = jnp.dot(ones, aw, precision=_HI,
                              preferred_element_type=jnp.float32)
                cst = cs.T                                      # [128, lc]
                x = jnp.where(live, xdb_ref[j, pl.ds(w0, lc), :], 0.0)
                at = pl.ds(pl.multiple_of(j * lanes, lanes), lanes)
                hb = h_sc[:, at]                                # [N, lanes]
                outs, hs = [], []
                for q in range(hpb):
                    csc, csr = cs[:, q:q + 1], cst[q:q + 1, :]
                    end = tot[:, q:q + 1]               # [max(lc, N), 1]
                    decay = jnp.where(causal, jnp.exp(csc - csr), 0.0)
                    xq = x[:, q * hd:(q + 1) * hd]                # [lc, P]
                    h = hb[:, q * hd:(q + 1) * hd]                # [N, P]
                    y = jnp.dot(decay * cb, xq, precision=_HI,
                                preferred_element_type=jnp.float32)
                    y += jnp.exp(csc) * jnp.dot(
                        cw, h, precision=_HI,
                        preferred_element_type=jnp.float32)
                    w = xq * jnp.exp(end[:lc] - csc)              # [lc, P]
                    hs.append(jnp.exp(end[:N]) * h + jnp.dot(
                        bwt, w, precision=_HI,
                        preferred_element_type=jnp.float32))
                    outs.append(y)
                h_sc[:, at] = hs[0] if hpb == 1 else jnp.concatenate(
                    hs, axis=1)
                y = outs[0] if hpb == 1 else jnp.concatenate(outs, axis=1)
                ych_ref[j, pl.ds(w0, lc), :] = jnp.where(
                    live, y, ych_ref[j, pl.ds(w0, lc), :])
                return carry2

            return jax.lax.fori_loop(0, nb, block, carry)

        jax.lax.fori_loop(0, (n + lc - 1) // lc, chunk, 0)
        st_out[...] = h_sc[...].astype(st_out.dtype)


def _kernel_call(state, xd, a, B, C, rows, starts, lens, fresh, n_live, *,
                 interpret):
    R, N, HP = state.shape
    T, H, P = xd.shape
    lanes = min(_LANES, HP)
    if HP != H * P or HP % lanes or lanes % P:
        raise ValueError(f"state of {HP} lanes for {H} heads x {P}: the "
                         f"kernel lays a head's lanes within {lanes}-lane "
                         f"blocks")
    nb, hpb = HP // lanes, lanes // P
    lc = min(_CHUNK, T)
    # what one row reads (by its token): lane-dense [nb, lanes] rows
    xd1 = xd.reshape(T, nb, lanes)
    dec1 = jnp.repeat(jnp.exp(a), P, axis=1).reshape(T, nb, lanes)
    b1, c1 = B[:, None, :], C[:, None, :]
    # what a chunk reads, whole in VMEM: block j's rows token-major
    xdb = xd1.transpose(1, 0, 2)
    ab = jnp.pad(a.reshape(T, nb, hpb).transpose(1, 0, 2),
                 ((0, 0), (0, 0), (0, _LANES - hpb)))

    def by_token(i, starts, rows, lens, fresh):
        return (starts[i], 0, 0)

    def by_row(i, starts, rows, lens, fresh):
        return (rows[i], 0, 0)

    def whole(*shape):
        return pl.BlockSpec(shape, lambda i, *_: (0,) * len(shape))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(n_live,),
        in_specs=[pl.BlockSpec((None, nb, lanes), by_token),
                  pl.BlockSpec((None, nb, lanes), by_token),
                  pl.BlockSpec((None, 1, N), by_token),
                  pl.BlockSpec((None, 1, N), by_token),
                  whole(nb, T, lanes), whole(nb, T, _LANES),
                  whole(T, N), whole(T, N),
                  pl.BlockSpec((None, N, HP), by_row)],
        out_specs=[pl.BlockSpec((None, N, HP), by_row),
                   pl.BlockSpec((None, nb, lanes), by_token),
                   whole(nb, T, lanes)],
        scratch_shapes=[pltpu.VMEM((N, HP), jnp.float32)],
    )
    resident = 2 * 4 * (2 * nb * T * lanes + nb * T * _LANES + 2 * T * N)
    rowbytes = 4 * HP * N * state.dtype.itemsize + 4 * HP * N
    st, ydec, ych = pallas_call(
        functools.partial(_kernel, nb=nb, lanes=lanes, hpb=hpb, hd=P, lc=lc,
                          T=T),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((R, N, HP), state.dtype),
                   jax.ShapeDtypeStruct((T, nb, lanes), jnp.float32),
                   jax.ShapeDtypeStruct((nb, T, lanes), jnp.float32)],
        # operand 12 (the pool, after the 4 scalar lists and 8 inputs) is
        # output 0: rows the grid does not name keep their bytes
        input_output_aliases={12: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=resident + rowbytes + (24 << 20)),
        interpret=interpret,
        # the device op's name in a profiler trace (``ssd.N custom-call``)
        name="ssd",
    )(starts, rows, lens, fresh, xd1, dec1, b1, c1, xdb, ab, B, C, state)
    owner = _owner(starts, lens, n_live, T)
    one = (lens[jnp.minimum(owner, lens.shape[0] - 1)] == 1)[:, None]
    y = jnp.where(one, ydec.reshape(T, HP),
                  ych.transpose(1, 0, 2).reshape(T, HP))
    y = jnp.where((owner < rows.shape[0])[:, None], y, 0.0)
    return y.reshape(T, H, P), st
