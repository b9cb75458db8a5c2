"""Mamba-1's selective scan over a serving step's ragged packed rows, as one
Pallas TPU kernel that reads each live sequence's recurrent state once and
writes it back in place.

A Mamba-1 layer keeps, a sequence, one state ``h`` of ``[N, D]`` numbers
(state width, channels) in a pool of one row a serving slot: the state
width on sublanes, the channels along lanes. Unlike Mamba-2 (``ssd.py``)
the decay differs for every (state, channel) pair, ``exp(dt_t[c] A[n,
c])``, so there is no scalar decay a head to build a chunked product from:
the update is elementwise. The step's rows are packed back to back on one
token axis (decode rows of one token, prefill chunks of several); entry
``e`` owns rows ``[starts[e], starts[e] + lens[e])`` and the pool's row
``rows[e]``. Over its rows, in order,

    h_t = exp(dt_t A) h_{t-1} + u_t (x) B_t        u_t = dt_t x_t
    y_t = h_t C_t

starting from the row's state, or from zero where ``fresh[e]`` (its first
position is 0). The caller adds ``D x_t`` and the gate.

``grid = (D / lanes, n_live)``: a block of channels outer, the entries
inner (a traced bound). A channel block's rows of ``u``, ``dt`` and ``y``
stay in VMEM while every entry passes; the entry's state block comes
through a ``BlockSpec`` whose index map reads ``rows[e]`` from SMEM,
aliased to the output (``input_output_aliases``), so every live row
crosses HBM once each way and a row no entry names is neither read nor
written. Inside a grid step the entry's tokens are a sequential loop with
the state in VMEM, float32 whatever the pool holds: a token's ``dt`` and
``u`` rows are lane-dense and broadcast down the sublanes, its ``B`` and
``C`` columns broadcast along the lanes, and ``y`` is the sum of ``h C``
down the sublanes, written lane-dense: no lane reduction of the state, no
transpose. A decode row is one pass of that loop.

The call is ``selective_scan.N`` in a profiler trace
(``benchmark/kernels/selective_scan.py``). Off a TPU the same mathematics
runs as a sequential scan in XLA (:func:`_mode` ``"xla"``), which is also
the kernel's oracle in the tests (``"interpret"``).
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

from .ssd import _owner

__all__ = ["selective_scan_update"]

_LANES = 128
_HI = jax.lax.Precision.HIGHEST
#: VMEM for a channel block's resident rows (``u``, ``dt`` and ``y``, each
#: double-buffered): the block is the widest that fits
_RESIDENT = 24 << 20


def _mode() -> str:
    """``"mosaic"`` on a TPU, else ``"xla"``: the sequential scan (the
    Pallas interpreter would add seconds to each compile of a serving
    step). The kernel's tests ask for ``"interpret"``."""
    return "mosaic" if jax.default_backend() == "tpu" else "xla"


def selective_scan_update(state, u, dt, A, B, C, rows, starts, lens, fresh,
                          n_live):
    """``state`` [R, N, D] (the pool); ``u`` [T, D] float32 (``dt x``);
    ``dt`` [T, D] float32; ``A`` [N, D] float32; ``B``, ``C`` [T, N];
    ``rows``, ``starts``, ``lens``, ``fresh`` [E] int32, the first
    ``n_live`` entries live. Returns ``(y [T, D] float32, state')``; ``y``
    is 0 at rows no live entry owns."""
    mode = _mode()
    f32 = lambda x: x.astype(jnp.float32)      # noqa: E731
    args = (state, f32(u), f32(dt), f32(A), f32(B), f32(C),
            rows.astype(jnp.int32), starts.astype(jnp.int32),
            lens.astype(jnp.int32), fresh.astype(jnp.int32),
            jnp.asarray(n_live, jnp.int32))
    if mode == "xla":
        return _scan(*args)
    return _kernel_call(*args, interpret=mode == "interpret")


def _scan(state, u, dt, A, B, C, rows, starts, lens, fresh, n_live):
    """The recurrence row by row: the oracle and the CPU's path."""
    T = u.shape[0]
    E = rows.shape[0]
    owner = _owner(starts, lens, n_live, T)
    h0 = jnp.where(fresh[:, None, None] > 0, 0.0,
                   state[rows].astype(jnp.float32))       # [E, N, D]
    h0 = jnp.concatenate([h0, jnp.zeros_like(h0[:1])])   # E: no owner

    def row(h, inp):
        e, ut, dtt, bt, ct = inp
        he = jnp.exp(dtt[None, :] * A) * h[e] + bt[:, None] * ut[None, :]
        return h.at[e].set(he), jnp.einsum("nd,n->d", he, ct,
                                           precision=_HI)
    h, y = jax.lax.scan(row, h0, (owner, u, dt, B, C))
    y = jnp.where((owner < E)[:, None], y, 0.0)
    live = (jnp.arange(E) < n_live) & (lens > 0)
    dest = jnp.where(live, rows, state.shape[0])         # past the end: dropped
    state = state.at[dest].set(h[:E].astype(state.dtype), mode="drop")
    return y, state


# =========================== kernel ==========================================
def _blocks(D: int, T: int):
    """``(lanes, sub)``: a grid step's channel block and the width of the
    pieces it updates at a time (``[N, sub]`` float32, a few vregs)."""
    if D % _LANES:
        return D, D
    units = D // _LANES
    fit = max(1, _RESIDENT // (6 * 4 * T * _LANES))
    width = max(k for k in range(1, units + 1) if units % k == 0 and k <= fit)
    sub = max(k for k in range(1, 5) if width % k == 0)
    return width * _LANES, sub * _LANES


def _kernel(start_ref, row_ref, len_ref, fresh_ref,
            u_ref, dt_ref, a_ref, bt_ref, ct_ref, st_ref,
            st_out, y_ref, h_sc, *, sub):
    i = pl.program_id(1)
    n, s = len_ref[i], start_ref[i]
    N, lanes = h_sc.shape
    h_sc[...] = jnp.where(fresh_ref[i] > 0, 0.0,
                          st_ref[...].astype(jnp.float32))
    lane = jax.lax.broadcasted_iota(jnp.int32, (N, _LANES), 1)

    def token(r, carry):
        t = s + r
        # the token's B and C as columns: its lane of the [N, 128] window
        # the transposed rows hold it in
        sel = lane == t % _LANES
        b = jnp.sum(jnp.where(sel, bt_ref[t // _LANES], 0.0), axis=1,
                    keepdims=True)                               # [N, 1]
        c = jnp.sum(jnp.where(sel, ct_ref[t // _LANES], 0.0), axis=1,
                    keepdims=True)
        for k in range(lanes // sub):
            at = pl.ds(k * sub, sub)
            d = dt_ref[pl.ds(t, 1), at]                          # [1, sub]
            h = jnp.exp(d * a_ref[:, at]) * h_sc[:, at] \
                + b * u_ref[pl.ds(t, 1), at]
            h_sc[:, at] = h
            y_ref[pl.ds(t, 1), at] = jnp.sum(h * c, axis=0, keepdims=True)
        return carry

    jax.lax.fori_loop(0, n, token, 0)
    st_out[...] = h_sc[...].astype(st_out.dtype)


def _kernel_call(state, u, dt, A, B, C, rows, starts, lens, fresh, n_live,
                 *, interpret):
    R, N, D = state.shape
    T = u.shape[0]
    if A.shape != (N, D) or u.shape != (T, D):
        raise ValueError(f"state [{R}, {N}, {D}], A {A.shape}, u {u.shape}")
    lanes, sub = _blocks(D, T)
    # B and C transposed, 128 tokens a window: a token's column is a lane
    # of window t // 128
    nw = -(-T // _LANES)

    def windows(x):
        x = jnp.pad(x, ((0, nw * _LANES - T), (0, 0)))
        return x.reshape(nw, _LANES, N).transpose(0, 2, 1)     # [nw, N, 128]

    def rows_of(j, i, *_):
        return (0, j)

    def by_row(j, i, starts, rows, lens, fresh):
        return (rows[i], 0, j)

    def whole(*shape):
        return pl.BlockSpec(shape, lambda j, i, *_: (0,) * len(shape))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(D // lanes, n_live),
        in_specs=[pl.BlockSpec((T, lanes), rows_of),
                  pl.BlockSpec((T, lanes), rows_of),
                  pl.BlockSpec((N, lanes), rows_of),
                  whole(nw, N, _LANES), whole(nw, N, _LANES),
                  pl.BlockSpec((None, N, lanes), by_row)],
        out_specs=[pl.BlockSpec((None, N, lanes), by_row),
                   pl.BlockSpec((T, lanes), rows_of)],
        scratch_shapes=[pltpu.VMEM((N, lanes), jnp.float32)],
    )
    resident = 4 * (6 * T * lanes + 2 * N * lanes + 4 * nw * N * _LANES) \
        + 4 * N * lanes * (state.dtype.itemsize + 1)
    st, y = pallas_call(
        functools.partial(_kernel, sub=sub),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((R, N, D), state.dtype),
                   jax.ShapeDtypeStruct((T, D), jnp.float32)],
        # operand 9 (the pool, after the 4 scalar lists and 5 inputs) is
        # output 0: rows the grid does not name keep their bytes
        input_output_aliases={9: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=resident + (16 << 20)),
        interpret=interpret,
        # the device op's name in a profiler trace
        # (``selective_scan.N custom-call``)
        name="selective_scan",
    )(starts, rows, lens, fresh, u, dt, A, windows(B), windows(C), state)
    owner = _owner(starts, lens, n_live, T)
    y = jnp.where((owner < rows.shape[0])[:, None], y, 0.0)
    return y, st
