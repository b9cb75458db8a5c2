"""The grouped products of a dropless expert layer as two Pallas TPU
kernels: each held expert's weights streamed from HBM once a call, and only
the row tiles its assignments fill computed.

A step's ``T * top_k`` (token, chosen expert) assignments are laid out by
held expert in an array of **row tiles**: expert ``e``'s rows start at
``starts[e]``, a multiple of the row tile ``tm``, and fill
``tiles[e] = ceil(size_e / tm)`` tiles, so no tile holds two experts' rows
(:func:`layout`, in an array of :func:`laid_rows` rows). Two calls
then do the layer's three products:

    gate_up : act = gate(x W_gate[e]) * (x W_up[e])   (f32 accumulate, the
              gate applied in f32, ``act`` written once in x's dtype)
    down    : out = act W_down[e]

``grid = (held expert e, column tile j)``. The weight blocks
``W[e, :, j*tn:(j+1)*tn]`` (gate and up side by side in the first call)
come through the ``BlockSpec`` pipeline, which fetches block ``s + 1``
while step ``s`` computes: every weight byte crosses HBM once a call. The
rows stay in HBM; inside a grid step a ``fori_loop`` whose trip count is
``tiles[e]`` (traced, scalar-prefetched) copies a ``[tm, width]`` row tile
into VMEM, multiplies it on the MXU against the resident weight block and
copies the ``[tm, tn]`` result back, so a step's cost is its weight block
where an expert takes a few rows. An expert that took no row runs no tile
and fetches no block: its index map names the block of the step before it
(the first live expert's first block before any live one), which the
pipeline does not fetch again.

Rows the tiles do not cover (past the last tile, and those of absent
experts) are never written, and the rows at the tail of an expert's last
tile are products of whatever its input rows there held: the caller reads
only the rows of its groups. Forward only: the layer's callers are serving steps (training of
dropless experts is ROADMAP M1).

The tile sizes come from the shapes: ``tm`` from the rows an expert takes
when the step's budget is full (:func:`row_tile`), ``tn`` the widest
column tile whose double-buffered weight blocks fit the VMEM budget
(:func:`column_tile`). The calls are ``moe_gmm_up.N`` / ``moe_gmm_down.N
custom-call`` in a profiler trace (``benchmark/kernels/moe_gmm.py``).
Off a TPU, XLA's grouped product computes the same tiles (:func:`_mode`).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
# the function itself, not ``pl.pallas_call``: the benchmark's tests count a
# serving step's attention kernels by patching that attribute, and these
# calls are not theirs (the module may be imported under the patch)
from jax._src.pallas.pallas_call import pallas_call

__all__ = ["row_tile", "column_tile", "laid_rows", "layout", "gate_up", "down"]

_LANES = 128
#: rows of a bf16 sublane tile: a row tile's height and start are multiples
_SUBLANES = 16
#: the tallest row tile. A tile's copy in, products and copy out run one
#: after another under the next weight block's fetch; measured alone on a
#: TPU v5e, 128-row tiles took 15 % longer than 64-row ones at
#: SmallThinker's widths, and a tile of 48 rows 6 % longer than one of 16
#: where an openPangu expert takes about 12 rows
_MAX_ROW_TILE = 64
#: VMEM for the double-buffered weight blocks of one call (v5e: 128 MiB).
#: Wider blocks mean fewer grid steps: at K-EXAONE's widths 1024 columns of
#: gate and up took 4 % less than 512 (measured alone on a TPU v5e)
_WEIGHT_VMEM = 48 * 1024 * 1024


def _mode() -> str:
    """Where the products run: ``"mosaic"``, the kernels compiled for the
    TPU, or off a TPU ``"xla"``: XLA's grouped product over the same row
    tiles, since the Pallas interpreter adds about 0.2 s a kernel to each
    compile of a serving step that holds experts. ``"interpret"`` (the
    kernels in Pallas interpret mode) is what the kernels' tests ask for."""
    return "mosaic" if jax.default_backend() == "tpu" else "xla"


def row_tile(rows_per_expert: float) -> int:
    """The row tile for ``rows_per_expert`` rows a held expert takes when
    the step's budget is full (``T * top_k / num_experts``): the smallest
    multiple of 16 that holds half of them (a serving step's budget runs
    part full), 16 to 64 rows."""
    rows = -(-math.ceil(rows_per_expert / 2) // _SUBLANES) * _SUBLANES
    return max(_SUBLANES, min(_MAX_ROW_TILE, rows))


def column_tile(width: int, depth: int, n_weights: int, itemsize: int) -> int:
    """The widest divisor of ``width`` that is a multiple of 128 and whose
    ``n_weights`` double-buffered ``[depth, tn]`` blocks fit the weights'
    VMEM; a width that is no multiple of 128 is one block."""
    if width % _LANES:
        return width
    best = _LANES
    for tn in range(_LANES, width + 1, _LANES):
        if width % tn == 0 \
                and 2 * n_weights * depth * tn * itemsize <= _WEIGHT_VMEM:
            best = tn
    return best


def laid_rows(R: int, n: int, tm: int) -> int:
    """Rows of the array that holds ``R`` assignments laid out for ``n``
    experts in tiles of ``tm``: at most ``tm - 1`` of padding an expert,
    rounded up to a whole tile (a TPU's grouped product wants whole
    tiles)."""
    return -(-(R + n * (tm - 1)) // tm) * tm


def layout(slot, n, tm):
    """Where each assignment sits in the row tiles. ``slot`` ``[R]`` int32
    holds each assignment's held expert, ``n`` for one that no held expert
    takes. Returns ``(sizes, starts, tiles, dest)``: each held expert's
    rows ``[n]``, its first row (a multiple of ``tm``) and its tile count,
    and each assignment's row ``[R]`` in an array of :func:`laid_rows`
    rows (the absent experts' after the last tile, which nothing covers)."""
    R = slot.shape[0]
    order = jnp.argsort(slot, stable=True)
    sizes = jnp.bincount(slot, length=n + 1).astype(jnp.int32)   # [n + 1]
    tiles = (sizes[:n] + tm - 1) // tm
    padded = jnp.concatenate([tiles * tm, sizes[n:]])
    starts = jnp.cumsum(padded) - padded                        # [n + 1]
    first = jnp.cumsum(sizes) - sizes                           # unpadded
    e_sorted = slot[order]
    row = starts[e_sorted] + jnp.arange(R, dtype=jnp.int32) - first[e_sorted]
    dest = jnp.zeros((R,), jnp.int32).at[order].set(row.astype(jnp.int32))
    return sizes[:n], starts[:n].astype(jnp.int32), tiles, dest


def _block_map(tiles, n_cols):
    """Each expert's weight block index ``(expert, column)`` for a step
    of an expert without rows: the step before it's (the last column of
    the last live expert before it), or before any live expert the first
    live expert's first block."""
    n = tiles.shape[0]
    live = tiles > 0
    idx = jnp.arange(n, dtype=jnp.int32)
    last = jax.lax.cummax(jnp.where(live, idx, -1))
    first_live = jnp.argmax(live).astype(jnp.int32)
    blk_e = jnp.where(last >= 0, last, first_live).astype(jnp.int32)
    blk_j = jnp.where(last >= 0, n_cols - 1, 0).astype(jnp.int32)
    return blk_e, blk_j


def _kernel(starts_ref, tiles_ref, blk_e_ref, blk_j_ref, x_hbm, *refs,
            tm, tn, gate):
    *w_refs, o_hbm, xbuf, obuf, sems = refs
    e, j = pl.program_id(0), pl.program_id(1)
    col = pl.multiple_of(j * tn, tn)

    def tile(i, carry):
        r0 = pl.multiple_of(starts_ref[e] + i * tm, _SUBLANES)
        load = pltpu.make_async_copy(x_hbm.at[pl.ds(r0, tm)], xbuf,
                                     sems.at[0])
        load.start()
        load.wait()
        x = xbuf[...]
        y = jnp.dot(x, w_refs[0][...], preferred_element_type=jnp.float32)
        if gate is not None:
            y = gate(y) * jnp.dot(x, w_refs[1][...],
                                  preferred_element_type=jnp.float32)
        obuf[...] = y.astype(obuf.dtype)
        store = pltpu.make_async_copy(
            obuf, o_hbm.at[pl.ds(r0, tm), pl.ds(col, tn)], sems.at[1])
        store.start()
        store.wait()
        return carry

    jax.lax.fori_loop(0, tiles_ref[e], tile, 0)


@functools.partial(jax.jit,
                   static_argnames=("tm", "tn", "gate", "name", "mode"))
def _grouped(x, weights, starts, tiles, *, tm, tn, gate, name, mode):
    """``[rows, width]`` rows times each expert's ``[n, width, out]``
    weights (two, multiplied through ``gate``, or one). Jitted, so that an
    eager caller compiles a shape once."""
    n, depth, width = weights[0].shape
    itemsize = jnp.dtype(weights[0].dtype).itemsize
    if tn is None:
        tn = column_tile(width, depth, len(weights), itemsize)
    if width % tn or tm % _SUBLANES:
        raise ValueError(f"tiles ({tm}, {tn}) for width {width}")
    n_cols = width // tn

    def w_map(e, j, starts, tiles, blk_e, blk_j):
        return (blk_e[e], 0, jnp.where(tiles[e] > 0, j, blk_j[e]))

    blocks = 2 * len(weights) * depth * tn * itemsize
    rows = tm * (depth + tn) * jnp.dtype(x.dtype).itemsize
    scratch = (len(weights) + 1) * tm * tn * 4        # the f32 products
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(n, n_cols),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)]
        + [pl.BlockSpec((None, depth, tn), w_map) for _ in weights],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.VMEM((tm, depth), x.dtype),
                        pltpu.VMEM((tm, tn), x.dtype),
                        pltpu.SemaphoreType.DMA((2,))],
    )
    kernel = pallas_call(
        functools.partial(_kernel, tm=tm, tn=tn, gate=gate),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((x.shape[0], width), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=blocks + rows + scratch + (8 << 20)),
        interpret=mode == "interpret",
        # the device op's name in a profiler trace (``moe_gmm_up.N
        # custom-call``); without it the op is named after the jitted caller
        name=name,
    )

    def call(starts, tiles, x, *weights):
        if mode != "xla":
            return kernel(starts, tiles, *_block_map(tiles, n_cols), x,
                          *weights)
        # each expert's group is its tiles, the rows past them nought
        y = [jax.lax.ragged_dot(x, w, tiles * tm,
                                preferred_element_type=jnp.float32)
             for w in weights]
        return (y[0] if gate is None else gate(y[0]) * y[1]).astype(x.dtype)

    # forward only: an eager caller that records gradients traces the
    # forward, and a backward pass is refused
    product = jax.custom_vjp(call)

    def backward(_, g):
        raise NotImplementedError(
            "moe_gmm is forward only (training of dropless experts: "
            "ROADMAP M1)")

    product.defvjp(lambda *a: (call(*a), None), backward)
    return product(starts, tiles, x, *weights)


def gate_up(x, w_gate, w_up, starts, tiles, *, tm, activation="silu",
            tn=None):
    """``act = gate(x W_gate[e]) * (x W_up[e])`` over each held expert's
    row tiles: ``x`` ``[rows, d]`` laid out by :func:`layout`, weights
    ``[n, d, f]``, ``activation`` ``"silu"`` or ``"relu"``. Returns
    ``[rows, f]`` in x's dtype, defined on the tiles only."""
    gate = {"silu": jax.nn.silu, "relu": jax.nn.relu}[activation]
    return _grouped(x, (w_gate, w_up), starts, tiles, tm=tm, tn=tn,
                    gate=gate, name="moe_gmm_up", mode=_mode())


def down(act, w_down, starts, tiles, *, tm, tn=None):
    """``out = act W_down[e]`` over each held expert's row tiles: ``act``
    ``[rows, f]``, ``w_down`` ``[n, f, d]``. Returns ``[rows, d]``,
    defined on the tiles only."""
    return _grouped(act, (w_down,), starts, tiles, tm=tm, tn=tn,
                    gate=None, name="moe_gmm_down", mode=_mode())
