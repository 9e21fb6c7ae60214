"""Pallas LSTM static-mode scan kernel with reuse-factor column tiling.

TPU adaptation of the paper's STATIC mode (Fig. 1 left): ONE physical block —
the gate weights stay resident in VMEM across the whole sequence (the BRAM
analogue), the (h, c) state lives in VMEM scratch, and the sequential grid
dimension walks timesteps.  HBM traffic: weights read once (not T times),
x_t streamed in, final h written out — exactly the paper's resource-minimal
schedule.

Reuse factor R (hls4ml's central knob): the gate matmul z = x W + h U + b is
partitioned into R *sequential column tiles* of width 4h/R.  Per sequential
step only a (fin + h) x 4h/R weight tile is live — the parallel-multiplier
working set (DSP analogue) shrinks by R — while the sequential grid grows to
T x R steps (latency x R).  R = 1 degenerates to the fully parallel kernel.

Grid: (B/bt, T, R) — batch tiles parallel ("independent inferences"), time
and reuse sequential ("arbitrary": they carry scratch state).  Inputs are
time-major ([T, B, ...], transposed once by ops.py), so each grid cell's
block is a squeezed timestep over a (bt, features) tile: the batch tile sits
on sublanes (a multiple of 8) and the feature or gate tile on lanes (the
whole width, or a multiple of 128 — ``ops.check_tpu_alignment``).  Biases
are 2-D ``[1, G*h]`` so their column tiles share the weights' lane layout.

Hoisted variant (``lstm_scan_hoisted_pallas``): the input projection
zx = x W for ALL timesteps is computed OUTSIDE the scan as one batched
matmul (ops.py's hoist stage — full MXU utilization; only hU carries a
sequential dependency), and the sequential kernel consumes zx: per grid
cell ONE [bt, h] x [h, gw] dot instead of two, live weight tile h x gw
instead of (fin + h) x gw.  Bit-identical to the in-loop kernel: the final
pre-activation keeps the exact association (xW + hU) + b.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gate_update(z, c, hidden: int):
    """z: [bt, 4h] pre-activations, c: [bt, h] -> (h_new, c_new)."""
    i = jax.nn.sigmoid(z[:, :hidden])
    f = jax.nn.sigmoid(z[:, hidden:2 * hidden])
    g = jnp.tanh(z[:, 2 * hidden:3 * hidden])
    o = jax.nn.sigmoid(z[:, 3 * hidden:])
    c_new = f * c + i * g                                  # Hadamard products
    h_new = o * jnp.tanh(c_new)
    return h_new, c_new


def store_col_tile(scr, r, tile, reuse: int) -> None:
    """Write column tile ``r`` of a gate scratch.  At reuse 1 the tile is
    the whole width and the store is static; at reuse > 1 the lane offset is
    dynamic, and Mosaic takes it only for 128-aligned tiles."""
    if reuse == 1:
        scr[...] = tile
    else:
        gw = tile.shape[-1]
        scr[:, pl.ds(pl.multiple_of(r * gw, gw), gw)] = tile


def _lstm_kernel(x_ref, w_ref, u_ref, b_ref, out_ref, z_scr, h_scr, c_scr, *,
                 hidden: int, seq_len: int, reuse: int):
    """One (batch-tile, timestep, column-tile) grid cell."""
    t = pl.program_id(1)
    r = pl.program_id(2)

    @pl.when(jnp.logical_and(t == 0, r == 0))
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)
        c_scr[...] = jnp.zeros_like(c_scr)

    x_t = x_ref[...]                                       # [bt, in]
    h = h_scr[...]                                         # pre-update state

    # column tile r of the gate pre-activations: a (fin+h) x gw weight slice
    # is the only weight data live this step — the reuse resource saving
    store_col_tile(z_scr, r, (
        jnp.dot(x_t, w_ref[...], preferred_element_type=jnp.float32)
        + jnp.dot(h, u_ref[...], preferred_element_type=jnp.float32)
        + b_ref[...]), reuse)

    @pl.when(r == reuse - 1)
    def _update():
        h_new, c_new = _gate_update(z_scr[...], c_scr[...], hidden)
        h_scr[...] = h_new
        c_scr[...] = c_new

        @pl.when(t == seq_len - 1)
        def _emit():
            out_ref[...] = h_new.astype(out_ref.dtype)


def _lstm_hoisted_kernel(zx_ref, u_ref, b_ref, out_ref, z_scr, h_scr, c_scr,
                         *, hidden: int, seq_len: int, reuse: int):
    """Hoisted grid cell: zx = x W is precomputed for every timestep, so the
    only weight data live per step is the h x gw recurrent tile and the body
    runs ONE dot instead of two (the per-step FLOPs halve for fin ~ h).
    Block movement mirrors the in-loop kernel tile-for-tile — the zx tile
    replaces the (x_t, W-tile) pair."""
    t = pl.program_id(1)
    r = pl.program_id(2)

    @pl.when(jnp.logical_and(t == 0, r == 0))
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)
        c_scr[...] = jnp.zeros_like(c_scr)

    # (zx + zh) + b — elementwise the same association as the in-loop
    # (dot_x + dot_h) + b, so the two paths are bit-identical
    store_col_tile(z_scr, r, (
        zx_ref[...]
        + jnp.dot(h_scr[...], u_ref[...], preferred_element_type=jnp.float32)
        + b_ref[...]), reuse)

    @pl.when(r == reuse - 1)
    def _update():
        h_new, c_new = _gate_update(z_scr[...], c_scr[...], hidden)
        h_scr[...] = h_new
        c_scr[...] = c_new

        @pl.when(t == seq_len - 1)
        def _emit():
            out_ref[...] = h_new.astype(out_ref.dtype)


def lstm_scan_pallas(xs: jax.Array, W: jax.Array, U: jax.Array,
                     b: jax.Array, *, block_batch: int = 128,
                     reuse: int = 1, interpret: bool = True) -> jax.Array:
    """xs: [T, B, in] (time-major); W: [in, 4h]; U: [h, 4h]; b: [4h]
    -> final h [B, h].

    The caller (ops.py) transposes to time-major, pads B to block_batch and
    clamps ``reuse`` to a divisor of 4h.
    """
    T, B, fin = xs.shape
    hidden = U.shape[0]
    assert B % block_batch == 0
    assert (4 * hidden) % reuse == 0
    gw = (4 * hidden) // reuse

    kernel = functools.partial(_lstm_kernel, hidden=hidden, seq_len=T,
                               reuse=reuse)
    return pl.pallas_call(
        kernel,
        grid=(B // block_batch, T, reuse),
        in_specs=[
            pl.BlockSpec((None, block_batch, fin),
                         lambda i, t, r: (t, i, 0)),
            pl.BlockSpec((fin, gw), lambda i, t, r: (0, r)),
            pl.BlockSpec((hidden, gw), lambda i, t, r: (0, r)),
            pl.BlockSpec((1, gw), lambda i, t, r: (0, r)),
        ],
        out_specs=pl.BlockSpec((block_batch, hidden), lambda i, t, r: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, hidden), xs.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_batch, 4 * hidden), jnp.float32),
            pltpu.VMEM((block_batch, hidden), jnp.float32),
            pltpu.VMEM((block_batch, hidden), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(xs, W, U, b.reshape(1, -1))


def _lstm_pipeline_kernel(zx_ref, u_ref, b_ref, out_ref, h_scr, c_scr, *,
                          hidden: int, seq_len: int, reuse: int):
    """One PIPELINED block (paper Fig. 1 right): the R reuse passes of this
    timestep's hU product are unrolled INSIDE the block — resources
    replicate (the full U stays resident, as priced by estimate_schedule's
    blocks = seq_len) and the sequential grid carries only time, so the
    block frees up after its own R passes: II = schedule.ii, not T x R."""
    t = pl.program_id(1)
    gw = (4 * hidden) // reuse

    @pl.when(t == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)
        c_scr[...] = jnp.zeros_like(c_scr)

    h = h_scr[...]
    zx = zx_ref[...]
    u = u_ref[...]
    # the R sequential column-tile passes, unrolled in-block; each element
    # keeps the association (xW + hU) + b of the in-loop kernels ->
    # bit-identical (the bias joins after the tiles are concatenated)
    parts = [
        zx[:, r * gw:(r + 1) * gw]
        + jnp.dot(h, u[:, r * gw:(r + 1) * gw],
                  preferred_element_type=jnp.float32)
        for r in range(reuse)
    ]
    z = (parts[0] if reuse == 1
         else jnp.concatenate(parts, axis=-1)) + b_ref[...]
    h_new, c_new = _gate_update(z, c_scr[...], hidden)
    h_scr[...] = h_new
    c_scr[...] = c_new

    @pl.when(t == seq_len - 1)
    def _emit():
        out_ref[...] = h_new.astype(out_ref.dtype)


def lstm_scan_pipeline_pallas(zx: jax.Array, U: jax.Array, b: jax.Array, *,
                              block_batch: int = 128, reuse: int = 1,
                              interpret: bool = True,
                              out_dtype=None) -> jax.Array:
    """zx: [T, B, 4h] time-major precomputed x W (f32, NO bias) -> final
    h [B, h].

    The pipelined NONSTATIC executor: grid (B/bt, T) with the R reuse
    passes unrolled in-block (one 'block per timestep' in paper terms —
    seq_len x R sequential steps total, T grid cells).
    """
    T, B, gh = zx.shape
    hidden = U.shape[0]
    assert gh == 4 * hidden
    assert B % block_batch == 0
    assert (4 * hidden) % reuse == 0

    kernel = functools.partial(_lstm_pipeline_kernel, hidden=hidden,
                               seq_len=T, reuse=reuse)
    return pl.pallas_call(
        kernel,
        grid=(B // block_batch, T),
        in_specs=[
            pl.BlockSpec((None, block_batch, 4 * hidden),
                         lambda i, t: (t, i, 0)),
            pl.BlockSpec((hidden, 4 * hidden), lambda i, t: (0, 0)),
            pl.BlockSpec((1, 4 * hidden), lambda i, t: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_batch, hidden), lambda i, t: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, hidden),
                                       out_dtype if out_dtype is not None
                                       else zx.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_batch, hidden), jnp.float32),
            pltpu.VMEM((block_batch, hidden), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(zx, U, b.reshape(1, -1))


def lstm_scan_hoisted_pallas(zx: jax.Array, U: jax.Array, b: jax.Array, *,
                             block_batch: int = 128, reuse: int = 1,
                             interpret: bool = True,
                             out_dtype=None) -> jax.Array:
    """zx: [T, B, 4h] time-major precomputed x W (f32, NO bias); U: [h, 4h];
    b: [4h] -> final h [B, h].

    The sequential grid is identical to ``lstm_scan_pallas`` — (B/bt, T, R)
    — but each cell's live weight tile is h x gw (the xW half left the
    recurrence with the hoist stage in ops.py).
    """
    T, B, gh = zx.shape
    hidden = U.shape[0]
    assert gh == 4 * hidden
    assert B % block_batch == 0
    assert (4 * hidden) % reuse == 0
    gw = (4 * hidden) // reuse

    kernel = functools.partial(_lstm_hoisted_kernel, hidden=hidden,
                               seq_len=T, reuse=reuse)
    return pl.pallas_call(
        kernel,
        grid=(B // block_batch, T, reuse),
        in_specs=[
            pl.BlockSpec((None, block_batch, gw),
                         lambda i, t, r: (t, i, r)),
            pl.BlockSpec((hidden, gw), lambda i, t, r: (0, r)),
            pl.BlockSpec((1, gw), lambda i, t, r: (0, r)),
        ],
        out_specs=pl.BlockSpec((block_batch, hidden), lambda i, t, r: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, hidden),
                                       out_dtype if out_dtype is not None
                                       else zx.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_batch, 4 * hidden), jnp.float32),
            pltpu.VMEM((block_batch, hidden), jnp.float32),
            pltpu.VMEM((block_batch, hidden), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(zx, U, b.reshape(1, -1))
