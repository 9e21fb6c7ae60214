"""Pallas GRU static-mode scan kernel (reset_after, Keras-compatible) with
reuse-factor column tiling.

Same schedule as lstm_scan: weights VMEM-resident, h state in scratch,
sequential time grid.  GRU has 3 gate groups (z|r|hh) and the Hadamard
product sits inside the candidate tanh (r * (h U_h + b_rec)), so the kernel
accumulates the input-side (zx) and recurrent-side (zh) pre-activations in
separate scratches across the R sequential column tiles and combines them at
the last tile.  Inputs are time-major with 2-D bias tiles, as in lstm_scan.

Hoisted variant (``gru_scan_hoisted_pallas``): zx = x W + b_in for ALL
timesteps is computed outside the scan (ops.py's hoist stage) — the GRU is
the ideal hoist target because its input-side pre-activation is ALREADY kept
separate from the recurrent side in-kernel, so hoisting removes the zx dot
and scratch wholesale without touching the gate math.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.lstm_scan import store_col_tile


def _gate_update(zx, zh, h, hidden: int):
    """zx, zh: [bt, 3h] input-/recurrent-side pre-activations (z|r|hh
    packed), h: [bt, h] -> h_new.  The single home of the GRU gate math for
    all three kernel variants (in-loop / hoisted / pipeline)."""
    z = jax.nn.sigmoid(zx[:, :hidden] + zh[:, :hidden])
    rg = jax.nn.sigmoid(zx[:, hidden:2 * hidden] + zh[:, hidden:2 * hidden])
    hh = jnp.tanh(zx[:, 2 * hidden:] + rg * zh[:, 2 * hidden:])
    return z * h + (1.0 - z) * hh


def _gru_kernel(x_ref, w_ref, u_ref, b_ref, out_ref, zx_scr, zh_scr, h_scr,
                *, hidden: int, seq_len: int, reuse: int):
    t = pl.program_id(1)
    r = pl.program_id(2)

    @pl.when(jnp.logical_and(t == 0, r == 0))
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    x_t = x_ref[...]
    h = h_scr[...]
    b_in = b_ref[0:1, :]                                   # [1, gw]
    b_rec = b_ref[1:2, :]

    store_col_tile(zx_scr, r, (
        jnp.dot(x_t, w_ref[...], preferred_element_type=jnp.float32) + b_in),
        reuse)
    store_col_tile(zh_scr, r, (
        jnp.dot(h, u_ref[...], preferred_element_type=jnp.float32) + b_rec),
        reuse)

    @pl.when(r == reuse - 1)
    def _update():
        h_new = _gate_update(zx_scr[...], zh_scr[...], h_scr[...], hidden)
        h_scr[...] = h_new

        @pl.when(t == seq_len - 1)
        def _emit():
            out_ref[...] = h_new.astype(out_ref.dtype)


def _gru_hoisted_kernel(zx_ref, u_ref, b_ref, out_ref, zx_scr, zh_scr, h_scr,
                        *, hidden: int, seq_len: int, reuse: int):
    """Hoisted grid cell: zx (input side, bias folded) is precomputed; only
    the recurrent-side zh = h U + b_rec accumulates across column tiles.
    Block movement mirrors the in-loop kernel — the zx tile copy replaces
    the (x_t, W-tile) dot."""
    t = pl.program_id(1)
    r = pl.program_id(2)

    @pl.when(jnp.logical_and(t == 0, r == 0))
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    store_col_tile(zx_scr, r, zx_ref[...], reuse)
    store_col_tile(zh_scr, r, (
        jnp.dot(h_scr[...], u_ref[...], preferred_element_type=jnp.float32)
        + b_ref[...]), reuse)

    @pl.when(r == reuse - 1)
    def _update():
        h_new = _gate_update(zx_scr[...], zh_scr[...], h_scr[...], hidden)
        h_scr[...] = h_new

        @pl.when(t == seq_len - 1)
        def _emit():
            out_ref[...] = h_new.astype(out_ref.dtype)


def gru_scan_pallas(xs: jax.Array, W: jax.Array, U: jax.Array,
                    b: jax.Array, *, block_batch: int = 128,
                    reuse: int = 1, interpret: bool = True) -> jax.Array:
    """xs: [T, B, in] (time-major); W: [in, 3h]; U: [h, 3h]; b: [2, 3h]
    -> h [B, h]."""
    T, B, fin = xs.shape
    hidden = U.shape[0]
    assert B % block_batch == 0
    assert (3 * hidden) % reuse == 0
    gw = (3 * hidden) // reuse

    kernel = functools.partial(_gru_kernel, hidden=hidden, seq_len=T,
                               reuse=reuse)
    return pl.pallas_call(
        kernel,
        grid=(B // block_batch, T, reuse),
        in_specs=[
            pl.BlockSpec((None, block_batch, fin),
                         lambda i, t, r: (t, i, 0)),
            pl.BlockSpec((fin, gw), lambda i, t, r: (0, r)),
            pl.BlockSpec((hidden, gw), lambda i, t, r: (0, r)),
            pl.BlockSpec((2, gw), lambda i, t, r: (0, r)),
        ],
        out_specs=pl.BlockSpec((block_batch, hidden), lambda i, t, r: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, hidden), xs.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_batch, 3 * hidden), jnp.float32),
            pltpu.VMEM((block_batch, 3 * hidden), jnp.float32),
            pltpu.VMEM((block_batch, hidden), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(xs, W, U, b)


def _gru_pipeline_kernel(zx_ref, u_ref, b_ref, out_ref, h_scr, *,
                         hidden: int, seq_len: int, reuse: int):
    """One PIPELINED block (Fig. 1 right): R reuse passes of the hU product
    unrolled in-block, full U resident (resources replicate x seq_len as
    priced), sequential grid carries only time."""
    t = pl.program_id(1)
    gw = (3 * hidden) // reuse

    @pl.when(t == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    h = h_scr[...]
    zx = zx_ref[...]                                       # [bt, 3h], b_in in
    u = u_ref[...]
    parts = [
        jnp.dot(h, u[:, r * gw:(r + 1) * gw],
                preferred_element_type=jnp.float32)
        for r in range(reuse)
    ]
    zh = (parts[0] if reuse == 1
          else jnp.concatenate(parts, axis=-1)) + b_ref[...]
    h_new = _gate_update(zx, zh, h, hidden)
    h_scr[...] = h_new

    @pl.when(t == seq_len - 1)
    def _emit():
        out_ref[...] = h_new.astype(out_ref.dtype)


def gru_scan_pipeline_pallas(zx: jax.Array, U: jax.Array, b_rec: jax.Array,
                             *, block_batch: int = 128, reuse: int = 1,
                             interpret: bool = True,
                             out_dtype=None) -> jax.Array:
    """zx: [T, B, 3h] time-major precomputed x W + b_in (f32); U: [h, 3h];
    b_rec: [3h] -> final h [B, h].  Grid (B/bt, T): the pipelined NONSTATIC
    executor."""
    T, B, gh = zx.shape
    hidden = U.shape[0]
    assert gh == 3 * hidden
    assert B % block_batch == 0
    assert (3 * hidden) % reuse == 0

    kernel = functools.partial(_gru_pipeline_kernel, hidden=hidden,
                               seq_len=T, reuse=reuse)
    return pl.pallas_call(
        kernel,
        grid=(B // block_batch, T),
        in_specs=[
            pl.BlockSpec((None, block_batch, 3 * hidden),
                         lambda i, t: (t, i, 0)),
            pl.BlockSpec((hidden, 3 * hidden), lambda i, t: (0, 0)),
            pl.BlockSpec((1, 3 * hidden), lambda i, t: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_batch, hidden), lambda i, t: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, hidden),
                                       out_dtype if out_dtype is not None
                                       else zx.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_batch, hidden), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(zx, U, b_rec.reshape(1, -1))


def gru_scan_hoisted_pallas(zx: jax.Array, U: jax.Array, b_rec: jax.Array,
                            *, block_batch: int = 128, reuse: int = 1,
                            interpret: bool = True,
                            out_dtype=None) -> jax.Array:
    """zx: [T, B, 3h] time-major precomputed x W + b_in (f32); U: [h, 3h];
    b_rec: [3h] recurrent bias -> final h [B, h].

    Same (B/bt, T, R) sequential grid as ``gru_scan_pallas``; the live
    weight tile per step shrinks from (fin + h) x gw to h x gw.
    """
    T, B, gh = zx.shape
    hidden = U.shape[0]
    assert gh == 3 * hidden
    assert B % block_batch == 0
    assert (3 * hidden) % reuse == 0
    gw = (3 * hidden) // reuse

    kernel = functools.partial(_gru_hoisted_kernel, hidden=hidden,
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
            pltpu.VMEM((block_batch, 3 * hidden), jnp.float32),
            pltpu.VMEM((block_batch, 3 * hidden), jnp.float32),
            pltpu.VMEM((block_batch, hidden), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(zx, U, b_rec.reshape(1, -1))
