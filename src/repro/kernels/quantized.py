"""Native int8/int4 kernel bodies — the datapath `FixedPointConfig` selects.

Before this module every fixed-point config executed the SAME f32 kernels
with quantize() wrapped around each intermediate (emulation).  Here the
integral configs (``core.quant.fixed_point.is_native_int``: signed, rnd,
sat, <= 8 total bits) get genuinely low-precision execution:

  * weights live in the residency cache as int8 grid indices — int4 configs
    nibble-pack two weights per byte along K — so resident bytes drop 4x/8x
    vs the f32 layout (``packed_weight_bytes`` is the shared formula the
    HLS pricing uses, keeping measured and estimated bytes identical);
  * gate matmuls run int8 x int8 -> INT32 accumulation inside a Pallas
    kernel (``quant_matmul_pallas``) whose R reuse passes serialize the
    output column tiles exactly like the float kernels' schedule;
  * requantization happens at the gate boundaries: the int32 accumulator
    (scale 2^2F) is rescaled once and the activation/Hadamard steps apply
    the SAME quantization points as the emulation cells.

Numerical contract (what the conformance suite pins down):

  ``native_matmul`` returns ``(a_int @ w_int) / scale^2`` with the division
  EXACT in f32 — int8 products are <= 2^14 and the K-sums for tagger fan-ins
  stay far below 2^24 (f32's integer-exact range), so the native gate
  pre-activation is bit-identical to the emulation path's f32 matmul of the
  same on-grid operands.  Hence: native == emulation BIT-FOR-BIT whenever
  the weights are already on the fp grid (PTQ'd), and within one grid step
  of the numpy integer golden models (testing.py) otherwise — the weight
  quantization the packer applies is the only divergence.

Quantized datapaths never hoist (splitting z = q(xW + hU + b) would move
the hls4ml quantization points), so every schedule mode runs the same
per-timestep structure; the mode still selects pricing and the reuse factor
still tiles the kernel's output columns.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.config import FixedPointConfig
from repro.core.quant.fixed_point import (from_ints, grid_constants,
                                          is_native_int, native_bits,
                                          quantize, to_ints)
from repro.kernels.schedule import KernelSchedule, schedule_key


# ---------------------------------------------------------------------------
# Packed integer weight layouts (the residency cache's quantized format)
# ---------------------------------------------------------------------------


def pack_ints(w: jax.Array, fp: FixedPointConfig) -> jax.Array:
    """Quantize a float [K, N] weight matrix to its packed int8 layout.

    int8 grids store one weight per byte.  int4 grids nibble-pack two
    K-adjacent weights per byte (low nibble = even row, high nibble = odd
    row; odd K pads a zero row), so the packed array is [ceil(K/2), N] —
    1/8 the f32 bytes.  ``packed_weight_bytes`` prices exactly this layout.
    """
    q = to_ints(w, fp)
    if native_bits(fp) == 8:
        return q
    k = q.shape[0]
    if k % 2:
        q = jnp.concatenate([q, jnp.zeros((1,) + q.shape[1:], q.dtype)])
    qi = q.astype(jnp.int32) & 0xF          # two's-complement nibbles
    return (qi[0::2] | (qi[1::2] << 4)).astype(jnp.int8)


def unpack_ints(packed: jax.Array, fp: FixedPointConfig,
                k: int) -> jax.Array:
    """Packed layout -> int8 grid indices [k, N] (inverse of pack_ints)."""
    if native_bits(fp) == 8:
        return packed
    b = packed.astype(jnp.int32) & 0xFF
    lo = b & 0xF
    lo = lo - ((lo >= 8) << 4)              # sign-extend the 4-bit field
    hi = (b >> 4) & 0xF
    hi = hi - ((hi >= 8) << 4)
    out = jnp.stack([lo, hi], axis=1).reshape((-1,) + packed.shape[1:])
    return out[:k].astype(jnp.int8)


def packed_nbytes(packed) -> int:
    """Measured bytes of a packed layout (what the LRU accounting sees)."""
    return sum(getattr(a, "nbytes", 0)
               for a in jax.tree_util.tree_leaves(packed))


# ---------------------------------------------------------------------------
# The int32-accumulating scheduled matmul kernel
# ---------------------------------------------------------------------------


def _quant_mm_kernel(x_ref, w_ref, o_ref, *, reuse: int, ns: int):
    """One batch-tile cell: int8 operands fed to the MXU as int8, INT32
    accumulation, the R output column tiles serialized in-block (the decode
    kernels' reuse structure — column tiles never split the K reduction, so
    every output element is the full-K integer dot product)."""
    x = x_ref[...]
    for r in range(reuse):
        w = w_ref[:, r * ns:(r + 1) * ns]
        o_ref[:, r * ns:(r + 1) * ns] = jax.lax.dot_general(
            x, w, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)


def quant_matmul_pallas(x: jax.Array, w: jax.Array, *, reuse: int = 1,
                        block_m: int = 8, interpret: bool = True
                        ) -> jax.Array:
    """x: [M, K] int8 @ w: [K, N] int8 -> [M, N] int32, with the N columns
    computed in ``reuse`` sequential in-block passes over the resident
    integer weight block.  N must divide by reuse; M by block_m."""
    M, K = x.shape
    K2, N = w.shape
    assert K == K2 and N % reuse == 0 and M % block_m == 0
    kernel = partial(_quant_mm_kernel, reuse=reuse, ns=N // reuse)
    return pl.pallas_call(
        kernel,
        grid=(M // block_m,),
        in_specs=[
            pl.BlockSpec((block_m, K), lambda i: (i, 0)),
            pl.BlockSpec((K, N), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_m, N), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x, w)


def _residency_key(schedule: Optional[KernelSchedule],
                   fp: FixedPointConfig, tag: str) -> str:
    """Quantized layouts key on (schedule key, fp token): a precision change
    can never serve a stale float — or other-precision — layout."""
    return f"quant/{tag}/{schedule_key(schedule, fp)}"


def resident_quantized(w: jax.Array, fp: FixedPointConfig, *,
                       schedule: Optional[KernelSchedule] = None,
                       tag: str = "w") -> jax.Array:
    """The packed integer layout of one weight matrix, cached ONCE per
    (array identity, schedule key, fp) in RESIDENT_WEIGHTS.  The cache's
    byte accounting sees the PACKED nbytes (int4: 1/8 of f32)."""
    from repro.kernels.ops import resident

    return resident(w, _residency_key(schedule, fp, tag),
                    lambda: pack_ints(w, fp))


def _int_matmul(ai: jax.Array, wq: jax.Array,
                schedule: Optional[KernelSchedule]) -> jax.Array:
    """int8 [M, K] @ int8 [K, N] -> int32, scheduled.  Pallas backends run
    the in-block reuse-tiled kernel; the xla backend (and schedule=None)
    keep the same int32 dot as the golden integer reference."""
    if schedule is None or not schedule.use_pallas:
        return jax.lax.dot_general(
            ai.astype(jnp.int32), wq.astype(jnp.int32),
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32)
    from repro.kernels.ops import _pad_axis, check_tpu_alignment

    M = ai.shape[0]
    re = schedule.effective_reuse(wq.shape[-1])
    bm = min(schedule.block_batch, max(8, M))
    check_tpu_alignment(schedule, tile_width=wq.shape[-1] // re,
                        full_width=wq.shape[-1], block_batch=bm,
                        kernel="quant_matmul")
    a_p = _pad_axis(ai, 0, bm)
    out = quant_matmul_pallas(a_p, wq, reuse=re, block_m=bm,
                              interpret=schedule.interpret)
    return out[:M]


def native_matmul(a: jax.Array, w: jax.Array, fp: FixedPointConfig, *,
                  schedule: Optional[KernelSchedule] = None,
                  tag: str = "w") -> jax.Array:
    """The native gate matmul: quantize-to-ints, int32-accumulate, rescale.

    ``a`` [M, K] holds on-grid activations (the quantized cells quantize
    every input before the matmul, so ``to_ints`` is exact); ``w`` is the
    float weight matrix, PTQ'd to ints at residency-pack time.  Returns
    ``(a_int @ w_int) / scale^2`` as f32 — EXACT for int8/int4 ranges, i.e.
    bit-identical to the emulation path's f32 ``a @ quantize(w)``.
    """
    packed = resident_quantized(w, fp, schedule=schedule, tag=tag)
    wq = unpack_ints(packed, fp, w.shape[0])
    acc = _int_matmul(to_ints(a, fp), wq, schedule)
    scale, _, _ = grid_constants(fp)
    return acc.astype(jnp.float32) * (1.0 / (scale * scale))


# ---------------------------------------------------------------------------
# Native quantized cells (same quantization points as core.rnn.cells)
# ---------------------------------------------------------------------------
#
# The steps below mirror lstm_cell_quantized / gru_cell_quantized LINE FOR
# LINE — same q() placement, same float association order — with the gate
# matmuls swapped for native_matmul.  Because native_matmul's rescaled
# accumulator equals the emulation's f32 matmul exactly (see module doc),
# the two datapaths are bit-identical for PTQ'd weights; the conformance
# suite asserts this, which is what lets the cell math live in two places.


def _native_lstm_step(x_t, state, W, U, b, fp, schedule):
    q = lambda v: quantize(v, fp)                          # noqa: E731
    mm = lambda a, w, tag: native_matmul(a, w, fp, schedule=schedule,
                                         tag=tag)          # noqa: E731
    h_prev, c_prev = state
    hdim = h_prev.shape[-1]
    x_t = q(x_t)
    z = q(mm(x_t, W, "lstm-W") + mm(h_prev, U, "lstm-U") + b)
    i, f, g, o = (z[..., :hdim], z[..., hdim:2 * hdim],
                  z[..., 2 * hdim:3 * hdim], z[..., 3 * hdim:])
    i = q(jax.nn.sigmoid(i))
    f = q(jax.nn.sigmoid(f))
    g = q(jnp.tanh(g))
    o = q(jax.nn.sigmoid(o))
    c_t = q(q(f * c_prev) + q(i * g))
    h_t = q(o * q(jnp.tanh(c_t)))
    return h_t, (h_t, c_t)


def _native_gru_step(x_t, state, W, U, b, fp, schedule):
    q = lambda v: quantize(v, fp)                          # noqa: E731
    mm = lambda a, w, tag: native_matmul(a, w, fp, schedule=schedule,
                                         tag=tag)          # noqa: E731
    h_prev = state
    x_t = q(x_t)
    zx = q(mm(x_t, W, "gru-W") + b[0])
    zh = q(mm(h_prev, U, "gru-U") + b[1])
    zxz, zxr, zxh = jnp.split(zx, 3, axis=-1)
    zhz, zhr, zhh = jnp.split(zh, 3, axis=-1)
    z = q(jax.nn.sigmoid(zxz + zhz))
    r = q(jax.nn.sigmoid(zxr + zhr))
    hh = q(jnp.tanh(q(zxh + q(r * zhh))))
    h_t = q(q(z * h_prev) + q((1.0 - z) * hh))
    return h_t, h_t


NATIVE_STEPS = {"lstm": _native_lstm_step, "gru": _native_gru_step}


# ---------------------------------------------------------------------------
# Scheduled entry points (what ops.py dispatches to for integral fp)
# ---------------------------------------------------------------------------


def quantized_scan(cell: str, xs, W, U, b, *, fp: FixedPointConfig,
                   schedule: KernelSchedule):
    """[B, T, in] -> final hidden [B, h] on the native integer datapath.

    Weights pack ONCE per (identity, schedule key, fp) in the residency
    cache (eager call path; tracers pack in-trace as usual), then every
    timestep runs the native cell: int8 state/activations at the gate
    boundaries, int32-accumulated gate matmuls through the Pallas kernel.
    All modes share the per-timestep structure — quantized datapaths never
    hoist (it would move the q points), and a "static"-mode schedule still
    means weights-resident + R column tiles per step.
    """
    assert is_native_int(fp), fp
    # warm the residency cache eagerly (concrete weights only)
    for w, tag in ((W, f"{cell}-W"), (U, f"{cell}-U")):
        if isinstance(w, jax.Array) and not isinstance(w, jax.core.Tracer):
            resident_quantized(w, fp, schedule=schedule, tag=tag)
    return _quantized_scan_jit(xs, W, U, b, cell=cell, fp=fp,
                               schedule=schedule)


@partial(jax.jit, static_argnames=("cell", "fp", "schedule"))
def _quantized_scan_jit(xs, W, U, b, *, cell: str, fp: FixedPointConfig,
                        schedule: KernelSchedule):
    from repro.core.rnn.cells import initial_state

    B, T, _ = xs.shape
    H = U.shape[0]
    step = NATIVE_STEPS[cell]
    state = initial_state(cell, B, H, jnp.float32)
    bf = b.astype(jnp.float32)
    for t in range(T):
        _, state = step(xs[:, t].astype(jnp.float32), state, W, U, bf,
                        fp, schedule)
    h = state[0] if cell == "lstm" else state
    return h.astype(xs.dtype)


def quantized_decode_step(cell: str, x_t, state, W, U, b, *,
                          fp: FixedPointConfig,
                          schedule: Optional[KernelSchedule] = None):
    """One native single-event state update (kernels/decode_step.py's fp
    route for integral configs): same cell math, one step."""
    assert is_native_int(fp), fp
    step = NATIVE_STEPS[cell]
    return step(x_t, state, W, U, b, fp, schedule)


@partial(jax.jit, static_argnames=("fp", "schedule"))
def _quantized_rglru_jit(a, bx, *, fp: FixedPointConfig,
                         schedule: KernelSchedule):
    B, T, Wd = a.shape
    scale, lo, hi = grid_constants(fp)
    F = fp.fractional_bits
    ai = to_ints(a, fp).astype(jnp.int32)        # grid indices, scale 2^F
    bi = to_ints(bx, fp).astype(jnp.int32)
    h = jnp.zeros((B, Wd), jnp.int32)
    hs = []
    for t in range(T):
        # a*h products land on the 2^2F grid; fold bx up and requantize the
        # sum back to 2^F — integer round-half-even via the exact f32 round
        # (|acc| <= 2^15 << 2^24)
        acc = ai[:, t] * h + (bi[:, t] << F)
        h = jnp.clip(jnp.round(acc.astype(jnp.float32) * (1.0 / scale)),
                     lo, hi).astype(jnp.int32)
        hs.append(h)
    out = jnp.stack(hs, axis=1)
    return from_ints(out, fp, a.dtype)


def quantized_rglru_scan(a, bx, *, fp: FixedPointConfig,
                         schedule: KernelSchedule):
    """Native RG-LRU: matmul-free, so the whole recurrence runs on INTEGER
    grid indices (int32 elementwise products — scale 2^2F — requantized to
    the 2^F grid each step).  Bit-identical to the numpy integer golden
    model by construction: every op is exact integer arithmetic.
    """
    assert is_native_int(fp), fp
    return _quantized_rglru_jit(a, bx, fp=fp, schedule=schedule)


def quantized_reuse_matmul(x, w, *, fp: FixedPointConfig,
                           schedule: Optional[KernelSchedule] = None):
    """Native scheduled matmul: q(x) and PTQ'd w multiply as integers, the
    int32 accumulator requantizes ONCE to the fp grid (z = q(xW) — the
    dense-layer gate boundary).  The reuse factor serializes output column
    tiles in-block (kernels' N-tiling; the float kernel's K-split reuse has
    no integer analogue without double-rounding the accumulator)."""
    assert is_native_int(fp), fp
    xq = quantize(x.astype(jnp.float32), fp)
    out = native_matmul(xq, w, fp, schedule=schedule, tag="mm")
    return quantize(out, fp).astype(x.dtype)
