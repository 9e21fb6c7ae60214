"""Fused single-step decode kernels — reuse-tiled, weight-resident.

The paper's headline engine is the SINGLE-EVENT regime: state resident, one
block processes each new element, initiation interval = one block latency.
This module is that regime's software kernel family, built so the
:class:`~repro.kernels.schedule.KernelSchedule` changes what the per-token
hot path EXECUTES (not just how it is priced or routed):

``decode_matmul``
    The scheduled gate matmul ``[B, d] @ [d, N]`` of one decode step.  The
    R reuse passes are *unrolled in-block*: the grid carries only the batch
    tiles, the whole weight matrix stays resident in VMEM for the step (the
    paper's static-mode "weights live on-chip" discipline), and each pass
    produces one ``N/R``-wide column tile.  Column tiles never split the K
    reduction, so every output element is the same full-K dot product as
    the unscheduled ``x @ w`` — the scheduled path is bit-identical to the
    einsum golden path, which the conformance tests assert exactly.

``rnn_decode_step``
    One scheduled LSTM/GRU state update (the paper's Eq. 1 as a single
    step): the cell equations come from ``core.rnn.cells`` with the gate
    matmul swapped for ``decode_matmul``, so the math lives in one place
    and scheduled == golden bitwise.  ``fp`` routes through the quantized
    cells (hls4ml ap_fixed datapath) with the same matmul injection.

Weight residency rides :data:`repro.kernels.ops.RESIDENT_WEIGHTS`: callers
pack each weight matrix ONCE per (weights identity, schedule key) into the
compute-ready layout (dtype cast, gate fusion, tile-aligned padding) via
:func:`resident_matrix` instead of re-deriving it inside every call's
compiled program — ``models/decode.py`` packs whole decoder layers through
the same cache.

Backend discipline matches ops.py: ``backend="xla"`` is the plain-dot
reference; Pallas backends run the in-block unrolled kernel (interpret on
CPU, compiled on TPU with the usual 128-lane tile checks).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ops import _pad_axis, check_tpu_alignment, resident
from repro.kernels.schedule import KernelSchedule


# ---------------------------------------------------------------------------
# The reuse-tiled, weight-resident single-step matmul
# ---------------------------------------------------------------------------


def _decode_mm_kernel(x_ref, w_ref, o_ref, *, reuse: int, ns: int):
    """One batch-tile cell: the R column-tile passes unrolled in-block.

    The full [K, N] weight block is resident for the step; pass ``r``
    reads only its K x ns column slice — the live-multiplier working set
    of the paper's reuse factor — and the passes serialize in-block, so
    the step's II is R passes, not R grid cells."""
    x = x_ref[...]
    for r in range(reuse):
        o_ref[:, r * ns:(r + 1) * ns] = jnp.dot(x, w_ref[:, r * ns:(r + 1) * ns])


def decode_matmul_pallas(x: jax.Array, w: jax.Array, *, reuse: int = 1,
                         block_m: int = 8, interpret: bool = True
                         ) -> jax.Array:
    """x: [M, K] @ w: [K, N] with the N columns computed in ``reuse``
    sequential in-block passes.  N must divide by reuse; M by block_m
    (``decode_matmul`` pads)."""
    M, K = x.shape
    K2, N = w.shape
    assert K == K2 and N % reuse == 0 and M % block_m == 0
    kernel = functools.partial(_decode_mm_kernel, reuse=reuse, ns=N // reuse)
    return pl.pallas_call(
        kernel,
        grid=(M // block_m,),
        in_specs=[
            pl.BlockSpec((block_m, K), lambda i: (i, 0)),
            pl.BlockSpec((K, N), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_m, N), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x, w)


def decode_matmul(x: jax.Array, w: jax.Array, *,
                  schedule: Optional[KernelSchedule] = None) -> jax.Array:
    """The scheduled single-step matmul: [M, K] @ [K, N] -> [M, N].

    ``schedule=None`` or ``backend="xla"`` is the golden plain dot; Pallas
    backends run :func:`decode_matmul_pallas` with the schedule's effective
    reuse (clamped to a divisor of N, hls4ml-style).  Bit-identical to the
    golden path for every R: column tiling never splits the K reduction.
    """
    if schedule is None or not schedule.use_pallas:
        return jnp.dot(x, w)
    re = schedule.effective_reuse(w.shape[-1])
    M = x.shape[0]
    bm = min(schedule.block_batch, max(8, M))
    check_tpu_alignment(schedule, tile_width=w.shape[-1] // re,
                        full_width=w.shape[-1], block_batch=bm,
                        kernel="decode_matmul")
    x_p = _pad_axis(x, 0, bm)
    out = decode_matmul_pallas(x_p, w, reuse=re, block_m=bm,
                               interpret=schedule.interpret)
    return out[:M]


# ---------------------------------------------------------------------------
# Weight residency helpers (pack once per (weights identity, schedule key))
# ---------------------------------------------------------------------------


def _residency_key(schedule: Optional[KernelSchedule], tag: str) -> str:
    base = "none" if schedule is None else schedule.key()
    return f"decode/{tag}/{base}"


def resident_matrix(w, *, schedule: Optional[KernelSchedule],
                    dtype=None, tag: str = "w") -> jax.Array:
    """The compute-ready 2D layout of one weight matrix, cached per
    (array identity, schedule key): trailing dims flattened to the matmul's
    N axis, optional dtype cast.  Tracers pack in-trace (no host cache)."""

    def pack():
        m = w.reshape(w.shape[0], -1)
        return m if dtype is None else m.astype(dtype)

    return resident(w, _residency_key(schedule, tag), pack)


def resident_fused(ws: Tuple[jax.Array, ...], *,
                   schedule: Optional[KernelSchedule], dtype=None,
                   tag: str = "fused") -> jax.Array:
    """Gate-fuse several same-K weight matrices into ONE [K, sum(N_i)]
    matrix (q|k|v, gate|up — the LSTM i|f|c|o packing at LM scale), cached
    per (identities, schedule key).  The fused dot is bit-identical to the
    separate dots: each output column keeps its own full-K reduction."""

    def pack():
        flat = [w.reshape(w.shape[0], -1) for w in ws]
        m = jnp.concatenate(flat, axis=-1) if len(flat) > 1 else flat[0]
        return m if dtype is None else m.astype(dtype)

    return resident(tuple(ws), _residency_key(schedule, tag), pack)


# ---------------------------------------------------------------------------
# Scheduled single-step RNN decode (the paper's single-event engine)
# ---------------------------------------------------------------------------


def rnn_decode_step(cell: str, x_t: jax.Array, state,
                    W: jax.Array, U: jax.Array, b: jax.Array, *,
                    schedule: Optional[KernelSchedule] = None,
                    fp=None):
    """One scheduled recurrent state update.  x_t: [B, in]; state as in
    ``core.rnn.cells`` ((h, c) for LSTM, h for GRU).  Returns (h_t, state).

    The gate matmuls ``[B, d] @ [d, G*h]`` run through
    :func:`decode_matmul` under ``schedule`` — R sequential column-tile
    passes, weights resident — and are bit-identical to the golden cells
    for every (cell, R, dtype, fp): the cell equations ARE the golden
    cells', only the matmul implementation is injected.

    Native integral fp on a Pallas schedule runs the int8/int4 step from
    ``kernels/quantized.py`` instead: the weights' nibble-/byte-packed
    layout comes from the fp-keyed residency cache and the gate matmuls
    accumulate in int32 — bit-identical to the emulation cells when the
    weights are PTQ'd (on-grid), which the conformance suite asserts.
    """
    from repro.core.quant.fixed_point import is_native_int
    from repro.core.rnn.cells import (gru_cell, gru_cell_quantized, lstm_cell,
                                      lstm_cell_quantized)

    use_pallas = schedule is not None and schedule.use_pallas
    if fp is not None and is_native_int(fp) and use_pallas:
        from repro.kernels.quantized import quantized_decode_step

        return quantized_decode_step(cell, x_t, state, W, U, b, fp=fp,
                                     schedule=schedule)
    if use_pallas:
        mm = lambda a, w: decode_matmul(a, w, schedule=schedule)  # noqa: E731
    else:
        mm = None
    if fp is not None:
        step = lstm_cell_quantized if cell == "lstm" else gru_cell_quantized
        return step(x_t, state, W, U, b, fp, matmul=mm)
    step = lstm_cell if cell == "lstm" else gru_cell
    return step(x_t, state, W, U, b, matmul=mm)
