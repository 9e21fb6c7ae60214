"""Pallas RG-LRU scan kernel (recurrentgemma's recurrent core).

h_t = a_t * h_{t-1} + sqrt(1 - a_t^2) * (i_t * x_t)  with
a_t = exp(log_a_t) precomputed by the caller (gates are dense matmuls that
XLA already fuses well; the kernel owns the sequential elementwise
recurrence, which is the part XLA serializes poorly at long T).

This kernel is ALREADY in hoisted form in the sense of
``KernelSchedule.hoist_input``: its entire input side (the gated input bx
and the decay a) is precomputed by the caller — the dense gate matmuls are
the hoist stage — and only the elementwise a_t * h recurrence is
sequential.  The scheduling layer (ops.py) therefore accepts
``hoist_input`` as a no-op for rglru and runs pipeline mode as the unrolled
per-timestep elementwise chain.

Grid: (B/bt, W/wt, T) — batch and width tiles parallel, time sequential and
INNERMOST (fastest-varying) so the state scratch persists across t for each
(batch, width) tile.  State scratch: [bt, wt] f32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rglru_kernel(a_ref, bx_ref, out_ref, h_scr, *, seq_len: int):
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    a_t = a_ref[:, 0, :].astype(jnp.float32)
    b_t = bx_ref[:, 0, :].astype(jnp.float32)
    h = a_t * h_scr[...] + b_t
    h_scr[...] = h
    out_ref[:, 0, :] = h.astype(out_ref.dtype)


def rglru_scan_pallas(a: jax.Array, bx: jax.Array, *,
                      block_batch: int = 8, block_width: int = 128,
                      serial_width: bool = False,
                      interpret: bool = True) -> jax.Array:
    """a, bx: [B, T, W] (decay and gated input) -> all states h [B, T, W].

    ``serial_width=True`` is the reuse-factor schedule for this (matmul-free)
    recurrence: the width tiles execute sequentially instead of in parallel,
    so one tile's worth of VPU lanes (the DSP analogue) is reused W/wt times
    per step — resources / R, sequential grid length x R.
    """
    B, T, Wd = a.shape
    assert B % block_batch == 0 and Wd % block_width == 0
    width_sem = "arbitrary" if serial_width else "parallel"

    kernel = functools.partial(_rglru_kernel, seq_len=T)
    return pl.pallas_call(
        kernel,
        grid=(B // block_batch, Wd // block_width, T),
        in_specs=[
            pl.BlockSpec((block_batch, 1, block_width),
                         lambda i, j, t: (i, t, j)),
            pl.BlockSpec((block_batch, 1, block_width),
                         lambda i, j, t: (i, t, j)),
        ],
        out_specs=pl.BlockSpec((block_batch, 1, block_width),
                               lambda i, j, t: (i, t, j)),
        out_shape=jax.ShapeDtypeStruct((B, T, Wd), a.dtype),
        scratch_shapes=[pltpu.VMEM((block_batch, block_width), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", width_sem, "arbitrary")),
        interpret=interpret,
    )(a, bx)
