"""Reuse-factor matmul kernel — the TPU analogue of hls4ml's `reuse` knob.

On the FPGA, reuse R means each DSP performs R multiplications per matrix
product: DSP count shrinks by R, latency grows by R.  On TPU the analogous
serialization is K-dimension splitting: the kernel performs the matmul in R
sequential passes over K-slices, accumulating in a VMEM scratch.  The VMEM
working set for the weight operand shrinks by R (K/R x N resident at a time)
while the sequential grid length — the latency — grows by R.  This gives the
same resource/latency Pareto the paper sweeps in Tables 2-4, with VMEM bytes
playing the role of DSPs/BRAM.

Grid: (M/bm, R) — R sequential K-passes (innermost), M tiles parallel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _reuse_mm_kernel(x_ref, w_ref, o_ref, acc_scr, *, reuse: int):
    r = pl.program_id(1)

    @pl.when(r == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    acc_scr[...] += jnp.dot(x_ref[...], w_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(r == reuse - 1)
    def _emit():
        o_ref[...] = acc_scr[...].astype(o_ref.dtype)


def reuse_matmul_pallas(x: jax.Array, w: jax.Array, *, reuse: int = 1,
                        block_m: int = 128, interpret: bool = True
                        ) -> jax.Array:
    """x: [M, K] @ w: [K, N] in `reuse` sequential K-passes.

    K must divide by reuse; M by block_m (ops.py pads).
    VMEM per step: block_m*K/R (x) + (K/R)*N (w) + block_m*N (acc).
    """
    M, K = x.shape
    K2, N = w.shape
    assert K == K2 and K % reuse == 0 and M % block_m == 0
    ks = K // reuse

    kernel = functools.partial(_reuse_mm_kernel, reuse=reuse)
    return pl.pallas_call(
        kernel,
        grid=(M // block_m, reuse),
        in_specs=[
            pl.BlockSpec((block_m, ks), lambda i, r: (i, r)),
            pl.BlockSpec((ks, N), lambda i, r: (r, 0)),
        ],
        out_specs=pl.BlockSpec((block_m, N), lambda i, r: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_m, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(x, w)


def _col_mm_kernel(x_ref, w_ref, o_ref):
    o_ref[...] = jnp.dot(x_ref[...], w_ref[...],
                         preferred_element_type=jnp.float32
                         ).astype(o_ref.dtype)


def col_matmul_pallas(x: jax.Array, w: jax.Array, *, reuse: int = 1,
                      block_m: int = 128, interpret: bool = True
                      ) -> jax.Array:
    """x @ w with the OUTPUT columns serialized into `reuse` sequential tiles.

    This is the gate-matmul schedule of the scan kernels exposed standalone:
    per sequential step only a K x N/R weight tile is live (the DSP/BRAM
    working set shrinks by R) and the grid runs R sequential passes.  The
    non-static execution mode builds each per-timestep block out of these.
    N must divide by reuse; M by block_m (ops.py pads / clamps).
    """
    M, K = x.shape
    K2, N = w.shape
    assert K == K2 and N % reuse == 0 and M % block_m == 0
    ns = N // reuse

    return pl.pallas_call(
        _col_mm_kernel,
        grid=(M // block_m, reuse),
        in_specs=[
            pl.BlockSpec((block_m, K), lambda i, r: (i, 0)),
            pl.BlockSpec((K, ns), lambda i, r: (0, r)),
        ],
        out_specs=pl.BlockSpec((block_m, ns), lambda i, r: (i, r)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(x, w)


def vmem_bytes(M: int, K: int, N: int, reuse: int, block_m: int = 128,
               itemsize: int = 4) -> int:
    """Analytical VMEM working set — the 'resource' axis of the Pareto."""
    ks = K // reuse
    return (block_m * ks + ks * N + block_m * N) * itemsize
