"""Non-static mode across devices: sequence-pipelined RNN inference.

The paper's non-static mode instantiates one RNN block per timestep and
passes state block-to-block, dropping the initiation interval from seq_len
to 1 block (Table 5: II 315 -> 1).  The TPU adaptation maps timestep GROUPS
to devices along a mesh axis: device k owns timesteps [k*spp, (k+1)*spp);
recurrent state hops k -> k+1 via collective_permute.  A software-pipeline
schedule streams a batch of B inferences through P stages in B + P - 1
beats; steady-state II = spp block-steps instead of T — exactly the paper's
throughput argument, with ICI hops playing the role of block-to-block wires.

Run under jax.jit with the mesh active; tests verify bit-equality with the
static scan on 8 host devices.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.config import RNNConfig
from repro.core.rnn.cells import gru_cell, lstm_cell


def pipelined_rnn(
    rnn: RNNConfig,
    xs: jax.Array,             # [B, T, F]
    W: jax.Array, U: jax.Array, b: jax.Array,
    mesh: Mesh,
    axis: str = "model",
    hoist_input: bool = False,
) -> jax.Array:
    """Returns final hidden state [B, hidden]; T must divide the axis size.

    ``hoist_input`` is the multi-device face of the hoisted-projection
    schedule (KernelSchedule.hoist_input / pipeline mode): zx = xs @ W for
    ALL timesteps is one batched matmul BEFORE the stage pipeline, so each
    stage's blocks carry only the hU recurrence — the per-stage (and thus
    per-beat) latency drops, which is exactly what shrinks the pipeline's
    initiation interval.
    """
    B, T, F = xs.shape
    n_stages = mesh.shape[axis]
    assert T % n_stages == 0, f"T={T} % stages={n_stages}"
    spp = T // n_stages
    H = rnn.hidden
    cell = lstm_cell if rnn.cell == "lstm" else gru_cell
    n_state = 2 if rnn.cell == "lstm" else 1

    if hoist_input:
        # the hoist stage: stream slices of zx (not xs) through the pipe;
        # cells consume the precomputed projection via their zx= injection
        xs = jnp.einsum("btf,fg->btg", xs, W,
                        preferred_element_type=jnp.float32).astype(xs.dtype)
        F = xs.shape[-1]

    def stage_fn(xs_local, W_, U_, b_):
        # xs_local: [B, spp, F] — this device's timestep slice (zx when
        # hoisted: F = G*H and the x-side matmul is skipped in-cell)
        k = jax.lax.axis_index(axis)
        perm = [(i, i + 1) for i in range(n_stages - 1)]

        def run_block(x_blk, state):
            # x_blk: [1, spp, F]; state tuple of [1, H]
            def step(s, x_t):
                st = (s[0], s[1]) if n_state == 2 else s[0]
                _, ns = cell(x_t, st, W_, U_, b_,
                             **({"zx": x_t} if hoist_input else {}))
                ns = ns if n_state == 2 else (ns,)
                return (ns[0],) + ((ns[1],) if n_state == 2 else ()), None
            s0 = tuple(state[i] for i in range(n_state))
            sT, _ = jax.lax.scan(step, s0, jnp.moveaxis(x_blk, 1, 0))
            return jnp.stack(sT)                       # [n_state, 1, H]

        def beat(j, carry):
            out_acc, state_in = carry
            i = j - k                                   # inference handled now
            valid = (i >= 0) & (i < B)
            idx = jnp.clip(i, 0, B - 1)
            x_blk = jax.lax.dynamic_slice(
                xs_local, (idx, 0, 0), (1, spp, F))
            boundary = jnp.where(k == 0,
                                 jnp.zeros_like(state_in), state_in)
            state_out = run_block(x_blk, boundary)
            state_out = jnp.where(valid, state_out,
                                  jnp.zeros_like(state_out))
            # emit: last stage writes the finished inference's hidden state
            emit = valid & (k == n_stages - 1)
            out_acc = jax.lax.dynamic_update_slice(
                out_acc,
                jnp.where(emit, state_out[0],
                          jax.lax.dynamic_slice(out_acc, (idx, 0), (1, H))),
                (idx, 0))
            # pass state rightwards for the next beat
            state_pass = jax.lax.ppermute(state_out, axis, perm)
            return out_acc, state_pass

        out0 = jnp.zeros((B, H), xs_local.dtype)
        s0 = jnp.zeros((n_state, 1, H), xs_local.dtype)
        out, _ = jax.lax.fori_loop(0, B + n_stages - 1, beat, (out0, s0))
        # outputs live on the last stage; share them with everyone
        out = jax.lax.psum(
            jnp.where(k == n_stages - 1, out, jnp.zeros_like(out)), axis)
        return out

    in_specs = (P(None, axis, None), P(), P(), P())
    fn = jax.shard_map(stage_fn, mesh=mesh, in_specs=in_specs,
                       out_specs=P(), check_vma=False)
    return fn(xs, W, U, b)
