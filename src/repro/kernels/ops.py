"""Jit'd public wrappers around the Pallas kernels: padding to hardware-
aligned tiles, dtype handling, and — the scheduling layer — dispatch of every
scan kernel through a single :class:`KernelSchedule`.

A schedule carries (reuse_factor, mode, block_batch, backend) and selects:

  backend "xla"             the lax.scan golden reference (ref.py) — the
                            bit-for-bit ground truth of the conformance
                            harness;
  backend "pallas_*"/"auto" the Pallas kernels.  Static mode runs the
                            weights-resident scan kernel with the gate
                            matmuls partitioned into reuse_factor sequential
                            column tiles; non-static mode unrolls one block
                            per timestep, each block built from the
                            column-serialized ``col_matmul`` kernel (paper
                            Fig. 1 right); pipeline mode is non-static with
                            the input projection hoisted (NONSTATIC in paper
                            terms: slimmed blocks, II = schedule.ii).

Hoisted input projection (``schedule.hoist_input``): of the gate matmul
z = x W + h U + b only the hU half carries a sequential dependency — xW for
all T timesteps is embarrassingly parallel, so the hoist stage computes it
as ONE batched [B*T, fin] @ [fin, G*h] matmul outside the scan (full MXU
utilization; R-tiled through ``col_matmul`` only when ``hoist_reuse`` > 1)
and the sequential kernel consumes the precomputed zx.  The hoisted and
in-loop paths are bit-identical: the pre-activation keeps the association
(xW + hU) + b, and the conformance suite enforces the bit-match.

The same schedule object drives ``core.hls.resources.estimate_schedule`` so
software latency/resource numbers describe exactly what executes here.

TPU lane alignment: when a schedule runs compiled Mosaic, the per-reuse
column tile is a lane-dimension block — Mosaic requires it to span the whole
gate width or be a multiple of 128 lanes (and the batch tile a multiple of 8
sublanes).  The dispatch validates this at schedule-application time and
raises a clear ValueError instead of failing inside the compiler.

``backend="auto"`` compiles on a TPU and interprets on the CPU
(``schedule.resolve_interpret``).  The scan kernels take time-major inputs;
the wrappers here transpose ``[B, T, ...]`` once before the kernel call.
"""

from __future__ import annotations

from collections import OrderedDict
from functools import partial
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

import math

from repro.config import FixedPointConfig
from repro.core.quant.fixed_point import is_native_int
from repro.kernels import ref
from repro.kernels.fixed_point import fixed_point_pallas
from repro.kernels.gru_scan import (gru_scan_hoisted_pallas, gru_scan_pallas,
                                    gru_scan_pipeline_pallas)
from repro.kernels.hadamard import hadamard_pallas
from repro.kernels.lstm_scan import (lstm_scan_hoisted_pallas,
                                     lstm_scan_pallas,
                                     lstm_scan_pipeline_pallas)
from repro.kernels.reuse_matmul import col_matmul_pallas, reuse_matmul_pallas
from repro.kernels.rglru_scan import rglru_scan_pallas
from repro.kernels.schedule import KernelSchedule, resolve_interpret

#: Mosaic tiling floors for f32 blocks — last dim lanes, second-to-last
#: sublanes; a column tile off these boundaries miscompiles on hardware
TPU_LANES = 128
TPU_SUBLANES = 8


def check_tpu_alignment(schedule: KernelSchedule, *, tile_width: int,
                        full_width: int, block_batch: int,
                        kernel: str) -> None:
    """Validate Mosaic tiling for a schedule that runs compiled Pallas.

    The per-reuse column tile of width ``tile_width`` (out of a gate
    dimension of ``full_width``) is a lane-dim block: Mosaic takes it when
    it spans the whole dimension or is a multiple of 128 lanes.  The batch
    tile spans sublanes.  Interpreted and XLA schedules have no such
    constraint, so the check applies only where the schedule resolves to
    compiled Mosaic — raising at schedule-application time with an
    actionable message instead of failing inside the compiler.
    """
    if not schedule.use_pallas or schedule.interpret:
        return
    if tile_width != full_width and tile_width % TPU_LANES != 0:
        raise ValueError(
            f"{kernel}: compiled column tile width {tile_width} of "
            f"{full_width} is not a multiple of {TPU_LANES} lanes (schedule "
            f"{schedule.key()}). Pick a reuse factor so the per-reuse tile "
            f"is the whole gate width or 128-aligned, or pad the gate "
            f"dimension.")
    if block_batch % TPU_SUBLANES != 0:
        raise ValueError(
            f"{kernel}: compiled batch tile {block_batch} is not a "
            f"multiple of {TPU_SUBLANES} sublanes (schedule "
            f"{schedule.key()}). Use a block_batch that is 8-aligned.")


def _pad_axis(x: jax.Array, axis: int, multiple: int) -> jax.Array:
    n = x.shape[axis]
    pad = (-n) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _time_major(xs: jax.Array, bt: int) -> jax.Array:
    """[B, T, ...] -> [T, B_pad, ...] with the batch padded to the batch
    tile: the scan kernels' layout, where each timestep is one
    (batch-tile, features) block."""
    return jnp.swapaxes(_pad_axis(xs, 0, bt), 0, 1)


def _resolve(schedule: Optional[KernelSchedule],
             block_batch: Optional[int], default_bb: int = 128
             ) -> KernelSchedule:
    if schedule is None:
        return KernelSchedule(block_batch=block_batch or default_bb)
    if block_batch is not None:
        return schedule.replace(block_batch=block_batch)
    return schedule


# ---------------------------------------------------------------------------
# Weight residency: pack each weight ONCE per (weights identity, schedule key)
# ---------------------------------------------------------------------------


class WeightResidency:
    """Host-side cache of packed/padded weight layouts.

    The kernels' weight transformations (compute-dtype cast, gate fusion,
    R-tile layout) are pure functions of the weight arrays and the schedule
    key, yet before this cache they re-ran inside every call's compiled
    program.  ``get`` runs the pack function ONCE per (source identity,
    schedule key) and returns the resident result on every later call — the
    software analogue of the paper's weights-stay-on-chip static mode.

    Safety: only IMMUTABLE sources are cacheable — every source must be a
    ``jax.Array`` (in-place mutation is impossible, so an identity hit
    implies value equality); numpy or other mutable buffers pack uncached,
    exactly like the pre-cache behavior.  An entry stores a strong
    reference to every source array, so CPython cannot recycle an ``id``
    while the entry lives, and a hit additionally verifies each source
    ``is`` the remembered object.  Tracers never reach the cache — callers
    bypass it in-trace, where packing stays a traced (and XLA-CSE'd)
    computation.  Eviction is LRU, bounded BOTH by entry count and by the
    packed payload's total bytes (LM-scale packs would otherwise pin many
    model-sized copies in a count-only cache).
    """

    def __init__(self, max_entries: int = 128,
                 max_bytes: int = 512 * 1024 * 1024):
        self.max_entries = max_entries
        self.max_bytes = max_bytes
        self.hits = 0
        self.misses = 0
        self.bytes = 0
        self._entries: "OrderedDict[Tuple, Tuple[Tuple, object, int]]" = \
            OrderedDict()

    @staticmethod
    def _nbytes(packed) -> int:
        return sum(getattr(a, "nbytes", 0)
                   for a in jax.tree_util.tree_leaves(packed))

    def get(self, srcs, key: str, pack: Callable[[], object]):
        """Packed layout for ``srcs`` (one array or a tuple) under ``key``."""
        if not isinstance(srcs, tuple):
            srcs = (srcs,)
        if not all(isinstance(a, jax.Array)
                   and not isinstance(a, jax.core.Tracer) for a in srcs):
            return pack()       # tracer or mutable buffer: never cache
        ck = (key,) + tuple(id(a) for a in srcs)
        ent = self._entries.get(ck)
        if ent is not None and all(a is b for a, b in zip(ent[0], srcs)):
            self.hits += 1
            self._entries.move_to_end(ck)
            return ent[1]
        self.misses += 1
        packed = pack()
        nb = self._nbytes(packed)
        self._entries[ck] = (srcs, packed, nb)
        self.bytes += nb
        while self._entries and (len(self._entries) > self.max_entries
                                 or self.bytes > self.max_bytes):
            _, (_, _, old_nb) = self._entries.popitem(last=False)
            self.bytes -= old_nb
        return packed

    def __len__(self) -> int:
        return len(self._entries)

    def clear(self) -> None:
        self._entries.clear()
        self.bytes = 0


#: module-level residency cache shared by the scan wrappers and the decode
#: kernels (kernels/decode_step.py, models/decode.py pack through it too)
RESIDENT_WEIGHTS = WeightResidency()


def resident(srcs, key: str, pack: Callable[[], object]):
    """Module-level convenience over :data:`RESIDENT_WEIGHTS`."""
    return RESIDENT_WEIGHTS.get(srcs, key, pack)


def _scan_weights_resident(cell: str, W, U, b, schedule: KernelSchedule):
    """The Pallas scan kernels compute every gate matmul in f32
    (``preferred_element_type``/explicit casts in ``_gate_mm`` and
    ``_hoist_stage``), so the f32 weight layout is schedule-invariant data —
    pre-cast it once per weights identity instead of re-casting inside every
    compiled call.  bf16 -> f32 is exact, hence bit-identical to the in-call
    cast.  The XLA golden path computes in the caller's dtype and is left
    untouched."""
    if not schedule.use_pallas:
        return W, U, b

    def pack():
        return (jnp.asarray(W, jnp.float32), jnp.asarray(U, jnp.float32),
                jnp.asarray(b, jnp.float32))

    return resident((W, U, b), f"{cell}-scan-f32", pack)


# ---------------------------------------------------------------------------
# Hoisted input-projection stage + per-timestep unrolled blocks
# ---------------------------------------------------------------------------


def _gate_mm(x: jax.Array, w: jax.Array, reuse: int,
             interpret: bool) -> jax.Array:
    """f32 x @ w through the column-tiled Pallas kernel (one per-timestep
    'block' of the non-static pipeline)."""
    M = x.shape[0]
    bm = min(128, max(8, M))
    x_p = _pad_axis(x.astype(jnp.float32), 0, bm)
    out = col_matmul_pallas(x_p, w.astype(jnp.float32), reuse=reuse,
                            block_m=bm, interpret=interpret)
    return out[:M]


def _hoist_stage(xs: jax.Array, W: jax.Array,
                 schedule: KernelSchedule) -> jax.Array:
    """The hoisted input projection: ONE batched [B*T, fin] @ [fin, G*h]
    matmul outside the sequential scan (f32 accumulate, no bias) — the
    embarrassingly parallel half of the gate pre-activation, previously
    recomputed inside every sequential grid cell.  Layout-agnostic over the
    leading axes: ``[..., fin] -> [..., G*h]`` (the scan wrappers pass
    time-major inputs, so zx comes out in the kernels' layout).

    Fully parallel (one full-MXU pass) unless the schedule asks for R-tiling
    via ``hoist_reuse``, in which case it runs as sequential column tiles
    through the same ``col_matmul`` kernel the non-static blocks use.
    """
    flat = xs.reshape(-1, xs.shape[-1])
    hr = math.gcd(schedule.hoist_reuse, W.shape[-1])
    if hr > 1:
        check_tpu_alignment(schedule, tile_width=W.shape[-1] // hr,
                            full_width=W.shape[-1],
                            block_batch=min(128, max(8, flat.shape[0])),
                            kernel="hoist_stage")
        zx = _gate_mm(flat, W, hr, schedule.interpret)
    else:
        zx = jnp.dot(flat, W, preferred_element_type=jnp.float32)
    return zx.reshape(xs.shape[:-1] + (W.shape[-1],))


def _cell_pipeline(cell: str, xs, W, U, b,
                   schedule: KernelSchedule) -> jax.Array:
    """The fused pipelined-NONSTATIC executor: hoist stage + ONE Pallas
    kernel whose grid carries only (batch, time) and whose block unrolls
    the R reuse passes of the hU product in-silicon (Fig. 1 right) — the
    schedule estimate_schedule prices with blocks = seq_len and
    II = schedule.ii."""
    B, T, _ = xs.shape
    H = U.shape[0]
    g = 4 if cell == "lstm" else 3
    re = schedule.effective_reuse(g * H)
    bt = min(schedule.block_batch, max(8, B))
    check_tpu_alignment(schedule, tile_width=g * H // re, full_width=g * H,
                        block_batch=bt, kernel=f"{cell}_scan")
    zx = _hoist_stage(_time_major(xs, bt), W, schedule)
    if cell == "lstm":
        out = lstm_scan_pipeline_pallas(zx, U, b, block_batch=bt, reuse=re,
                                        interpret=schedule.interpret,
                                        out_dtype=xs.dtype)
    else:
        out = gru_scan_pipeline_pallas(zx + b[0], U, b[1], block_batch=bt,
                                       reuse=re,
                                       interpret=schedule.interpret,
                                       out_dtype=xs.dtype)
    return out[:B]


def _cell_unrolled(cell: str, xs, W, U, b,
                   schedule: KernelSchedule) -> jax.Array:
    """One block per timestep (Fig. 1 right): the cell equations come from
    core.rnn.cells with the gate matmul swapped for the column-serialized
    Pallas kernel — the math lives in exactly one place.

    With ``schedule.hoist_input`` the xW projections for ALL timesteps come
    from the hoist stage and each block computes only its hU tiles — the
    same restructuring the fused pipeline kernel executes in one call.
    """
    from repro.core.rnn.cells import gru_cell, initial_state, lstm_cell

    B, T, _ = xs.shape
    H = U.shape[0]
    g = 4 if cell == "lstm" else 3
    re = schedule.effective_reuse(g * H)
    itp = schedule.interpret
    check_tpu_alignment(schedule, tile_width=g * H // re, full_width=g * H,
                        block_batch=min(128, max(8, B)),
                        kernel=f"{cell}_scan")

    def mm(a, w):
        return _gate_mm(a, w, re, itp)

    zx_all = None
    if schedule.hoist_input:
        flat = xs.reshape(B * T, -1)
        hr = math.gcd(schedule.hoist_reuse, g * H)
        check_tpu_alignment(schedule, tile_width=g * H // hr,
                            full_width=g * H,
                            block_batch=min(128, max(8, flat.shape[0])),
                            kernel="hoist_stage")
        # same col-serialized kernel as the in-loop blocks -> bit-identical
        zx_all = _gate_mm(flat, W, max(hr, 1), itp).reshape(B, T, g * H)

    state = initial_state(cell, B, H, jnp.float32)
    bf = b.astype(jnp.float32)
    step = lstm_cell if cell == "lstm" else gru_cell
    for t in range(T):
        _, state = step(xs[:, t], state, W, U, bf, matmul=mm,
                        zx=None if zx_all is None else zx_all[:, t])
    h = state[0] if cell == "lstm" else state
    return h.astype(xs.dtype)


# ---------------------------------------------------------------------------
# Fixed-point dispatch: native int bodies vs ap_fixed emulation
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("cell", "fp"))
def _emulated_scan_jit(xs, W, U, b, *, cell: str,
                       fp: FixedPointConfig):
    """The ap_fixed EMULATION scan: the quantized cells from core.rnn.cells
    (f32 compute, quantize() at every hls4ml datapath point) unrolled over
    T — the fallback body for every fp ``is_native_int`` does not cover
    (wide words, trn rounding, wrap saturation, unsigned)."""
    from repro.core.rnn.cells import (gru_cell_quantized, initial_state,
                                      lstm_cell_quantized)

    B, T, _ = xs.shape
    H = U.shape[0]
    step = lstm_cell_quantized if cell == "lstm" else gru_cell_quantized
    state = initial_state(cell, B, H, jnp.float32)
    bf = b.astype(jnp.float32)
    for t in range(T):
        _, state = step(xs[:, t].astype(jnp.float32), state, W, U, bf, fp)
    h = state[0] if cell == "lstm" else state
    return h.astype(xs.dtype)


def _scan_fp_dispatch(cell: str, xs, W, U, b, schedule: KernelSchedule,
                      fp: FixedPointConfig):
    """Route a quantized scan: native int bodies for integral fp on a
    Pallas backend, the f32 emulation otherwise (incl. backend="xla" —
    the quantized golden reference stays the emulation cells)."""
    from repro.kernels.quantized import quantized_scan

    if is_native_int(fp) and schedule.use_pallas:
        return quantized_scan(cell, xs, W, U, b, fp=fp, schedule=schedule)
    return _emulated_scan_jit(xs, W, U, b, cell=cell, fp=fp)


# ---------------------------------------------------------------------------
# Scheduled scan kernels
# ---------------------------------------------------------------------------


def lstm_scan(xs, W, U, b, *, schedule: Optional[KernelSchedule] = None,
              block_batch: Optional[int] = None,
              fp: Optional[FixedPointConfig] = None):
    """[B, T, in] -> final hidden [B, h], scheduled by ``schedule``.

    Eager wrapper: resolves the schedule and fetches the weights' resident
    f32 layout from :data:`RESIDENT_WEIGHTS` (packed once per weights
    identity) before entering the jitted kernel body — repeated calls with
    the same weight arrays stop re-casting them in-program.  Under an outer
    jit the inputs are tracers, the cache bypasses itself, and the packing
    stays in-trace exactly as before.

    ``fp`` selects the fixed-point datapath: None is today's float route
    (bit-identical), an ``is_native_int`` config runs the int8/int4 kernel
    bodies (kernels/quantized.py) on Pallas backends, any other config runs
    the ap_fixed emulation cells.
    """
    schedule = _resolve(schedule, block_batch)
    if fp is not None:
        return _scan_fp_dispatch("lstm", xs, W, U, b, schedule, fp)
    W, U, b = _scan_weights_resident("lstm", W, U, b, schedule)
    return _lstm_scan_jit(xs, W, U, b, schedule=schedule)


@partial(jax.jit, static_argnames=("schedule",))
def _lstm_scan_jit(xs, W, U, b, *, schedule: KernelSchedule):
    if not schedule.use_pallas:
        return ref.lstm_scan_ref(xs, W, U, b)
    if schedule.mode == "pipeline":
        return _cell_pipeline("lstm", xs, W, U, b, schedule)
    if schedule.mode == "nonstatic":
        return _cell_unrolled("lstm", xs, W, U, b, schedule)
    B = xs.shape[0]
    bt = min(schedule.block_batch, max(8, B))
    reuse = schedule.effective_reuse(4 * U.shape[0])
    check_tpu_alignment(schedule, tile_width=4 * U.shape[0] // reuse,
                        full_width=4 * U.shape[0], block_batch=bt,
                        kernel="lstm_scan")
    xs_t = _time_major(xs, bt)
    if schedule.hoist_input:
        zx = _hoist_stage(xs_t, W, schedule)
        out = lstm_scan_hoisted_pallas(zx, U, b, block_batch=bt, reuse=reuse,
                                       interpret=schedule.interpret,
                                       out_dtype=xs.dtype)
    else:
        out = lstm_scan_pallas(xs_t, W, U, b, block_batch=bt, reuse=reuse,
                               interpret=schedule.interpret)
    return out[:B]


def gru_scan(xs, W, U, b, *, schedule: Optional[KernelSchedule] = None,
             block_batch: Optional[int] = None,
             fp: Optional[FixedPointConfig] = None):
    """GRU counterpart of :func:`lstm_scan` (same eager wrapper + resident
    f32 weight layout + jitted body split + fp dispatch)."""
    schedule = _resolve(schedule, block_batch)
    if fp is not None:
        return _scan_fp_dispatch("gru", xs, W, U, b, schedule, fp)
    W, U, b = _scan_weights_resident("gru", W, U, b, schedule)
    return _gru_scan_jit(xs, W, U, b, schedule=schedule)


@partial(jax.jit, static_argnames=("schedule",))
def _gru_scan_jit(xs, W, U, b, *, schedule: KernelSchedule):
    if not schedule.use_pallas:
        return ref.gru_scan_ref(xs, W, U, b)
    if schedule.mode == "pipeline":
        return _cell_pipeline("gru", xs, W, U, b, schedule)
    if schedule.mode == "nonstatic":
        return _cell_unrolled("gru", xs, W, U, b, schedule)
    B = xs.shape[0]
    bt = min(schedule.block_batch, max(8, B))
    reuse = schedule.effective_reuse(3 * U.shape[0])
    check_tpu_alignment(schedule, tile_width=3 * U.shape[0] // reuse,
                        full_width=3 * U.shape[0], block_batch=bt,
                        kernel="gru_scan")
    xs_t = _time_major(xs, bt)
    if schedule.hoist_input:
        # GRU keeps input- and recurrent-side pre-activations separate, so
        # the input bias folds into the hoisted zx (same add order as the
        # in-loop kernel's dot + b_in)
        zx = _hoist_stage(xs_t, W, schedule) + b[0]
        out = gru_scan_hoisted_pallas(zx, U, b[1], block_batch=bt,
                                      reuse=reuse,
                                      interpret=schedule.interpret,
                                      out_dtype=xs.dtype)
    else:
        out = gru_scan_pallas(xs_t, W, U, b, block_batch=bt, reuse=reuse,
                              interpret=schedule.interpret)
    return out[:B]


@jax.jit
def hadamard(a, b):
    shape = a.shape
    rows = a.size // shape[-1]
    a2 = a.reshape(rows, shape[-1])
    b2 = b.reshape(rows, shape[-1])
    bn = min(1024, rows)
    a2 = _pad_axis(a2, 0, bn)
    b2 = _pad_axis(b2, 0, bn)
    out = hadamard_pallas(a2, b2, block=bn, interpret=resolve_interpret())
    return out[:rows].reshape(shape)


def fixed_point(x, fp: FixedPointConfig):
    @jax.jit
    def run(x):
        shape = x.shape
        x2 = x.reshape(-1, shape[-1])
        bn = min(1024, x2.shape[0])
        x2 = _pad_axis(x2, 0, bn)
        out = fixed_point_pallas(x2, fp, block=bn,
                                 interpret=resolve_interpret())
        return out[: (x.size // shape[-1])].reshape(shape)
    return run(x)


@partial(jax.jit, static_argnames=("fp",))
def _rglru_emulated_jit(a, bx, *, fp: FixedPointConfig):
    """ap_fixed emulation of the RG-LRU recurrence: gates and state on the
    grid, one requantization per step (h = q(q(a)*h + q(bx)))."""
    from repro.core.quant.fixed_point import quantize

    B, T, W = a.shape
    aq = quantize(a.astype(jnp.float32), fp)
    bq = quantize(bx.astype(jnp.float32), fp)
    h = jnp.zeros((B, W), jnp.float32)
    hs = []
    for t in range(T):
        h = quantize(aq[:, t] * h + bq[:, t], fp)
        hs.append(h)
    return jnp.stack(hs, axis=1).astype(a.dtype)


def rglru_scan(a, bx, *, schedule: Optional[KernelSchedule] = None,
               block_batch: Optional[int] = None, block_width: int = 128,
               fp: Optional[FixedPointConfig] = None):
    """a, bx: [B, T, W] -> all recurrence states [B, T, W].

    Reuse for this matmul-free kernel serializes the width tiles: per
    sequential step one W/R-wide tile of VPU lanes is live.

    ``hoist_input`` is accepted as a no-op: the RG-LRU kernel consumes a
    PRECOMPUTED gated input bx (the caller's dense gates are the hoist
    stage), i.e. the kernel is already in hoisted form — only the
    elementwise a_t * h recurrence is sequential.  Pipeline mode unrolls
    one block per timestep like nonstatic (slim elementwise blocks).

    ``fp`` as in :func:`lstm_scan`: integral configs run the all-integer
    recurrence (kernels/quantized.py), others the f32 emulation.
    """
    schedule = _resolve(schedule, block_batch, default_bb=8)
    if fp is not None:
        if is_native_int(fp) and schedule.use_pallas:
            from repro.kernels.quantized import quantized_rglru_scan

            return quantized_rglru_scan(a, bx, fp=fp, schedule=schedule)
        return _rglru_emulated_jit(a, bx, fp=fp)
    return _rglru_scan_jit(a, bx, schedule=schedule,
                           block_width=block_width)


@partial(jax.jit, static_argnames=("schedule", "block_width"))
def _rglru_scan_jit(a, bx, *, schedule: KernelSchedule,
                    block_width: int = 128):
    B, T, W = a.shape
    if not schedule.use_pallas:
        return ref.rglru_scan_ref(a, bx)
    if schedule.mode in ("nonstatic", "pipeline"):
        h = jnp.zeros((B, W), jnp.float32)
        hs = []
        for t in range(T):                 # one block per timestep
            h = a[:, t].astype(jnp.float32) * h + bx[:, t].astype(jnp.float32)
            hs.append(h)
        return jnp.stack(hs, axis=1).astype(a.dtype)
    reuse = schedule.reuse_factor
    bb = min(schedule.block_batch, max(1, B))
    bw = min(block_width, -(-W // reuse))  # ceil: R sequential width tiles
    check_tpu_alignment(schedule, tile_width=bw, full_width=W,
                        block_batch=bb, kernel="rglru_scan")
    a_p = _pad_axis(_pad_axis(a, 0, bb), 2, bw)
    b_p = _pad_axis(_pad_axis(bx, 0, bb), 2, bw)
    out = rglru_scan_pallas(a_p, b_p, block_batch=bb, block_width=bw,
                            serial_width=reuse > 1,
                            interpret=schedule.interpret)
    return out[:B, :, :W]


def reuse_matmul(x, w, *, reuse: int = 1, block_m: int = 128,
                 schedule: Optional[KernelSchedule] = None,
                 fp: Optional[FixedPointConfig] = None):
    """[M, K] @ [K, N] with K serialized into `reuse` passes (a schedule's
    reuse_factor overrides the bare ``reuse`` argument).

    ``fp``: integral configs on a Pallas schedule run the int8/int4
    column-tiled kernel (z = q(q(x) @ q(w)) with int32 accumulation);
    other fp configs emulate the same quantization points in f32.
    """
    if fp is not None:
        if (is_native_int(fp) and schedule is not None
                and schedule.use_pallas):
            from repro.kernels.quantized import quantized_reuse_matmul

            return quantized_reuse_matmul(x, w, fp=fp, schedule=schedule)
        from repro.core.quant.fixed_point import quantize

        xq = quantize(x.astype(jnp.float32), fp)
        wq = quantize(w.astype(jnp.float32), fp)
        out = _reuse_matmul_jit(xq, wq, reuse=reuse, block_m=block_m,
                                schedule=schedule)
        return quantize(out, fp).astype(x.dtype)
    return _reuse_matmul_jit(x, w, reuse=reuse, block_m=block_m,
                             schedule=schedule)


@partial(jax.jit, static_argnames=("reuse", "block_m", "schedule"))
def _reuse_matmul_jit(x, w, *, reuse: int = 1, block_m: int = 128,
                      schedule: Optional[KernelSchedule] = None):
    if schedule is not None:
        if not schedule.use_pallas:
            return ref.reuse_matmul_ref(x, w)
        reuse = schedule.effective_reuse(x.shape[1])
        interpret = schedule.interpret
        check_tpu_alignment(schedule, tile_width=x.shape[1] // reuse,
                            full_width=x.shape[1],
                            block_batch=min(block_m, max(8, x.shape[0])),
                            kernel="reuse_matmul")
    else:
        interpret = resolve_interpret()
    M, K = x.shape
    bm = min(block_m, max(8, M))
    x_p = _pad_axis(x, 0, bm)
    out = reuse_matmul_pallas(x_p, w, reuse=reuse, block_m=bm,
                              interpret=interpret)
    return out[:M]


# kernel name -> (scheduled entry point, golden reference) — the conformance
# harness and benchmarks enumerate this
SCHEDULED_KERNELS = {
    "lstm": (lstm_scan, ref.lstm_scan_ref),
    "gru": (gru_scan, ref.gru_scan_ref),
    "rglru": (rglru_scan, ref.rglru_scan_ref),
    "reuse_matmul": (reuse_matmul, ref.reuse_matmul_ref),
}
