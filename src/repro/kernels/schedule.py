"""Reuse-factor scheduling layer — ONE object that configures every scan
kernel AND the analytical HLS estimators.

The paper's central knob is the hls4ml reuse factor: with reuse R each DSP
performs R multiplications per matrix product, so DSPs shrink by R while
latency grows by R (Tables 2-4), and the static / non-static mode choice
trades initiation interval against resource replication (Table 5, Fig. 6).
``KernelSchedule`` carries exactly those degrees of freedom plus the TPU
execution backend, and is:

  * hashable / frozen — usable as a ``jax.jit`` static argument;
  * honored by the Pallas kernels: gate matmuls are partitioned into
    ``reuse_factor`` *sequential column tiles* (one extra sequential grid
    dimension), so the kernel's sequential grid length really is
    ``sequential_steps(seq_len)``;
  * the input to ``core.hls.resources.estimate_schedule`` — latency-cycle
    and DSP/BRAM estimates are derived from the same object the kernel
    executes, which is what makes the software sweep of the paper's Fig. 1
    latency–resource curve trustworthy.

Dependency note: this module imports nothing from ``repro`` so that
``repro.config`` can embed schedules in frozen model configs.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, replace
from typing import Iterable, Tuple

import jax

MODES = ("static", "nonstatic", "pipeline")
BACKENDS = ("auto", "xla", "pallas_interpret", "pallas_tpu")

#: queue key for requests that carry no schedule at all
DEFAULT_SCHEDULE_KEY = "default"


def resolve_interpret(backend: str = "auto") -> bool:
    """Whether a Pallas kernel on ``backend`` runs in the interpreter.

    The explicit backends decide for themselves; ``"auto"`` follows the
    platform JAX traces for (read at trace time): compiled Mosaic on
    ``tpu``, the interpreter on ``cpu``.  Any other platform raises — a
    kernel never quietly drops to the interpreter on an accelerator.
    """
    if backend == "pallas_interpret":
        return True
    if backend == "pallas_tpu":
        return False
    if backend != "auto":
        raise ValueError(f"backend {backend!r} runs no Pallas kernel")
    platform = jax.default_backend()
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise RuntimeError(
        f"backend='auto' has no Pallas mode for platform {platform!r}: "
        f"run on tpu (compiled) or cpu (interpreted), or name the backend")


@dataclass(frozen=True)
class KernelSchedule:
    """How a scan kernel is scheduled on the latency–resource curve.

    reuse_factor  hls4ml reuse R: gate matmuls run as R sequential column
                  tiles; latency x R, parallel multipliers (DSP analogue,
                  VMEM-resident weight tile on TPU) / R.
    mode          "static" — one weights-resident block scans the whole
                  sequence (paper Fig. 1 left, II = seq_len x R).
                  "nonstatic" — one block per timestep, state flows
                  block-to-block (Fig. 1 right, II = one block latency).
                  "pipeline" — NONSTATIC with the input projection hoisted
                  out of every block (implies ``hoist_input``): the
                  per-timestep blocks carry only the hU recurrence, so the
                  cross-inference initiation interval can shrink to ``ii``
                  sequential steps (paper Table 5's II 315 -> 1, with the
                  xW GEMM as a separate fully-pipelined front stage).
    block_batch   batch tile per kernel invocation (TPU sublane analogue of
                  the paper's "independent inferences in flight").
    backend       "auto" (Pallas, compiled on a TPU and interpreted on the
                  CPU: ``resolve_interpret``), "pallas_interpret",
                  "pallas_tpu", or "xla" (the lax.scan golden reference).
    hoist_input   compute the input projection xW for ALL timesteps as ONE
                  batched [B*T, fin] @ [fin, G*h] matmul outside the
                  sequential scan (only hU carries the recurrence): the
                  sequential working set drops from (fin+h) x G*h/R to
                  h x G*h/R and the per-step FLOPs roughly halve for
                  fin ~ h.  Bit-identical to the in-loop path (same
                  association order; conformance-enforced).
    ii            pipeline mode only: target initiation interval in
                  sequential steps before the NEXT inference enters the
                  block chain (0 = auto = reuse_factor, one block's column
                  tiles).  Per-inference latency keeps the irreducible
                  seq_len x R recurrence chain; ii is the throughput axis.
    hoist_reuse   reuse factor of the hoisted input GEMM itself (1 = fully
                  parallel, full MXU utilization; >1 runs it as R-tiled
                  sequential column passes — trades the front stage's
                  resources the same way reuse_factor trades the scan's).
    """

    reuse_factor: int = 1
    mode: str = "static"
    block_batch: int = 128
    backend: str = "auto"
    hoist_input: bool = False
    ii: int = 0
    hoist_reuse: int = 1

    def __post_init__(self):
        if self.reuse_factor < 1:
            raise ValueError(f"reuse_factor must be >= 1: {self.reuse_factor}")
        if self.mode not in MODES:
            raise ValueError(f"mode {self.mode!r} not in {MODES}")
        if self.backend not in BACKENDS:
            raise ValueError(f"backend {self.backend!r} not in {BACKENDS}")
        if self.block_batch < 1:
            raise ValueError(f"block_batch must be >= 1: {self.block_batch}")
        if self.ii < 0:
            raise ValueError(f"ii must be >= 0: {self.ii}")
        if self.hoist_reuse < 1:
            raise ValueError(f"hoist_reuse must be >= 1: {self.hoist_reuse}")
        if self.mode == "pipeline":
            # pipelining the block chain REQUIRES the hoist: only once the
            # xW GEMM leaves the blocks is a block slim enough to free up
            # after its hU tiles, letting the next inference enter at ii
            object.__setattr__(self, "hoist_input", True)
        elif self.ii:
            # ii is a pipeline-mode knob; normalize it away on other modes
            # (instead of raising) so replace(mode=...) — the engine's and
            # rnn_layer's mode-override path — stays total, and the
            # normalized schedule keys/hashes equal the ii-free one
            object.__setattr__(self, "ii", 0)
        if self.hoist_reuse > 1 and not self.hoist_input:
            raise ValueError(
                "hoist_reuse > 1 without hoist_input: there is no hoisted "
                "input GEMM to tile")

    # -- backend resolution -------------------------------------------------

    @property
    def use_pallas(self) -> bool:
        return self.backend != "xla"

    @property
    def interpret(self) -> bool:
        return resolve_interpret(self.backend)

    # -- reuse partitioning -------------------------------------------------

    def effective_reuse(self, dim: int) -> int:
        """Largest divisor of ``dim`` that also divides ``reuse_factor``.

        Column tiles must align with the gate layout (i|f|c|o packed along
        the last axis), so the tiled dimension has to split evenly; ragged
        reuse requests degrade gracefully to the nearest feasible divisor
        instead of erroring (same behavior hls4ml applies to invalid R).
        """
        return math.gcd(self.reuse_factor, dim)

    def sequential_steps(self, seq_len: int) -> int:
        """Sequential kernel grid length — the software latency axis.

        Static: one block serializes time x reuse.  Non-static/pipeline: the
        chain of seq_len blocks still costs seq_len x R end-to-end for one
        inference (each block serializes its R column tiles).  Hoisting does
        NOT change the step count — it shrinks each step's working set and
        FLOPs (the xW half leaves the recurrence).
        """
        return seq_len * self.reuse_factor

    def initiation_interval(self, seq_len: int) -> int:
        """Sequential steps before the NEXT inference can enter (paper II).

        Static re-uses the single block for the whole sequence; non-static
        frees its first block after one block latency (II 315 -> 1 in
        Table 5 terms, scaled by R); pipeline reaches the explicit ``ii``
        target (default one block's R tiles) because the hoisted blocks
        carry only the hU tiles.
        """
        if self.mode == "static":
            return seq_len * self.reuse_factor
        if self.mode == "pipeline":
            return max(self.ii or self.reuse_factor, 1)
        return self.reuse_factor

    # -- stable identity ----------------------------------------------------

    def key(self) -> str:
        """Stable, human-readable hash of the schedule — the co-batching key.

        Two requests with equal keys compile to the SAME kernel (identical
        jit trace), so the serving layer batches them together; the string is
        stable across processes (unlike ``hash()``) and shows up verbatim in
        latency reports and benchmark CSV rows.

        Non-default axes append as suffix tokens (``-hoist``, ``-hrN``,
        ``-iiN``) so default schedules keep their PR 2-era keys and old
        parsers that read only the first four tokens stay correct.
        """
        base = (f"{self.mode}-R{self.reuse_factor}"
                f"-bb{self.block_batch}-{self.backend}")
        if self.hoist_input:
            base += "-hoist"
        if self.hoist_reuse != 1:
            base += f"-hr{self.hoist_reuse}"
        if self.ii:
            base += f"-ii{self.ii}"
        return base

    # -- sweeping -----------------------------------------------------------

    def replace(self, **kw) -> "KernelSchedule":
        return replace(self, **kw)

    @classmethod
    def from_key(cls, key: str) -> "KernelSchedule":
        """Inverse of :meth:`key`; also accepts the fp-suffixed form
        ``schedule_key`` produces (the ``-apW_I_rnd_sat`` tail is ignored).
        Round-trips every valid schedule.

        Forward/backward compatible by construction: the first four tokens
        are positional and REQUIRED (a malformed core raises ValueError);
        every later token is an optional axis — known ones (``hoist``,
        ``hrN``, ``iiN``) parse, unknown ones (axes from a future PR, the
        fp tail) are ignored, so PR 2-era keys still parse after new axes
        land and vice versa.
        """
        parts = key.split("-")
        if len(parts) < 4:
            raise ValueError(f"not a schedule key: {key!r}")
        mode, r, bb, backend = parts[:4]
        if not (r.startswith("R") and r[1:].isdigit()
                and bb.startswith("bb") and bb[2:].isdigit()):
            raise ValueError(f"not a schedule key: {key!r}")
        kw = dict(reuse_factor=int(r[1:]), mode=mode,
                  block_batch=int(bb[2:]), backend=backend)
        for tok in parts[4:]:
            if tok == "hoist":
                kw["hoist_input"] = True
            elif tok.startswith("hr") and tok[2:].isdigit():
                kw["hoist_reuse"] = int(tok[2:])
            elif tok.startswith("ii") and tok[2:].isdigit():
                kw["ii"] = int(tok[2:])
            # anything else: an axis this build does not know (or the
            # schedule_key fp tail) — ignore, do not crash the parser
        return cls(**kw)

    @classmethod
    def sweep(cls, reuse_factors: Iterable[int] = (1, 2, 4, 8),
              modes: Iterable[str] = MODES, *, block_batch: int = 128,
              backend: str = "auto") -> Tuple["KernelSchedule", ...]:
        """The paper's Fig. 1 sweep grid as schedule objects."""
        return tuple(cls(reuse_factor=r, mode=m, block_batch=block_batch,
                         backend=backend)
                     for m in modes for r in reuse_factors)


def cache_meta(schedule: "KernelSchedule | None", fp=None) -> dict:
    """Exhaustive (schedule, fp) identity for the persistent compile cache.

    ``schedule_key`` is the co-batching string and stays forward-compatible
    by IGNORING axes it does not know — the right property for routing, the
    wrong one for naming a serialized executable (two schedules that differ
    in a future axis must never share an artifact).  This derivation is
    exhaustive by construction: every dataclass field of the schedule and
    the fixed-point config lands in the dict, so adding an axis
    automatically invalidates stale cache entries.
    """
    from dataclasses import asdict, is_dataclass

    meta: dict = {"schedule": (None if schedule is None
                               else asdict(schedule))}
    if fp is None:
        meta["fp"] = None
    elif is_dataclass(fp):
        meta["fp"] = asdict(fp)
    else:  # duck-typed fp (no-repro-imports invariant): fall back to repr
        meta["fp"] = repr(fp)
    return meta


def schedule_key(schedule: "KernelSchedule | None", fp=None) -> str:
    """Stable co-batching key for a (schedule, fixed-point config) pair.

    Requests whose key matches execute the same compiled kernel: the same
    column-tile partitioning, mode, backend AND datapath precision.  ``fp``
    is duck-typed (anything with ``total_bits`` / ``integer_bits``) so this
    module keeps its no-repro-imports invariant; ``None`` fp means the float
    datapath.
    """
    base = DEFAULT_SCHEDULE_KEY if schedule is None else schedule.key()
    if fp is None:
        return base
    rounding = getattr(fp, "rounding", "rnd")
    saturation = getattr(fp, "saturation", "sat")
    return (f"{base}-ap{fp.total_bits}_{fp.integer_bits}"
            f"_{rounding}_{saturation}")
