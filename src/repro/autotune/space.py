"""Legal KernelSchedule space enumeration — the paper's hand-built sweep
grid, generated and pruned mechanically.

The axes are exactly ``KernelSchedule``'s: reuse factor x mode x hoist x
hoist_reuse x ii x block_batch x backend.  Legality pruning applies the same
rules the kernels enforce at dispatch:

  * reuse factors must divide the gate dimension ``G x hidden`` (the kernels
    clamp non-divisors via ``effective_reuse`` — enumerating them would only
    alias already-enumerated points under a different name);
  * ``hoist_reuse > 1`` requires the hoist; pipeline mode implies it
    (``KernelSchedule.__post_init__``); ``ii`` is a pipeline-only axis;
  * points that run compiled Pallas (``"pallas_tpu"``, or ``"auto"`` on a
    TPU) must pass ``ops.check_tpu_alignment`` (whole-width or 128-lane
    column tiles, 8-sublane batch tiles) — misaligned points are pruned,
    not clamped, because they would raise at dispatch;
  * duplicates (same ``schedule.key()``) collapse to one point.

The result is deterministic (sorted by key) so Pareto frontiers and selected
schedules are reproducible across runs.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Iterator, Optional, Tuple

from repro.config import ModelConfig
from repro.core.hls.resources import gate_count
from repro.kernels.schedule import MODES, KernelSchedule


def divisors(n: int) -> Tuple[int, ...]:
    """All divisors of n, ascending — the legal reuse factors of a gate
    dimension (hls4ml restricts R the same way)."""
    small, large = [], []
    d = 1
    while d * d <= n:
        if n % d == 0:
            small.append(d)
            if d != n // d:
                large.append(n // d)
        d += 1
    return tuple(small + large[::-1])


@dataclass(frozen=True)
class SpaceSpec:
    """Which slice of the schedule space to enumerate.

    ``reuse_factors=None`` means every divisor of the gate dimension — the
    full hls4ml-legal R axis.  The defaults describe one block_batch on the
    ``"auto"`` backend (compiled on a TPU, interpreted on the CPU); points
    that resolve to compiled Pallas are alignment-pruned automatically.
    """

    reuse_factors: Optional[Tuple[int, ...]] = None
    modes: Tuple[str, ...] = MODES
    hoist: Tuple[bool, ...] = (False, True)
    hoist_reuses: Tuple[int, ...] = (1,)
    iis: Tuple[int, ...] = (0,)
    block_batches: Tuple[int, ...] = (8,)
    backends: Tuple[str, ...] = ("auto",)
    max_points: int = 4096

    def __post_init__(self):
        for m in self.modes:
            if m not in MODES:
                raise ValueError(f"mode {m!r} not in {MODES}")


def _tpu_aligned(schedule: KernelSchedule, gate_dim: int) -> bool:
    """True when the schedule passes the Mosaic alignment rules wherever it
    resolves to compiled Pallas (interpreted and XLA points are
    unconstrained)."""
    import math

    from repro.kernels.ops import check_tpu_alignment
    try:
        r = schedule.effective_reuse(gate_dim)
        check_tpu_alignment(schedule, tile_width=gate_dim // r,
                            full_width=gate_dim,
                            block_batch=schedule.block_batch, kernel="space")
        if schedule.hoist_reuse > 1:
            hr = math.gcd(schedule.hoist_reuse, gate_dim)
            check_tpu_alignment(schedule, tile_width=gate_dim // hr,
                                full_width=gate_dim,
                                block_batch=schedule.block_batch,
                                kernel="space")
    except ValueError:
        return False
    return True


def _raw_points(gate_dim: int, spec: SpaceSpec) -> Iterator[KernelSchedule]:
    rfs = spec.reuse_factors if spec.reuse_factors is not None \
        else divisors(gate_dim)
    for backend in spec.backends:
        for bb in spec.block_batches:
            for r in rfs:
                if gate_dim % r != 0:
                    continue            # aliases the gcd point — prune
                for mode in spec.modes:
                    base = dict(reuse_factor=r, mode=mode, block_batch=bb,
                                backend=backend)
                    if mode == "pipeline":
                        # hoist is implied; ii and hoist_reuse are live axes
                        for ii in spec.iis:
                            for hr in spec.hoist_reuses:
                                if hr > 1 and gate_dim % hr != 0:
                                    continue
                                yield KernelSchedule(ii=ii, hoist_reuse=hr,
                                                     **base)
                        continue
                    for hoist in spec.hoist:
                        if not hoist:
                            yield KernelSchedule(**base)
                            continue
                        for hr in spec.hoist_reuses:
                            if hr > 1 and gate_dim % hr != 0:
                                continue
                            yield KernelSchedule(hoist_input=True,
                                                 hoist_reuse=hr, **base)


def enumerate_space(cfg: ModelConfig,
                    spec: Optional[SpaceSpec] = None
                    ) -> Tuple[KernelSchedule, ...]:
    """The legal, deduplicated, deterministic schedule space for one model."""
    assert cfg.rnn is not None, "the schedule space is an RNN-family concept"
    spec = spec or SpaceSpec()
    gate_dim = gate_count(cfg.rnn.cell) * cfg.rnn.hidden
    seen = {}
    for s in _raw_points(gate_dim, spec):
        if not _tpu_aligned(s, gate_dim):
            continue
        seen.setdefault(s.key(), s)
        if len(seen) >= spec.max_points:
            break
    return tuple(seen[k] for k in sorted(seen))


# ---------------------------------------------------------------------------
# Decode-legal slice (the single-step kernels of kernels/decode_step.py)
# ---------------------------------------------------------------------------


def decode_legal(schedule: KernelSchedule) -> bool:
    """True when the single-step decode kernels can execute ``schedule``.

    A decode step has no time axis, so the scan-only degrees of freedom are
    illegal: mode must be ``"static"`` (ONE weights-resident block serves
    the step; non-static/pipeline describe per-timestep block chains that
    do not exist here), and the hoist axes (``hoist_input``,
    ``hoist_reuse``) and pipeline ``ii`` must be off — there is no input
    projection to hoist out of a single step.  The reuse factor and
    backend axes carry over unchanged.
    """
    return (schedule.mode == "static" and not schedule.hoist_input
            and schedule.hoist_reuse == 1 and schedule.ii == 0)


def native_int_legal(schedule: KernelSchedule) -> bool:
    """True when the NATIVE int8/int4 kernel bodies can execute
    ``schedule``.

    Quantized datapaths never hoist — splitting z = q(xW + hU + b) into a
    precomputed zx plus an in-loop hU would move the hls4ml quantization
    points — so ``hoist_input``/``hoist_reuse`` and pipeline mode (which
    implies the hoist) are illegal, as is a pipeline ``ii``.  Reuse factor,
    mode static/nonstatic, block_batch and backend carry over: the native
    scan runs the same per-timestep structure either way, with R column
    tiles per gate matmul.
    """
    return (not schedule.hoist_input and schedule.mode != "pipeline"
            and schedule.hoist_reuse == 1 and schedule.ii == 0)


def enumerate_decode_space(cfg: ModelConfig,
                           spec: Optional[SpaceSpec] = None
                           ) -> Tuple[KernelSchedule, ...]:
    """The decode-legal slice of the schedule space (deduped, sorted) —
    what ``autotune.select_decode`` and the decode estimators price."""
    return tuple(s for s in enumerate_space(cfg, spec) if decode_legal(s))


# ---------------------------------------------------------------------------
# Speculative slice: legal (draft, verify, K) triples over the decode space
# ---------------------------------------------------------------------------


def lm_decode_schedules(cfg: ModelConfig,
                        spec: Optional[SpaceSpec] = None
                        ) -> Tuple[KernelSchedule, ...]:
    """The decode-legal schedule slice for a DENSE-stack LM config — the
    reuse factors are divisors of the gcd of the scheduled step's fused
    matmul output widths (q|k|v, attn out, MLP in, MLP down), so every
    enumerated R is what ``effective_reuse`` resolves on EVERY matmul in
    the chain: the point priced is the point executed, chain-wide.
    """
    import math

    spec = spec or SpaceSpec()
    d, f = cfg.d_model, cfg.d_ff
    hq, hk, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    glu = cfg.mlp_type in ("swiglu", "geglu")
    widths = [(hq + 2 * hk) * hd, d, 2 * f if glu else f, d]
    g = 0
    for w in widths:
        g = math.gcd(g, w)
    rfs = spec.reuse_factors if spec.reuse_factors is not None \
        else divisors(g)
    seen = {}
    for backend in spec.backends:
        for bb in spec.block_batches:
            for r in rfs:
                if g % r != 0:
                    continue
                s = KernelSchedule(reuse_factor=r, mode="static",
                                   block_batch=bb, backend=backend)
                if not _tpu_aligned(s, g):
                    continue
                seen.setdefault(s.key(), s)
                if len(seen) >= spec.max_points:
                    break
    return tuple(seen[k] for k in sorted(seen))


def speculative_draft_legal(draft: Optional[KernelSchedule],
                            verify: KernelSchedule) -> bool:
    """True when ``draft`` may propose tokens for ``verify`` to check.

    ``None`` (the n-gram CacheTable) is always legal — free drafts cost
    nothing to be wrong.  A model draft must itself be decode-legal
    (it runs the same single-step kernels) and STRICTLY cheaper than the
    verify schedule — reuse_factor strictly higher, the cheap side of the
    paper's R asymmetry.  Equal-or-denser drafts would pay more per draft
    than verification recovers; they are pruned, not penalized.
    """
    if draft is None:
        return True
    return (decode_legal(draft)
            and draft.reuse_factor > verify.reuse_factor)


def enumerate_speculative_space(cfg: ModelConfig,
                                spec: Optional[SpaceSpec] = None, *,
                                ks: Tuple[int, ...] = (1, 2, 4, 8),
                                include_ngram: bool = True
                                ) -> Tuple[Tuple[Optional[KernelSchedule],
                                                 KernelSchedule, int], ...]:
    """Every legal (draft, verify, K) triple: verify ranges over the
    decode-legal slice (RNN families via ``enumerate_decode_space``,
    dense stacks via ``lm_decode_schedules``), drafts over the same slice
    restricted by ``speculative_draft_legal`` plus the free n-gram draft
    (``None``) when ``include_ngram``.  Deterministic order: sorted by
    (verify key, draft key or '', K)."""
    if cfg.rnn is not None:
        pool = enumerate_decode_space(cfg, spec)
    else:
        pool = lm_decode_schedules(cfg, spec)
    triples = []
    for verify in pool:
        drafts: Tuple[Optional[KernelSchedule], ...] = tuple(
            d for d in pool if speculative_draft_legal(d, verify))
        if include_ngram:
            drafts = (None,) + drafts
        for draft in drafts:
            for k in ks:
                if k < 1:
                    continue        # K=0 is "speculation off", not a point
                triples.append((draft, verify, k))
    triples.sort(key=lambda t: (t[1].key(),
                                "" if t[0] is None else t[0].key(), t[2]))
    return tuple(triples)
