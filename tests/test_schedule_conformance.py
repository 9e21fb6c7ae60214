"""Golden-model conformance for the reuse-factor scheduling layer.

Every (kernel x mode x reuse_factor x dtype) cell must match the XLA
``lax.scan`` reference within dtype tolerance, and the HLS estimates must be
computed from the SAME schedule object the kernel executes, with the paper's
monotone trade-off: latency rises and DSP falls as reuse_factor grows.

Hoisted-input cells additionally must be BIT-IDENTICAL to their in-loop
counterpart at the same (mode, R, dtype): hoisting only moves the xW half of
(xW + hU) + b outside the scan without changing the association order.
"""

import jax
import numpy as np
import pytest

from repro.core.hls.resources import estimate_schedule
from repro.kernels.schedule import BACKENDS, MODES, KernelSchedule
from repro.registry import get_config
from repro.testing import (assert_schedule_conformance,
                           make_kernel_inputs)

REUSE_FACTORS = (1, 2, 4, 8)
CELLS = ("lstm", "gru")
#: modes with a hoisted/in-loop PAIR (pipeline forces hoist_input, so its
#: in-loop counterpart is the nonstatic schedule, covered separately)
PAIRED_MODES = ("static", "nonstatic")


def _sched(reuse, mode, block_batch=8, **kw):
    return KernelSchedule(reuse_factor=reuse, mode=mode,
                          block_batch=block_batch,
                          backend="pallas_interpret", **kw)


def _assert_hoisted_bitmatch(kernel, sched, *, dtype="float32", seed=0,
                             **shape_kw):
    """Hoisted output must equal the in-loop output bit-for-bit."""
    from repro.kernels import ops

    scheduled, _ = ops.SCHEDULED_KERNELS[kernel]
    inputs = make_kernel_inputs(kernel, dtype=dtype, seed=seed, **shape_kw)
    hoisted = np.asarray(
        scheduled(*inputs, schedule=sched.replace(hoist_input=True)),
        np.float32)
    in_loop = np.asarray(scheduled(*inputs, schedule=sched), np.float32)
    np.testing.assert_array_equal(
        hoisted, in_loop,
        err_msg=f"hoisted != in-loop for {kernel} under {sched.key()} "
                f"(dtype={dtype}, shapes={shape_kw})")


# ---------------------------------------------------------------------------
# The acceptance sweep: {lstm, gru} x {static, nonstatic} x {1, 2, 4, 8}
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("reuse", REUSE_FACTORS)
@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("cell", CELLS)
def test_cell_schedule_conformance(cell, mode, reuse):
    assert_schedule_conformance(cell, _sched(reuse, mode),
                                B=4, T=10, F=6, H=20, seed=reuse)


@pytest.mark.parametrize("reuse", (1, 4))
@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("cell", CELLS)
def test_cell_schedule_conformance_bf16(cell, mode, reuse):
    assert_schedule_conformance(cell, _sched(reuse, mode), dtype="bfloat16",
                                B=4, T=8, F=6, H=20, seed=3)


@pytest.mark.parametrize("reuse", REUSE_FACTORS)
@pytest.mark.parametrize("mode", MODES)
def test_rglru_schedule_conformance(mode, reuse):
    assert_schedule_conformance("rglru", _sched(reuse, mode),
                                B=3, T=9, H=128, seed=reuse)


@pytest.mark.parametrize("reuse", REUSE_FACTORS + (16,))
def test_reuse_matmul_schedule_conformance(reuse):
    assert_schedule_conformance("reuse_matmul", _sched(reuse, "static"),
                                M=33, K=64, N=48, seed=reuse)


def test_xla_backend_is_the_golden_model():
    """backend='xla' must be exactly the reference (error 0 by identity)."""
    s = KernelSchedule(backend="xla")
    for cell in CELLS:
        err = assert_schedule_conformance(cell, s, B=3, T=7, F=4, H=12)
        assert err == 0.0


# ---------------------------------------------------------------------------
# Edge shapes through the scheduling layer: ragged batch, T=1, off-lane H
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("B", (1, 3, 9))          # not multiples of 8
@pytest.mark.parametrize("cell", CELLS)
def test_ragged_batch(cell, B):
    assert_schedule_conformance(cell, _sched(2, "static"),
                                B=B, T=6, F=5, H=20, seed=B)


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("cell", CELLS)
def test_single_timestep(cell, mode):
    assert_schedule_conformance(cell, _sched(4, mode), B=4, T=1, F=6, H=20)


@pytest.mark.parametrize("H", (20, 100, 130))     # off the 128-lane boundary
@pytest.mark.parametrize("cell", CELLS)
def test_off_lane_hidden(cell, H):
    assert_schedule_conformance(cell, _sched(4, "static"),
                                B=4, T=5, F=6, H=H, seed=H)


def test_ragged_reuse_degrades_to_divisor():
    """4h=52 is not divisible by 8: effective reuse falls back to gcd."""
    s = _sched(8, "static")
    assert s.effective_reuse(4 * 13) == 4
    assert_schedule_conformance("lstm", s, B=2, T=4, F=3, H=13)


def test_rglru_ragged_width():
    assert_schedule_conformance("rglru", _sched(4, "static"),
                                B=5, T=7, H=200)


# ---------------------------------------------------------------------------
# Hoisted input projection: bit-identical to the in-loop path for every
# (kernel x mode x R x dtype) pair, plus the pipeline (NONSTATIC) mode
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("reuse", REUSE_FACTORS)
@pytest.mark.parametrize("mode", PAIRED_MODES)
@pytest.mark.parametrize("cell", CELLS)
def test_hoisted_bitmatch(cell, mode, reuse):
    _assert_hoisted_bitmatch(cell, _sched(reuse, mode),
                             B=4, T=10, F=6, H=20, seed=reuse)
    # and the hoisted cell still conforms to the golden model
    assert_schedule_conformance(cell, _sched(reuse, mode, hoist_input=True),
                                B=4, T=10, F=6, H=20, seed=reuse)


@pytest.mark.parametrize("reuse", (1, 4))
@pytest.mark.parametrize("mode", PAIRED_MODES)
@pytest.mark.parametrize("cell", CELLS)
def test_hoisted_bitmatch_bf16(cell, mode, reuse):
    _assert_hoisted_bitmatch(cell, _sched(reuse, mode), dtype="bfloat16",
                             B=4, T=8, F=6, H=20, seed=3)


@pytest.mark.parametrize("B", (1, 3, 9))          # not multiples of 8
@pytest.mark.parametrize("cell", CELLS)
def test_hoisted_ragged_batch(cell, B):
    _assert_hoisted_bitmatch(cell, _sched(2, "static"),
                             B=B, T=6, F=5, H=20, seed=B)


@pytest.mark.parametrize("mode", PAIRED_MODES)
@pytest.mark.parametrize("cell", CELLS)
def test_hoisted_single_timestep(cell, mode):
    _assert_hoisted_bitmatch(cell, _sched(4, mode), B=4, T=1, F=6, H=20)


@pytest.mark.parametrize("H", (20, 100, 130))     # off the 128-lane boundary
@pytest.mark.parametrize("cell", CELLS)
def test_hoisted_off_lane_hidden(cell, H):
    _assert_hoisted_bitmatch(cell, _sched(4, "static"),
                             B=4, T=5, F=6, H=H, seed=H)


def test_hoisted_fin_approx_h():
    """The hoist's target regime (per-step FLOPs halve when fin ~ h)."""
    for cell in CELLS:
        _assert_hoisted_bitmatch(cell, _sched(4, "static"),
                                 B=9, T=6, F=24, H=24)


@pytest.mark.parametrize("reuse", REUSE_FACTORS)
@pytest.mark.parametrize("cell", CELLS)
def test_pipeline_conformance(cell, reuse):
    """Pipeline mode (fused hoisted NONSTATIC kernel) conforms to the
    golden model for every R, including the hr-tiled hoist stage."""
    assert_schedule_conformance(cell, _sched(reuse, "pipeline"),
                                B=4, T=10, F=6, H=20, seed=reuse)
    assert_schedule_conformance(
        cell, _sched(reuse, "pipeline", hoist_reuse=4),
        B=4, T=10, F=6, H=20, seed=reuse)


@pytest.mark.parametrize("cell", CELLS)
def test_pipeline_edge_shapes(cell):
    assert_schedule_conformance(cell, _sched(4, "pipeline"),
                                B=3, T=1, F=5, H=20)
    assert_schedule_conformance(cell, _sched(4, "pipeline"),
                                B=9, T=6, F=5, H=130)
    assert_schedule_conformance(cell, _sched(4, "pipeline"),
                                dtype="bfloat16", B=4, T=8, F=6, H=20)


def test_hoisted_xla_layer_preserves_dtype():
    """The hoisted XLA path must keep the in-loop carry dtype (a f32 zx on
    a bfloat16 scan used to crash lax.scan's carry type check) and stay
    close to the in-loop result in both static and unrolled modes."""
    import jax.numpy as jnp

    from repro.core.rnn.layer import rnn_layer
    from repro.registry import get_config

    rnn = get_config("top-tagging-lstm").rnn
    for dtype in ("float32", "bfloat16"):
        xs, W, U, b = make_kernel_inputs("lstm", B=4, T=rnn.seq_len,
                                         F=rnn.input_size, H=rnn.hidden,
                                         dtype=dtype)
        for mode in ("static", "nonstatic", "pipeline"):
            s = KernelSchedule(mode=mode, hoist_input=True, backend="xla")
            out = rnn_layer(rnn, xs, W, U, b, impl="xla", schedule=s)
            assert out.dtype == jnp.dtype(dtype), (mode, dtype)
            ref = rnn_layer(rnn, xs, W, U, b, impl="xla",
                            schedule=KernelSchedule(mode=mode,
                                                    backend="xla"))
            tol = 3e-5 if dtype == "float32" else 2e-2
            assert float(jnp.max(jnp.abs(
                out.astype(jnp.float32) - ref.astype(jnp.float32)))) <= tol


def test_engine_mode_override_survives_pipeline_ii_request():
    """An engine pinned to another mode replaces the mode on an incoming
    pipeline(ii=...) schedule — the ii knob must normalize away instead of
    raising (the serving mode-override path)."""
    s = KernelSchedule(mode="pipeline", ii=1, reuse_factor=4)
    assert s.replace(mode="static").ii == 0
    assert s.replace(mode="static").key().count("ii") == 0


def test_hoist_stage_tpu_alignment_checked():
    """The hoist stage's own column tiles are validated for pallas_tpu —
    a misaligned hoist_reuse tile must raise, not miscompile."""
    from repro.kernels import ops

    xs, W, U, b = make_kernel_inputs("gru", B=8, T=4, F=6, H=128)
    # 3h = 384 is 128-aligned at R=1, but hoist tiles of 384/4 = 96 are not
    bad = KernelSchedule(mode="pipeline", hoist_reuse=4, backend="pallas_tpu",
                         block_batch=8)
    with pytest.raises(ValueError, match="hoist_stage"):
        ops.gru_scan(xs, W, U, b, schedule=bad)


def test_rglru_hoist_is_noop():
    """The RG-LRU kernel is already in hoisted form (bx is a precomputed
    gated input): hoist_input must be accepted and change nothing."""
    _assert_hoisted_bitmatch("rglru", _sched(4, "static"), B=3, T=9, H=128)
    _assert_hoisted_bitmatch("rglru", _sched(2, "nonstatic"),
                             B=3, T=9, H=128)


# ---------------------------------------------------------------------------
# TPU lane-alignment validation (ROADMAP open item): pallas_tpu schedules
# with misaligned column tiles must raise instead of miscompiling
# ---------------------------------------------------------------------------


def test_tpu_alignment_rejects_misaligned_tiles():
    from repro.kernels.ops import check_tpu_alignment

    tpu = KernelSchedule(backend="pallas_tpu", reuse_factor=2)
    # 4h = 80, R = 2 -> gw = 40: a partial tile off the 128-lane boundary
    with pytest.raises(ValueError, match="multiple of 128"):
        check_tpu_alignment(tpu, tile_width=40, full_width=80,
                            block_batch=8, kernel="lstm_scan")
    with pytest.raises(ValueError, match="sublanes"):
        check_tpu_alignment(tpu, tile_width=256, full_width=512,
                            block_batch=5, kernel="lstm_scan")
    # aligned tiles pass; interpreted backends are exempt (interpret pads)
    check_tpu_alignment(tpu, tile_width=256, full_width=512, block_batch=8,
                        kernel="x")
    check_tpu_alignment(_sched(2, "static"), tile_width=40, full_width=80,
                        block_batch=5, kernel="x")


@pytest.mark.parametrize("width", [80, 360, 480])
def test_tpu_alignment_accepts_whole_width_tile(width):
    """A tile spanning the whole gate width is legal for Mosaic at any
    width (the store is static): R=1 on the paper's H=20/120 taggers."""
    from repro.kernels.ops import check_tpu_alignment

    check_tpu_alignment(KernelSchedule(backend="pallas_tpu"),
                        tile_width=width, full_width=width, block_batch=8,
                        kernel="lstm_scan")


# ---------------------------------------------------------------------------
# Backend resolution: "auto" follows the platform JAX traces for
# ---------------------------------------------------------------------------


def test_auto_backend_interprets_on_cpu():
    from repro.kernels.schedule import resolve_interpret

    assert jax.default_backend() == "cpu"
    assert KernelSchedule().interpret is True
    assert resolve_interpret() is True
    assert KernelSchedule(backend="pallas_tpu").interpret is False
    assert KernelSchedule(backend="pallas_interpret").interpret is True


def test_auto_backend_unknown_platform_raises(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="no Pallas mode"):
        KernelSchedule().interpret
    # the explicit backends do not consult the platform
    assert KernelSchedule(backend="pallas_tpu").interpret is False


def test_auto_backend_compiled_on_tpu_is_alignment_checked(monkeypatch):
    """An "auto" schedule that resolves to compiled Mosaic gets the same
    alignment check as an explicit pallas_tpu one."""
    from repro.kernels.ops import check_tpu_alignment

    auto = KernelSchedule(reuse_factor=2)
    check_tpu_alignment(auto, tile_width=40, full_width=80, block_batch=8,
                        kernel="lstm_scan")          # cpu: interpreted
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert auto.interpret is False
    with pytest.raises(ValueError, match="multiple of 128"):
        check_tpu_alignment(auto, tile_width=40, full_width=80,
                            block_batch=8, kernel="lstm_scan")
    check_tpu_alignment(auto, tile_width=80, full_width=80, block_batch=8,
                        kernel="lstm_scan")


def test_tpu_alignment_enforced_at_dispatch():
    """The scan dispatch applies the check before building the kernel (the
    error surfaces at trace time, not as a Mosaic miscompile)."""
    from repro.kernels import ops

    xs, W, U, b = make_kernel_inputs("lstm", B=8, T=4, F=6, H=20)
    bad = KernelSchedule(reuse_factor=2, backend="pallas_tpu",
                         block_batch=8)
    with pytest.raises(ValueError, match="multiple of 128"):
        ops.lstm_scan(xs, W, U, b, schedule=bad)
    xs, W, U, b = make_kernel_inputs("gru", B=8, T=4, F=6, H=20)
    with pytest.raises(ValueError, match="multiple of 128"):
        ops.gru_scan(xs, W, U, b, schedule=bad)


# ---------------------------------------------------------------------------
# schedule_key forward compatibility: PR 2-era keys parse after the new
# axes landed, unknown future axes are ignored, malformed cores raise
# ---------------------------------------------------------------------------


def test_from_key_parses_pr2_era_keys():
    """Keys minted before the hoist/pipeline axes existed must still parse
    to the schedule they named (all new axes at their defaults)."""
    for key in ("static-R4-bb128-auto",
                "nonstatic-R2-bb8-pallas_interpret",
                "static-R1-bb128-xla-ap16_6_rnd_sat"):
        s = KernelSchedule.from_key(key)
        assert not s.hoist_input and s.ii == 0 and s.hoist_reuse == 1
        assert key.startswith(s.key())


def test_from_key_ignores_unknown_fields():
    """A key minted by a FUTURE build with extra axes still parses here —
    known tokens apply, unknown ones are skipped."""
    s = KernelSchedule.from_key(
        "static-R4-bb128-auto-hoist-newaxis7-zz3-ap16_6_rnd_sat")
    assert s.hoist_input and s.reuse_factor == 4
    assert s == KernelSchedule(reuse_factor=4, hoist_input=True)


def test_from_key_roundtrips_new_axes():
    for s in (_sched(4, "pipeline"),
              _sched(4, "pipeline", ii=1),
              _sched(2, "static", hoist_input=True, hoist_reuse=4),
              _sched(2, "nonstatic", hoist_input=True)):
        assert KernelSchedule.from_key(s.key()) == s


def test_from_key_rejects_malformed_cores():
    for bad in ("", "static", "static-R4", "static-X4-bb8-auto",
                "static-R4-b8-auto"):
        with pytest.raises(ValueError):
            KernelSchedule.from_key(bad)


# ---------------------------------------------------------------------------
# Schedule object semantics + HLS estimates from the same object
# ---------------------------------------------------------------------------


def test_schedule_validation():
    with pytest.raises(ValueError):
        KernelSchedule(reuse_factor=0)
    with pytest.raises(ValueError):
        KernelSchedule(mode="pipelined")
    with pytest.raises(ValueError):
        KernelSchedule(backend="cuda")
    assert all(b in BACKENDS for b in ("xla", "auto"))
    # new-axis validation
    with pytest.raises(ValueError):
        KernelSchedule(ii=-1, mode="pipeline")
    with pytest.raises(ValueError):
        KernelSchedule(hoist_reuse=0)
    with pytest.raises(ValueError):
        KernelSchedule(hoist_reuse=2)              # no hoisted GEMM to tile
    # ii is a pipeline-only knob: on other modes it normalizes to 0 so the
    # mode-override path (engine / rnn_layer replace(mode=...)) stays total
    # and keys of semantically equal schedules collide as they should
    assert KernelSchedule(ii=2, mode="static").ii == 0
    p = KernelSchedule(mode="pipeline", ii=1)
    n = p.replace(mode="nonstatic")
    assert n.ii == 0 and n == KernelSchedule(mode="nonstatic",
                                             hoist_input=True)


def test_pipeline_mode_forces_hoist():
    """Pipelining REQUIRES the hoist (only slimmed blocks can free up at
    ii); the constructor enforces the implication."""
    s = KernelSchedule(mode="pipeline", reuse_factor=4)
    assert s.hoist_input
    assert "pipeline" in MODES


def test_schedule_sweep_grid():
    grid = KernelSchedule.sweep()
    n = len(MODES) * 4                     # modes x default reuse factors
    assert len(grid) == n
    assert len(set(grid)) == n             # hashable + distinct
    assert {s.mode for s in grid} == set(MODES)


def test_sequential_steps_and_ii():
    s = KernelSchedule(reuse_factor=4, mode="static")
    assert s.sequential_steps(20) == 80
    assert s.initiation_interval(20) == 80
    n = s.replace(mode="nonstatic")
    assert n.initiation_interval(20) == 4  # one block latency

    # same kernel, same grid: the Pallas static grid is (B/bt, T, R) whose
    # sequential length is exactly sequential_steps
    assert s.sequential_steps(20) == 20 * s.reuse_factor

    # pipeline: the recurrence chain (sequential steps) is irreducible but
    # the II drops to the explicit target (default: one block's R passes)
    p = KernelSchedule(reuse_factor=4, mode="pipeline")
    assert p.sequential_steps(20) == 80
    assert p.initiation_interval(20) == 4
    assert p.replace(ii=1).initiation_interval(20) == 1
    # hoisting alone changes neither axis — it shrinks the working set
    h = s.replace(hoist_input=True)
    assert h.sequential_steps(20) == s.sequential_steps(20)
    assert h.initiation_interval(20) == s.initiation_interval(20)


@pytest.mark.parametrize("cell", CELLS)
def test_estimates_monotone_in_reuse(cell):
    """Latency rises and DSP falls as R grows — from the SAME schedule
    objects the conformance sweep executed (acceptance criterion).

    hidden=24 makes every swept R an exact divisor of both 4h and 3h, so
    effective reuse == requested reuse across the sweep.
    """
    import dataclasses

    rnn = dataclasses.replace(get_config(f"top-tagging-{cell}").rnn,
                              hidden=24)
    ests = [estimate_schedule(_sched(r, "static"), rnn)
            for r in REUSE_FACTORS]
    lat = [e.latency_cycles for e in ests]
    dsp = [e.dsp for e in ests]
    vmem = [e.vmem_bytes for e in ests]
    assert all(a < b for a, b in zip(lat, lat[1:])), lat
    assert all(a > b for a, b in zip(dsp, dsp[1:])), dsp
    assert all(a >= b for a, b in zip(vmem, vmem[1:])), vmem


def test_estimate_prices_effective_reuse():
    """For non-divisor R the kernel clamps reuse to gcd (ops.py); the
    estimate must describe the schedule that actually executes, not the
    requested one."""
    rnn = get_config("top-tagging-gru").rnn        # 3h = 60, gcd(8, 60) = 4
    assert _sched(8, "static").effective_reuse(3 * rnn.hidden) == 4
    e8 = estimate_schedule(_sched(8, "static"), rnn)
    e4 = estimate_schedule(_sched(4, "static"), rnn)
    assert (e8.latency_cycles, e8.ii_cycles, e8.dsp, e8.vmem_bytes) == \
        (e4.latency_cycles, e4.ii_cycles, e4.dsp, e4.vmem_bytes)


def test_nonstatic_resource_blowup_static_ii_blowup():
    """Paper Table 5 / Fig. 6: non-static replicates resources x seq_len but
    drops II to one block; static is the reverse."""
    rnn = get_config("top-tagging-gru").rnn
    st = estimate_schedule(_sched(1, "static"), rnn)
    ns = estimate_schedule(_sched(1, "nonstatic"), rnn)
    assert ns.dsp == rnn.seq_len * st.dsp
    assert ns.ii_cycles < st.ii_cycles


def test_hoisted_estimate_shrinks_sequential_working_set():
    """Hoisting drops the per-block sequential mults from (fin+h)*G*h to
    h*G*h: the replicated-block DSP/BRAM shrink (the shared hoist GEMM is
    counted once), and at fin ~ h the live VMEM tile shrinks too."""
    import dataclasses

    rnn = dataclasses.replace(get_config("flavor-tagging-lstm").rnn,
                              input_size=120)        # fin ~ h regime
    for mode in ("static", "nonstatic"):
        for r in (1, 4):
            inl = estimate_schedule(_sched(r, mode), rnn)
            hst = estimate_schedule(_sched(r, mode, hoist_input=True), rnn)
            if mode == "nonstatic":
                # seq_len-replicated blocks: hoisting must win on DSP/BRAM
                assert hst.dsp < inl.dsp, (mode, r)
                assert hst.bram_18k < inl.bram_18k, (mode, r)
            assert hst.vmem_bytes < inl.vmem_bytes, (mode, r)
            # the front-stage GEMM adds latency cycles; the chain stays
            assert hst.latency_cycles >= inl.latency_cycles
            assert hst.ii_cycles == inl.ii_cycles


def test_pipeline_estimate_ii_target():
    """Pipeline mode prices the II at the schedule's target while the
    per-inference latency keeps the irreducible recurrence chain."""
    rnn = get_config("flavor-tagging-lstm").rnn
    st = estimate_schedule(_sched(4, "static"), rnn)
    pl = estimate_schedule(_sched(4, "pipeline"), rnn)
    pl1 = estimate_schedule(_sched(4, "pipeline", ii=1), rnn)
    assert pl.ii_cycles == 4 and pl1.ii_cycles == 1
    assert st.ii_cycles == rnn.seq_len * 4
    assert pl.latency_cycles >= st.latency_cycles     # chain + hoist stage
    # throughput is the point: Table 5's II 315 -> 1 shape
    assert pl1.throughput_eps() > 50 * st.throughput_eps()
    # resources replicate x seq_len like nonstatic (Fig. 6), minus the
    # hoisted kernel-GEMM which is shared
    ns = estimate_schedule(_sched(4, "nonstatic"), rnn)
    assert pl.dsp < ns.dsp


def test_design_bridge_prices_hoist_and_pipeline():
    """estimate_design_for_schedule consumes the new axes: hoisting removes
    the kernel GEMM from the replicated blocks, pipeline sets the II."""
    from repro.core.hls import estimate_design_for_schedule
    cfg = get_config("flavor-tagging-lstm")
    inl = estimate_design_for_schedule(cfg, _sched(4, "nonstatic"))
    hst = estimate_design_for_schedule(
        cfg, _sched(4, "nonstatic", hoist_input=True))
    assert hst.bram_18k < inl.bram_18k
    pl = estimate_design_for_schedule(cfg, _sched(4, "pipeline"))
    assert pl.ii_cycles == 4
    pl1 = estimate_design_for_schedule(cfg, _sched(4, "pipeline", ii=1))
    assert pl1.ii_cycles == 1
    assert pl1.throughput_eps > inl.throughput_eps


def test_design_bridge_uses_schedule():
    """The table-calibrated design model prices the same schedule object
    (R values are divisors of the GRU gate dim, so effective == requested)."""
    from repro.core.hls import estimate_design_for_schedule
    cfg = get_config("top-tagging-gru")
    designs = [estimate_design_for_schedule(cfg, _sched(r, "static"))
               for r in (1, 2, 6, 12)]
    lat = [d.latency_min_us for d in designs]
    dsp = [d.dsp for d in designs]
    assert all(a < b for a, b in zip(lat, lat[1:])), lat
    assert all(a >= b for a, b in zip(dsp, dsp[1:])), dsp

    # a non-divisor request is priced as the design that executes
    d8 = estimate_design_for_schedule(cfg, _sched(8, "static"))
    d4 = estimate_design_for_schedule(cfg, _sched(4, "static"))
    assert d8 == d4


def test_resolve_honors_schedule_block_batch():
    """A caller-supplied schedule's block_batch survives dispatch (rglru
    used to clobber it with its per-kernel default)."""
    from repro.kernels.ops import _resolve

    s = KernelSchedule(block_batch=64)
    assert _resolve(s, None).block_batch == 64
    assert _resolve(s, None, default_bb=8).block_batch == 64
    assert _resolve(None, None, default_bb=8).block_batch == 8
    assert _resolve(s, 16).block_batch == 16   # explicit arg still wins


def test_tiled_matmul_matches_untiled():
    """Column tiling at the cell level matches the full matmul to fp32
    accumulation-order tolerance for any divisor R."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core.rnn.cells import tiled_matmul

    r = np.random.RandomState(7)
    x = jnp.asarray(r.randn(5, 12).astype(np.float32))
    w = jnp.asarray(r.randn(12, 24).astype(np.float32))
    base = np.asarray(x @ w)
    for reuse in (1, 2, 3, 4, 6, 8, 12, 24):
        np.testing.assert_allclose(
            np.asarray(tiled_matmul(x, w, reuse)), base,
            rtol=1e-6, atol=1e-6)


def test_config_picks_schedule():
    """Models resolve their schedule from config; explicit schedule wins."""
    import dataclasses

    rnn = get_config("top-tagging-lstm").rnn
    assert rnn.kernel_schedule() == KernelSchedule(
        reuse_factor=rnn.reuse_kernel, mode=rnn.mode)
    s = KernelSchedule(reuse_factor=4, mode="nonstatic")
    rnn2 = dataclasses.replace(rnn, schedule=s)
    assert rnn2.kernel_schedule() is s


def test_layer_routes_schedule_through_pallas():
    """rnn_layer(impl='pallas', schedule=...) matches the XLA layer."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core.rnn.layer import rnn_layer
    from repro.testing import make_kernel_inputs

    rnn = get_config("top-tagging-lstm").rnn
    xs, W, U, b = make_kernel_inputs("lstm", B=5, T=rnn.seq_len,
                                     F=rnn.input_size, H=rnn.hidden)
    ref = rnn_layer(rnn, xs, W, U, b, impl="xla")
    for s in (KernelSchedule(reuse_factor=4, backend="pallas_interpret"),
              KernelSchedule(reuse_factor=2, mode="nonstatic",
                             backend="pallas_interpret")):
        out = rnn_layer(rnn, xs, W, U, b, impl="pallas", schedule=s)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)
