"""RNN serving engine — the paper's deliverable as a multi-tenant service.

Wraps a trained tagger with schedule-aware serving: every request optionally
carries a :class:`KernelSchedule` (plus fixed-point config), and the engine

  * co-batches requests by the stable ``schedule_key`` hash — requests that
    compile to the same kernel share a batch, requests that differ never mix
    (a multi-tenant FPGA farm serving several reuse-factor design points at
    once);
  * keeps ONE jit trace per schedule hash: flushed batches are padded to the
    key's ``max_batch`` (zero rows — row-wise bit-identical on every
    backend), so mixed-schedule traffic never retraces;
  * shares batches across ragged (variable seq_len) jet streams, either by
    length-bucketing sub-batches (bit-identical to direct ``predict``) or by
    a pad-and-mask scan (single batch, XLA datapath);
  * reports, per schedule key, measured wall-clock latency/throughput paired
    with ``core.hls.estimate_schedule`` of the SAME schedule object — the
    paper's measured-vs-analytical two-column comparison;
  * resolves :class:`~repro.autotune.DesignTarget`\\ s to schedules through
    the Pareto explorer (``auto_schedule`` / ``submit(target=...)``): a
    queue can be opened with a latency/resource budget instead of an
    explicit ``KernelSchedule``, and the static/nonstatic/pipeline mode is
    auto-picked from ``estimate_schedule`` pricing.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.autotune import DesignTarget, SpaceSpec
from repro.autotune import select as autotune_select
from repro.config import FixedPointConfig, ModelConfig
from repro.core.hls import (DesignPoint, HLSDesign, RNNDesignPoint,
                            estimate_design, estimate_schedule)
from repro.kernels.schedule import (DEFAULT_SCHEDULE_KEY, KernelSchedule,
                                    cache_meta, schedule_key)
from repro.models import rnn_tagger
from repro.serving.batcher import KeyStats, MicroBatcher, Request, _pad_stack
from repro.serving.compile_cache import CachedExecutor, CompileCache
from repro.serving.spans import tracer

RAGGED_POLICIES = ("bucket", "mask")


class EngineClosedError(RuntimeError):
    """Submit/predict on a closed engine: the replica was retired (drained
    and closed) and must never accept new work — the router re-places its
    keys instead.  A clear error beats a silently stranded request."""

    def __init__(self, what: str = "engine"):
        super().__init__(
            f"{what} is closed: it was drained and retired, so new requests "
            f"must be routed to a live replica (close() flushed every "
            f"queued request to a terminal state first)")


@dataclass
class RNNServingEngine:
    cfg: ModelConfig
    params: Dict
    mode: Optional[str] = None            # static | nonstatic | pipeline |
                                          # None: from the schedule / config
                                          # (pipeline implies the hoisted
                                          # input projection; its queue key
                                          # carries the -hoist/-ii tokens)
    impl: str = "xla"                     # xla | pallas
    fp: Optional[FixedPointConfig] = None
    max_batch: int = 256
    schedule: Optional[KernelSchedule] = None   # default-request schedule
    ragged: str = "bucket"                # bucket (bit-exact) | mask (one
                                          # padded batch, XLA datapath)
    pad_batches: bool = True              # pad flushes to max_batch: one jit
                                          # trace per schedule hash
    cache_dir: Optional[str] = None       # persistent AOT compile cache; a
                                          # warm dir serves the first request
                                          # of a FRESH engine with zero jit
                                          # compiles (N replicas may share it)
    device: Optional[jax.Device] = None   # where params, inputs and the
                                          # executables live (None: the
                                          # default device)
    _infer_cache: Dict[str, Callable] = field(default_factory=dict, repr=False)
    _key_specs: Dict[str, Tuple[KernelSchedule, Optional[FixedPointConfig]]] \
        = field(default_factory=dict, repr=False)
    _traces: Dict[str, int] = field(default_factory=dict, repr=False)
    _target_points: Dict[Tuple, DesignPoint] \
        = field(default_factory=dict, repr=False)
    # batch-1 fast path: its own jit traces + counters, so the batched
    # one-trace-per-key invariant and its stats stay untouched
    _one_cache: Dict[str, Callable] = field(default_factory=dict, repr=False)
    _one_traces: Dict[str, int] = field(default_factory=dict, repr=False)
    _one_stats: Dict[str, KeyStats] = field(default_factory=dict, repr=False)
    _closed: bool = field(default=False, repr=False)

    def __post_init__(self):
        if self.ragged not in RAGGED_POLICIES:
            raise ValueError(f"ragged {self.ragged!r} not in {RAGGED_POLICIES}")
        self.batcher = MicroBatcher(max_batch=self.max_batch)
        self.compile_cache = CompileCache(self.cache_dir,
                                          device=self.device)
        if self.device is not None:
            self.params = jax.device_put(self.params, self.device)

    def _put(self, x):
        """An input as the engine's executables take it.  A host array stays
        on the host, C-contiguous in its canonical dtype: the executable's
        own call copies it to the device.  A ``jax.Array`` on this engine's
        device passes as it is; any other is moved there."""
        if isinstance(x, jax.Array):
            if self.device is not None and x.devices() == {self.device}:
                return x
            return jax.device_put(x, self.device)
        x = np.asarray(x)
        return np.ascontiguousarray(
            x, dtype=jax.dtypes.canonicalize_dtype(x.dtype))

    # -- schedule resolution -------------------------------------------------

    @property
    def resolved_schedule(self) -> KernelSchedule:
        """The schedule executed for requests that don't carry one: the
        engine's explicit schedule or the config-derived one, with the legacy
        ``mode`` / ``impl`` fields folded in so the key names what runs."""
        s = self.schedule if self.schedule is not None \
            else self.cfg.rnn.kernel_schedule()
        if self.mode is not None and s.mode != self.mode:
            s = s.replace(mode=self.mode)
        if self.impl == "xla" and s.backend != "xla":
            s = s.replace(backend="xla")
        return s

    @property
    def resolved_mode(self) -> str:
        return self.resolved_schedule.mode

    def resolve(self, schedule: Optional[KernelSchedule] = None,
                fp: Optional[FixedPointConfig] = None
                ) -> Tuple[KernelSchedule, Optional[FixedPointConfig]]:
        """(schedule, fp) a request with these overrides actually executes."""
        return (schedule if schedule is not None else self.resolved_schedule,
                fp if fp is not None else self.fp)

    # -- target-driven auto-scheduling ---------------------------------------

    def _default_spec(self, target: DesignTarget) -> SpaceSpec:
        """The slice of schedule space this engine can execute: its backend
        family, a kernel-friendly block_batch, the full legal R/mode/hoist
        axes.  Callers needing other axes pass an explicit spec."""
        backend = "xla" if self.impl == "xla" else "auto"
        return SpaceSpec(backends=(backend,),
                         block_batches=(min(8, self.max_batch),))

    def schedule_for_target(self, target: DesignTarget, *,
                            spec: Optional[SpaceSpec] = None,
                            measure_top_k: int = 0) -> DesignPoint:
        """Resolve a DesignTarget to the priced point this engine will run.

        Memoized per (target, spec, measure_top_k) — all frozen/hashable —
        so a stream of requests carrying the same target resolves the
        explorer once and then co-batches on the selected schedule's key
        like any explicit-schedule stream, while the same target under a
        DIFFERENT space spec resolves independently (never served from the
        other spec's cache).  Raises ``InfeasibleTargetError`` (with the
        nearest-to-feasible point named) when the budget cannot be met.
        """
        memo = (target, spec, measure_top_k)
        pt = self._target_points.get(memo)
        if pt is None:
            import dataclasses
            eff = target
            if eff.fp is None and self.fp is not None:
                # price with the fp the engine will actually serve with
                eff = dataclasses.replace(eff, fp=self.fp)
            pt = autotune_select(self.cfg, eff,
                                 spec or self._default_spec(target),
                                 measure_top_k=measure_top_k)
            self._target_points[memo] = pt
        return pt

    def auto_schedule(self, target: DesignTarget, *,
                      spec: Optional[SpaceSpec] = None,
                      measure_top_k: int = 0,
                      warmup: bool = True) -> DesignPoint:
        """Make a DesignTarget this engine's default design point.

        The selected schedule becomes the engine default — subsequent
        ``predict`` / ``submit`` calls without an explicit schedule execute
        it (and the default queue reports it) — closing the ROADMAP
        "scheduler-over-schedules" item: the per-queue static / nonstatic /
        pipeline choice comes from ``estimate_schedule`` via the explorer
        instead of the caller.
        """
        pt = self.schedule_for_target(target, spec=spec,
                                      measure_top_k=measure_top_k)
        self.schedule = pt.schedule
        self.mode = None                 # the schedule is now authoritative
        self.impl = "pallas" if pt.schedule.use_pallas else "xla"
        if target.fp is not None:
            self.fp = pt.fp
        if warmup:
            self.warmup()
        return pt

    def _ensure_key(self, sched: KernelSchedule,
                    fp: Optional[FixedPointConfig]) -> str:
        key = schedule_key(sched, fp)
        if key not in self._infer_cache:
            self._key_specs[key] = (sched, fp)
            self._infer_cache[key] = self._make_infer(key, sched, fp)
        return key

    def _executor_meta(self, kind: str, sched: KernelSchedule,
                       fp: Optional[FixedPointConfig]) -> Dict:
        """Content identity of one compiled serving executable: the model
        config plus the EXHAUSTIVE schedule/fp axes (``cache_meta``, not the
        routing key — a future schedule axis must invalidate entries, not
        silently share them).  The toolchain axes (jaxlib, platform) are
        appended by the CompileCache itself; argument shapes by the
        executor."""
        return {"kind": kind, "cfg": repr(self.cfg),
                **cache_meta(sched, fp)}

    def _make_infer(self, key: str, sched: KernelSchedule,
                    fp: Optional[FixedPointConfig]) -> Callable:
        cfg = self.cfg
        impl = "pallas" if sched.use_pallas else "xla"

        def infer(params, x, lengths=None):
            # Python side effect runs at COLD lower/compile time only:
            # counts jit traces per schedule hash (the co-batching
            # efficiency criterion).  A warm cache hit deserializes the
            # executable instead of tracing, so this never runs — which is
            # exactly what trace_count() == 0 after a warm start asserts.
            self._traces[key] = self._traces.get(key, 0) + 1
            return rnn_tagger.forward(cfg, params, x, fp=fp, impl=impl,
                                      schedule=sched, lengths=lengths)

        return CachedExecutor(jax.jit(infer), self.compile_cache, key,
                              self._executor_meta("rnn_infer", sched, fp))

    def trace_count(self, key: str) -> int:
        return self._traces.get(key, 0)

    # -- direct batched inference -------------------------------------------

    def _resolve_default_key(self, key: str) -> str:
        """Requests on the bare DEFAULT_SCHEDULE_KEY queue (submitted via
        the batcher with no schedule) execute the engine's RESOLVED
        schedule: route them to its compiled key instead of KeyErroring on
        a queue that never had a kernel."""
        if key == DEFAULT_SCHEDULE_KEY:
            return self._ensure_key(*self.resolve())
        return key

    def _predict_key(self, key: str, x: np.ndarray,
                     lengths: Optional[np.ndarray] = None) -> np.ndarray:
        span = tracer()
        fn = self._infer_cache[self._resolve_default_key(key)]
        with span("engine.put"):
            args = (self._put(x),) if lengths is None else (
                self._put(x), self._put(np.asarray(lengths, np.int32)))
        with span("engine.dispatch"):
            y = fn(self.params, *args)
        with span("engine.fetch"):
            return np.asarray(y)

    def predict(self, x: np.ndarray,
                schedule: Optional[KernelSchedule] = None,
                fp: Optional[FixedPointConfig] = None,
                target: Optional[DesignTarget] = None) -> np.ndarray:
        """[b, T, in] -> [b, n_outputs] under the request's schedule (or the
        schedule auto-picked for its ``target``)."""
        with tracer()("engine.predict"):
            self._check_open()
            if target is not None and schedule is None:
                pt = self.schedule_for_target(target)
                schedule, fp = pt.schedule, fp if fp is not None else pt.fp
            key = self._ensure_key(*self.resolve(schedule, fp))
            return self._predict_key(key, x)

    def predict_ragged(self, xs: List[np.ndarray],
                       schedule: Optional[KernelSchedule] = None,
                       fp: Optional[FixedPointConfig] = None) -> List[np.ndarray]:
        """Variable-length requests sharing one logical batch.  ``bucket``
        groups by seq_len (bit-identical to per-length predict on every
        backend); ``mask`` pads to the max length and freezes each row's
        state past its true length (one batch, XLA-cell datapath)."""
        self._check_open()
        key = self._ensure_key(*self.resolve(schedule, fp))
        pad, lengths, _ = _pad_stack(list(xs))
        if self.ragged == "mask":
            # through _predict_padded, NOT _predict_key: a direct call would
            # compile one trace per distinct request count, silently
            # breaking the one-trace-per-key invariant the co-batching
            # design is built on
            out = self._predict_padded(key, pad, lengths)
            return [out[i] for i in range(len(xs))]
        return self._bucket_predict(key, xs, lengths)

    def _bucket_predict(self, key: str, xs: List[np.ndarray],
                        lengths: np.ndarray) -> List[np.ndarray]:
        out: List[Optional[np.ndarray]] = [None] * len(xs)
        for t in sorted({int(n) for n in lengths}):
            idx = [i for i, n in enumerate(lengths) if int(n) == t]
            sub = np.stack([np.asarray(xs[i])[:t] for i in idx])
            res = self._predict_padded(key, sub)
            for j, i in enumerate(idx):
                out[i] = res[j]
        return out                           # type: ignore[return-value]

    def warmup(self, schedule: Optional[KernelSchedule] = None,
               fp: Optional[FixedPointConfig] = None) -> Dict[str, Dict]:
        """Warm ONE (schedule, fp) pair's serving-shape executable — from
        the persistent cache when possible, else compile-and-store."""
        return self.prewarm(schedules=[schedule], fps=[fp])

    def prewarm(self, targets: Optional[List[DesignTarget]] = None,
                schedules: Optional[List[Optional[KernelSchedule]]] = None,
                fps: Optional[List[Optional[FixedPointConfig]]] = None
                ) -> Dict[str, Dict]:
        """Zero-warmup entry point: make the serving-bucket executables for
        a list of targets and/or schedules exist BEFORE traffic arrives.

        Each (schedule, fp) pair — targets are resolved through the
        explorer first — is lowered against the key's serving shape bucket
        (``max_batch`` rows x the config's sequence) from
        ``jax.ShapeDtypeStruct`` avals, so nothing executes.  Over a warm
        ``cache_dir`` this deserializes stored artifacts (zero jit
        compiles); cold entries compile once and are stored for the next
        engine / replica.  Returns per-key
        ``{"status": "hot"|"warm"|"cold", "compile_s": ...}``.
        """
        pairs: List[Tuple[Optional[KernelSchedule],
                          Optional[FixedPointConfig]]] = []
        for t in (targets or ()):
            pt = self.schedule_for_target(t)
            pairs.append((pt.schedule, pt.fp))
        if schedules is not None:
            fps = fps if fps is not None else [None] * len(schedules)
            pairs.extend(zip(schedules, fps))
        if not pairs:
            pairs.append((None, None))   # the engine's resolved default
        r = self.cfg.rnn
        out: Dict[str, Dict] = {}
        for sched, fp in pairs:
            key = self._ensure_key(*self.resolve(sched, fp))
            mb, _ = self.batcher.policy(key)
            rows = mb if self.pad_batches else 1
            x = jax.ShapeDtypeStruct(
                (rows, r.seq_len, r.input_size), jnp.float32,
                sharding=jax.sharding.SingleDeviceSharding(
                    self.compile_cache.device))
            out[key] = self._infer_cache[key].warm(self.params, x)
        return out

    # -- batch-1 latency fast path ------------------------------------------

    def _make_one_infer(self, key: str, sched: KernelSchedule,
                        fp: Optional[FixedPointConfig]) -> Callable:
        cfg = self.cfg
        impl = "pallas" if sched.use_pallas else "xla"

        def infer(params, x):
            # trace-time side effect: fast-path traces counted separately
            # from the batched path's (the one-trace-per-key invariant of
            # the co-batching tests must not see this trace)
            self._one_traces[key] = self._one_traces.get(key, 0) + 1
            return rnn_tagger.forward(cfg, params, x, fp=fp, impl=impl,
                                      schedule=sched)

        return CachedExecutor(jax.jit(infer), self.compile_cache, key,
                              self._executor_meta("rnn_one", sched, fp),
                              name_hint=f"{key}-one")

    def predict_one(self, x: np.ndarray,
                    schedule: Optional[KernelSchedule] = None,
                    fp: Optional[FixedPointConfig] = None,
                    target: Optional[DesignTarget] = None) -> np.ndarray:
        """Single-event inference: ``[T, in] -> [n_outputs]`` — the paper's
        single-collision latency scenario.

        Skips the batcher entirely: no queueing, no pad-to-``max_batch``
        round trip — ONE single-row scheduled step through a dedicated
        batch-1 jit trace of the request's schedule (row-wise bit-identical
        to the batched path, so ``predict_one(x) == predict(x[None])[0]``
        exactly; conformance-enforced).  Steady-state wall-clock is
        recorded per key (compile calls excluded) and reported by
        ``serve_report`` as the ``fast_path`` column.
        """
        span = tracer()
        with span("engine.predict_one"):
            self._check_open()
            if target is not None and schedule is None:
                pt = self.schedule_for_target(target)
                schedule, fp = pt.schedule, fp if fp is not None else pt.fp
            sched, fpr = self.resolve(schedule, fp)
            key = self._ensure_key(sched, fpr)  # registers specs for reports
            fn = self._one_cache.get(key)
            if fn is None:
                fn = self._one_cache[key] = self._make_one_infer(key, sched,
                                                                 fpr)
            traces_before = self._one_traces.get(key, 0)
            t0 = time.perf_counter()
            with span("engine.put"):
                x1 = self._put((x if isinstance(x, (np.ndarray, jax.Array))
                                else np.asarray(x))[None])
            with span("engine.dispatch"):
                y = fn(self.params, x1)
            with span("engine.fetch"):
                out = np.asarray(y)[0]
            if self._one_traces.get(key, 0) == traces_before:  # steady state
                self._one_stats.setdefault(key, KeyStats()).record_one(
                    time.perf_counter() - t0)
            # free the call's device buffers inside the root span, so that
            # their cost shows in its self time, not between calls
            del x1, y
            return out

    def one_trace_count(self, key: str) -> int:
        return self._one_traces.get(key, 0)

    def executables(self) -> Dict[str, List]:
        """Every compiled executable this engine has served: the batched
        path under its schedule key, the batch-1 path under ``<key>-one``."""
        out = {k: fn.executables() for k, fn in self._infer_cache.items()}
        out.update({f"{k}-one": fn.executables()
                    for k, fn in self._one_cache.items()})
        return out

    # -- lifecycle -----------------------------------------------------------

    @property
    def closed(self) -> bool:
        return self._closed

    def _check_open(self) -> None:
        if self._closed:
            raise EngineClosedError("RNNServingEngine")

    def drain(self, now: Optional[float] = None) -> List[Request]:
        """Flush EVERY per-key queue to completion (force, below-threshold
        leftovers included) and return the flushed requests — every queued
        request reaches a terminal state (answered, or failed with the
        error attached); none is stranded.  The engine stays open: drain is
        the quiesce step, :meth:`close` the retire step."""
        return self.flush(now=now, force=True)

    def close(self, now: Optional[float] = None) -> List[Request]:
        """Drain, then refuse all new work: ``submit`` / ``predict`` /
        ``predict_one`` / ``serve`` raise :class:`EngineClosedError` from
        now on.  Idempotent — a second close drains nothing and returns
        ``[]``.  This is the replica-retirement hook the router relies on:
        after ``close()`` returns, no request is in flight on this engine
        and none can sneak in."""
        if self._closed:
            return []
        flushed = self.drain(now=now)
        self._closed = True
        return flushed

    # -- schedule-keyed serving ---------------------------------------------

    def submit(self, x: np.ndarray,
               schedule: Optional[KernelSchedule] = None,
               fp: Optional[FixedPointConfig] = None,
               target: Optional[DesignTarget] = None,
               now: Optional[float] = None) -> Request:
        """Enqueue one request ([T, in] payload) on its schedule's queue.

        A request may carry a ``target`` instead of a schedule: the engine
        resolves it through the explorer (memoized), so a stream of
        same-target requests lands on one auto-picked queue — per-queue
        mode selection without any caller-side schedule plumbing.
        """
        self._check_open()
        if target is not None and schedule is None:
            pt = self.schedule_for_target(target)
            schedule, fp = pt.schedule, fp if fp is not None else pt.fp
        sched, fpr = self.resolve(schedule, fp)
        key = self._ensure_key(sched, fpr)
        return self.batcher.submit(x, now=now, key=key, schedule=sched,
                                   fp=fpr)

    def _pad_rows(self, x: np.ndarray, key: str) -> Tuple[np.ndarray, int]:
        b = x.shape[0]
        mb, _ = self.batcher.policy(key)
        if not self.pad_batches or b >= mb:
            return x, b
        pad = np.zeros((mb - b,) + x.shape[1:], x.dtype)
        return np.concatenate([x, pad], axis=0), b

    def _predict_padded(self, key: str, x: np.ndarray,
                        lengths: Optional[np.ndarray] = None) -> np.ndarray:
        """Key-cached inference with the batch padded to the key's
        max_batch: constant shapes, so mixed-schedule traffic costs at most
        one jit trace per schedule hash.  Zero rows are row-wise inert on
        every backend (verified by the conformance suite)."""
        with tracer()("engine.pad"):
            xp, b = self._pad_rows(np.asarray(x), key)
            if lengths is not None and xp.shape[0] != len(lengths):
                lp = np.zeros((xp.shape[0],), np.int32)
                lp[:b] = lengths
                lengths = lp
        return self._predict_key(key, xp, lengths)[:b]

    def _flush_fn(self, key: str) -> Callable:
        """The infer function handed to the batcher for one queue; accepts
        ``lengths`` so ragged flushes route through the engine's policy."""
        def fn(x, lengths=None):
            if lengths is None:
                return self._predict_padded(key, x)
            if self.ragged == "mask":
                return self._predict_padded(key, x, lengths=lengths)
            res = self._bucket_predict(
                key, [np.asarray(x[i]) for i in range(x.shape[0])],
                np.asarray(lengths))
            return np.stack(res)
        return fn

    def flush(self, now: Optional[float] = None,
              force: bool = False) -> List[Request]:
        """Flush every ready queue (fair round-robin across schedule keys);
        ``force`` also flushes below-threshold leftovers (end of stream)."""
        with tracer()("engine.flush"):
            return self.batcher.run_all(self._flush_fn, now=now, force=force)

    def serve(self, payloads, schedules=None, fps=None,
              now: Optional[float] = None) -> List[Request]:
        """Convenience: submit a whole stream (parallel lists), then flush to
        completion.  Returns the requests in submission order."""
        n = len(payloads)
        schedules = schedules if schedules is not None else [None] * n
        fps = fps if fps is not None else [None] * n
        reqs = [self.submit(x, schedule=s, fp=f, now=now)
                for x, s, f in zip(payloads, schedules, fps)]
        self.flush(now=now, force=True)
        return reqs

    # -- measured throughput/latency ----------------------------------------

    def benchmark(self, batch: int, iters: int = 20,
                  schedule: Optional[KernelSchedule] = None,
                  fp: Optional[FixedPointConfig] = None) -> Dict[str, float]:
        """Measured latency/throughput for one schedule key, paired with the
        analytical estimate of the same schedule object."""
        r = self.cfg.rnn
        sched, fpr = self.resolve(schedule, fp)
        key = self._ensure_key(sched, fpr)
        x = np.random.RandomState(0).randn(
            batch, r.seq_len, r.input_size).astype(np.float32)
        # through _predict_padded, NOT _predict_key: benchmarking at
        # arbitrary batch sizes must measure (and compile) the SAME padded
        # serving-shape executable the flush path runs — a direct call per
        # distinct batch size would silently stack extra traces on the key
        self._predict_padded(key, x)                # compile
        t0 = time.perf_counter()
        for _ in range(iters):
            self._predict_padded(key, x)
        dt = (time.perf_counter() - t0) / iters
        est = estimate_schedule(sched, r, fpr)
        return {"key": key, "batch": batch, "latency_s": dt,
                "throughput_eps": batch / dt,
                "latency_cycles": est.latency_cycles,
                "ii_cycles": est.ii_cycles, "dsp": est.dsp}

    # -- measured vs analytical, per schedule key ---------------------------

    def serve_report(self, clock_mhz: float = 200.0) -> Dict[str, Dict]:
        """Per schedule key: measured serving stats (from the batcher's
        per-key counters) next to ``estimate_schedule`` of the SAME schedule
        object the queue executed — the paper's two-column table.

        Requests served on the bare DEFAULT_SCHEDULE_KEY queue report the
        engine's RESOLVED schedule (the kernel they actually executed) with
        its estimate, not an estimate-less row.  Compiles always belong to
        the resolved key's own row: the default row reports ``traces: 0``
        and points at ``resolved_key`` — attributing the resolved key's
        trace count to BOTH rows would double-report the same compiles
        whenever both queues saw traffic.

        Each row also carries the ``compile`` column — the persistent
        cache's per-key cold/warm split (hit rate + first-request compile
        seconds), the zero-warmup acceptance signal."""
        specs = dict(self._key_specs)
        resolved_from: Dict[str, str] = {}
        if (DEFAULT_SCHEDULE_KEY in self.batcher.stats
                and DEFAULT_SCHEDULE_KEY not in specs):
            sched, fpr = self.resolve()
            specs[DEFAULT_SCHEDULE_KEY] = (sched, fpr)
            resolved_from[DEFAULT_SCHEDULE_KEY] = schedule_key(sched, fpr)
        report: Dict[str, Dict] = {}
        for key, (sched, fpr) in specs.items():
            est = estimate_schedule(sched, self.cfg.rnn, fpr)
            report[key] = {
                "schedule": sched,
                "fp": fpr,
                "traces": 0 if key in resolved_from else self.trace_count(key),
                "measured": self.batcher.key_stats(key).summary(),
                "analytical": est.report_row(clock_mhz),
                "compile": self.compile_cache.report_row(key),
            }
            if key in resolved_from:
                report[key]["resolved_key"] = resolved_from[key]
            if key in self._one_stats:
                # the batch-1 fast path's steady-state latency, next to the
                # batched queue's — the paper's single-event column
                report[key]["fast_path"] = self._one_stats[key].summary()
        return report

    # -- paired FPGA design point -------------------------------------------

    def fpga_design(self, reuse_kernel: int = 1, reuse_recurrent: int = 1,
                    strategy: str = "latency", part: str = "xcku115"
                    ) -> HLSDesign:
        return estimate_design(RNNDesignPoint(
            self.cfg, self.fp or FixedPointConfig(),
            reuse_kernel, reuse_recurrent, self.resolved_mode,
            strategy, part))


def format_serve_report(report: Dict[str, Dict],
                        clock_mhz: float = 200.0) -> str:
    """Render serve_report() as the measured-vs-analytical table."""
    lines = [f"{'schedule key':38s} {'served':>6s} {'meas p50':>10s} "
             f"{'meas p99':>10s} {'est lat':>9s} {'est II':>8s} {'DSP':>6s} "
             f"{'cold/warm':>9s} {'hit':>5s}"]
    for key, row in report.items():
        m, a = row["measured"], row["analytical"]
        c = row.get("compile", {})
        cw = f"{int(c.get('cold', 0))}/{int(c.get('warm', 0))}"
        lines.append(
            f"{key:38s} {int(m['served']):6d} "
            f"{m['latency_p50_s'] * 1e3:8.2f}ms "
            f"{m['latency_p99_s'] * 1e3:8.2f}ms "
            f"{a['latency_us']:7.2f}us {a['ii_cycles']:8d} {a['dsp']:6d} "
            f"{cw:>9s} {c.get('hit_rate', 0.0):4.0%}")
    return "\n".join(lines)
