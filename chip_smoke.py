#!/usr/bin/env python3
"""Serve the paper's RNN taggers on a TPU through ``RNNServingEngine``, with
the Pallas scan kernels compiled by Mosaic, and check every answer.

    python chip_smoke.py               # one chip: the phases below
    python chip_smoke.py --four-chips  # four chips: replicas behind the
                                       # router, and nothing else

One process, no children.  Weights come from ``model.init(PRNGKey(seed))``
(biases, which init zeroes, get seeded noise so the bias tiles are checked
too) and inputs from the synthetic datasets, both made from ``--seed``.

One-chip phases, each through the engine's own API (``predict``,
``predict_one``, ``submit``/``flush``):

  * flavor-tagging LSTM and GRU (H=120, T=15), static R1: a batch of 128
    plus a few single events;
  * QuickDraw LSTM (H=128, T=100): static R1, static R4, pipeline R4;
  * flavor-tagging LSTM on the native int8 datapath (ap_fixed<8,3>);
  * a mixed two-key ``submit``/``flush`` stream of 64 requests;
  * a second engine over the same AOT cache directory, which must answer
    its first requests with zero traces and the same outputs.

Every float answer is compared with a plain NumPy float32 recurrence
written below, independent of ``repro``; the int8 hidden state with the
NumPy int64 golden model ``repro.testing.quantized_golden_lstm``.  Every
executable an engine served must hold a ``tpu_custom_call`` (a compiled
Mosaic kernel, so no interpreter ran).

The last line of standard output is one JSON object,
``{"ok": true, "device": {...}}``, printed only when every phase passed.
The script exits non-zero, without that line, when JAX finds no TPU.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

# Tolerances.  f32 dots on the TPU run at the default MXU precision unless
# the code asks otherwise, and this code does not: XLA's dense head and
# Mosaic's in-kernel gate matmuls may round each f32 operand to bfloat16
# (8 significant bits) before accumulating in f32, while the NumPy
# reference keeps full f32.  Replaying the reference with every matmul
# operand rounded to bfloat16 moves the final hidden state by at most
# 2.8e-3 (QuickDraw, T=100) and the probabilities by at most 2.4e-4 on
# these inputs.  The bounds below allow about 4x that, and stay 17-48x below
# how far the reference probabilities sit from uniform (printed per phase
# as "ref spread"), so a wrong kernel cannot hide inside them.
HIDDEN_ATOL = 1e-2      # final RNN hidden state, |h| <= 1
PROB_ATOL = 1e-3        # served class probabilities
# int8: with post-training-quantized weights every operand of the head sits
# on the ap_fixed<8,3> grid (<= 8 significant bits, exact in bfloat16) and
# its sums are exact in f32, so the served probabilities equal the NumPy
# head up to the exp() in the softmax.
INT8_PROB_ATOL = 1e-5
N_EVENTS = 4            # predict_one events per float phase
N_STREAM = 64           # mixed submit/flush stream
N_ROUTED = 240          # four-chip router stream
KILL_AT = 80            # event at which one replica dies


# ---------------------------------------------------------------------------
# NumPy float32 reference (Keras layouts: LSTM gates i|f|c|o; GRU
# reset_after with gates z|r|h and bias [input; recurrent])
# ---------------------------------------------------------------------------


def _sigmoid(x):
    return np.float32(1.0) / (np.float32(1.0) + np.exp(-x))


def np_rnn(cell: str, x, W, U, b) -> np.ndarray:
    x, W, U, b = (np.asarray(a, np.float32) for a in (x, W, U, b))
    B, T, _ = x.shape
    H = U.shape[0]
    h = np.zeros((B, H), np.float32)
    c = np.zeros((B, H), np.float32)
    for t in range(T):
        if cell == "lstm":
            z = x[:, t] @ W + h @ U + b
            i, f, g, o = np.split(z, 4, axis=-1)
            c = _sigmoid(f) * c + _sigmoid(i) * np.tanh(g)
            h = _sigmoid(o) * np.tanh(c)
        else:
            zx = x[:, t] @ W + b[0]
            zh = h @ U + b[1]
            xz, xr, xh = np.split(zx, 3, axis=-1)
            hz, hr, hh = np.split(zh, 3, axis=-1)
            z = _sigmoid(xz + hz)
            r = _sigmoid(xr + hr)
            h = z * h + (1 - z) * np.tanh(xh + r * hh)
    return h


def np_head(rnn, params, h, q=lambda v: v) -> np.ndarray:
    """Dense stack + softmax; ``q`` quantizes at the same points as the
    model's fixed-point datapath (identity for float)."""
    p = {k: np.asarray(v, np.float32) for k, v in params.items()}
    h = q(np.asarray(h, np.float32))
    for i in range(len(rnn.dense_sizes)):
        h = q(h @ q(p[f"dense{i}/w"]) + q(p[f"dense{i}/b"]))
        h = q(np.maximum(h, 0))
    logits = h @ q(p["head/w"]) + q(p["head/b"])
    e = np.exp(logits - logits.max(-1, keepdims=True))
    return e / e.sum(-1, keepdims=True)


# ---------------------------------------------------------------------------
# Harness
# ---------------------------------------------------------------------------


class Smoke:
    def __init__(self):
        self.failures = []

    def check(self, phase: str, ok: bool, what: str) -> None:
        if not ok:
            self.failures.append(f"{phase}: {what}")
            print(f"  FAIL {phase}: {what}", flush=True)

    def phase(self, name: str, fn) -> None:
        t0 = time.perf_counter()
        try:
            fn(name)
        except Exception as e:  # a phase that raised has failed
            self.check(name, False, f"raised {type(e).__name__}: {e}")
        print(f"  [{name}] done in {time.perf_counter() - t0:.1f}s",
              flush=True)


def make_params(cfg, seed: int):
    import jax

    from repro.models import build_model

    params = build_model(cfg).init(jax.random.PRNGKey(seed))
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(params))
    return {name: (p + 0.1 * jax.random.normal(k, p.shape, p.dtype)
                   if name.endswith(("bias", "/b")) else p)
            for k, (name, p) in zip(keys, sorted(params.items()))}


def dataset(cfg, n: int, seed: int) -> np.ndarray:
    from repro.data import flavor_tagging_dataset, quickdraw_dataset

    make = (flavor_tagging_dataset if cfg.name.startswith("flavor")
            else quickdraw_dataset)
    return make(n, seed=seed)[0].astype(np.float32)


def reference(cfg, params, x):
    rnn = cfg.rnn
    h = np_rnn(rnn.cell, x, params["rnn/kernel"], params["rnn/recurrent"],
               params["rnn/bias"])
    return h, np_head(rnn, params, h)


def spread(p: np.ndarray) -> float:
    """How far the reference probabilities sit from uniform: the signal a
    tolerance has to stay well below."""
    return float(np.abs(p - 1.0 / p.shape[-1]).max())


def max_err(a, b) -> float:
    return float(np.abs(np.asarray(a, np.float32)
                        - np.asarray(b, np.float32)).max())


def check_compiled(smoke: Smoke, phase: str, engine) -> int:
    """Every executable the engine served holds a compiled Mosaic kernel."""
    n = 0
    for key, exes in engine.executables().items():
        for exe in exes:
            n += 1
            smoke.check(phase, "tpu_custom_call" in exe.as_text(),
                        f"executable {key} has no tpu_custom_call")
    smoke.check(phase, n > 0, "the engine served no executable")
    return n


# ---------------------------------------------------------------------------
# One-chip phases
# ---------------------------------------------------------------------------


def run_one_chip(smoke: Smoke, seed: int, engine_cache: str) -> None:
    import jax
    import jax.numpy as jnp

    from repro.config import FixedPointConfig
    from repro.configs import flavor_tagging, quickdraw
    from repro.core.quant.fixed_point import (fixed_point_error_bound,
                                              quantize_np)
    from repro.kernels import ops
    from repro.kernels.schedule import KernelSchedule, schedule_key
    from repro.models import rnn_tagger
    from repro.serving import RNNServingEngine
    from repro.testing import quantized_golden_lstm

    static_r1 = KernelSchedule()
    engines = {}

    def engine(cfg, params, **kw):
        return RNNServingEngine(cfg, params, impl="pallas", max_batch=128,
                                schedule=static_r1, cache_dir=engine_cache,
                                **kw)

    def float_tagger(name, cfg, schedules, n_events):
        params = make_params(cfg, seed)
        x = dataset(cfg, 128, seed)
        h_ref, p_ref = reference(cfg, params, x)
        eng = engines.setdefault(name, engine(cfg, params))
        for s in schedules:
            p = eng.predict(x, schedule=s)
            scan = ops.lstm_scan if cfg.rnn.cell == "lstm" else ops.gru_scan
            h = scan(x, params["rnn/kernel"], params["rnn/recurrent"],
                     params["rnn/bias"], schedule=s)
            e_h, e_p = max_err(h, h_ref), max_err(p, p_ref)
            print(f"  {name} {s.key()}: hidden max_err={e_h:.3e} "
                  f"(tol {HIDDEN_ATOL:g}, |h_ref| max "
                  f"{np.abs(h_ref).max():.3f}); probs max_err={e_p:.3e} "
                  f"(tol {PROB_ATOL:g}, ref spread {spread(p_ref):.3f})",
                  flush=True)
            smoke.check(name, p.shape == p_ref.shape
                        and np.isfinite(p).all(), f"{s.key()}: bad output")
            smoke.check(name, e_h <= HIDDEN_ATOL, f"{s.key()}: hidden err")
            smoke.check(name, e_p <= PROB_ATOL, f"{s.key()}: probs err")
        for i in range(n_events):
            p1 = eng.predict_one(x[i])
            e1 = max_err(p1, p_ref[i])
            print(f"  {name} predict_one[{i}]: max_err={e1:.3e}", flush=True)
            smoke.check(name, e1 <= PROB_ATOL, f"predict_one[{i}] err")
        n = check_compiled(smoke, name, eng)
        print(f"  {name}: {n} executables checked for tpu_custom_call",
              flush=True)

    def flavor_lstm(name):
        float_tagger(name, flavor_tagging.lstm_config(), [static_r1],
                     N_EVENTS)

    def flavor_gru(name):
        float_tagger(name, flavor_tagging.gru_config(), [static_r1],
                     N_EVENTS)

    def quickdraw_lstm(name):
        float_tagger(name, quickdraw.lstm_config(),
                     [static_r1, KernelSchedule(reuse_factor=4),
                      KernelSchedule(reuse_factor=4, mode="pipeline")], 0)

    def flavor_lstm_int8(name):
        cfg = flavor_tagging.lstm_config()
        fp = FixedPointConfig(8, 3)
        q = lambda v: quantize_np(np.asarray(v), fp)    # noqa: E731
        # post-training quantization, as the hls4ml flow deploys weights
        params = {k: jnp.asarray(q(v))
                  for k, v in make_params(cfg, seed).items()}
        x = dataset(cfg, 128, seed)
        eng = engine(cfg, params, fp=fp)
        engines[name] = eng
        p = eng.predict(x)
        W, U, b = (params[k] for k in ("rnn/kernel", "rnn/recurrent",
                                       "rnn/bias"))
        h = np.asarray(ops.lstm_scan(x, W, U, b, schedule=static_r1, fp=fp))
        h_emu = np.asarray(ops.lstm_scan(
            x, W, U, b, schedule=KernelSchedule(backend="xla"), fp=fp))
        h_gold = quantized_golden_lstm(x, W, U, b, fp)
        bound = 2 * fixed_point_error_bound(fp)      # one grid step
        e_h = max_err(h, h_gold)
        flips = int((h != h_gold).sum())
        p_ref = np_head(cfg.rnn, params, h, q=q)
        e_p = max_err(p, p_ref)
        print(f"  {name}: hidden vs golden max_err={e_h:.3e} (bound "
              f"{bound:g}, {flips}/{h.size} elements differ); native == "
              f"emulation bit-identical={np.array_equal(h, h_emu)} "
              f"(max_err={max_err(h, h_emu):.3e}); probs vs NumPy head "
              f"max_err={e_p:.3e} (tol {INT8_PROB_ATOL:g})", flush=True)
        smoke.check(name, e_h <= bound, "hidden state outside the bound")
        smoke.check(name, e_p <= INT8_PROB_ATOL, "served probs err")
        check_compiled(smoke, name, eng)

    def equality_claims(name):
        """The repo's bit-equality claims, re-run on the chip: hoisted ==
        in-loop and served == direct.  Reported bit-for-bit; held to the
        float tolerances."""
        for cfg, s in ((flavor_tagging.lstm_config(), static_r1),
                       (quickdraw.lstm_config(),
                        KernelSchedule(reuse_factor=4))):
            params = make_params(cfg, seed)
            x = dataset(cfg, 128, seed)
            args = (x, params["rnn/kernel"], params["rnn/recurrent"],
                    params["rnn/bias"])
            h = np.asarray(ops.lstm_scan(*args, schedule=s))
            hh = np.asarray(ops.lstm_scan(*args, schedule=s.replace(
                hoist_input=True)))
            e = max_err(h, hh)
            print(f"  {name} {cfg.name} {s.key()}: hoisted == in-loop "
                  f"bit-identical={np.array_equal(h, hh)} (max_err="
                  f"{e:.3e}, tol {HIDDEN_ATOL:g})", flush=True)
            smoke.check(name, e <= HIDDEN_ATOL, f"{cfg.name} hoist err")
        eng = engines["flavor_lstm"]
        cfg = eng.cfg
        x = dataset(cfg, 128, seed)
        direct = np.asarray(jax.jit(
            lambda p, x: rnn_tagger.forward(cfg, p, x, impl="pallas",
                                            schedule=static_r1)
        )(eng.params, x))
        served = eng.predict(x)
        one = eng.predict_one(x[0])
        e_d, e_1 = max_err(served, direct), max_err(one, served[0])
        print(f"  {name} served == direct: bit-identical="
              f"{np.array_equal(served, direct)} (max_err={e_d:.3e}); "
              f"predict_one == predict row: bit-identical="
              f"{np.array_equal(one, served[0])} (max_err={e_1:.3e}); "
              f"tol {PROB_ATOL:g}", flush=True)
        smoke.check(name, e_d <= PROB_ATOL, "served != direct")
        smoke.check(name, e_1 <= PROB_ATOL, "predict_one != predict")

    def mixed_stream(name):
        cfg = flavor_tagging.lstm_config()
        params = make_params(cfg, seed)
        x = dataset(cfg, N_STREAM, seed + 2)
        _, p_ref = reference(cfg, params, x)
        keys = [static_r1, KernelSchedule(mode="pipeline")]
        eng = engine(cfg, params)
        engines[name] = eng
        reqs = [eng.submit(x[i], schedule=keys[i % 2])
                for i in range(N_STREAM)]
        eng.flush(force=True)
        answered = sum(r.status == "answered" for r in reqs)
        err = max(max_err(r.result, p_ref[i]) for i, r in enumerate(reqs)
                  if r.status == "answered")
        traces = {schedule_key(s): eng.trace_count(schedule_key(s))
                  for s in keys}
        print(f"  {name}: {answered}/{N_STREAM} answered over 2 keys, "
              f"max_err={err:.3e} (tol {PROB_ATOL:g}), traces {traces}",
              flush=True)
        smoke.check(name, answered == N_STREAM, "unanswered requests")
        smoke.check(name, err <= PROB_ATOL, "stream answers err")
        smoke.check(name, all(v <= 1 for v in traces.values()),
                    "more than one trace per key")
        check_compiled(smoke, name, eng)

    def warm_engine(name):
        cfg = flavor_tagging.lstm_config()
        cold = engines["flavor_lstm"]
        x = dataset(cfg, 128, seed)
        warm = engine(cfg, cold.params)
        key = schedule_key(static_r1)
        p = warm.predict(x)
        p1 = warm.predict_one(x[0])
        same = (np.array_equal(p, cold.predict(x))
                and np.array_equal(p1, cold.predict_one(x[0])))
        traces = warm.trace_count(key) + warm.one_trace_count(key)
        print(f"  {name}: first requests on a fresh engine over the warm "
              f"cache: trace_count={traces}, outputs identical={same}",
              flush=True)
        smoke.check(name, traces == 0, "warm engine traced")
        smoke.check(name, same, "warm engine outputs differ")
        check_compiled(smoke, name, warm)

    for name, fn in (("flavor_lstm", flavor_lstm),
                     ("flavor_gru", flavor_gru),
                     ("quickdraw_lstm", quickdraw_lstm),
                     ("flavor_lstm_int8", flavor_lstm_int8),
                     ("equality_claims", equality_claims),
                     ("mixed_stream", mixed_stream),
                     ("warm_engine", warm_engine)):
        smoke.phase(name, fn)


# ---------------------------------------------------------------------------
# Four-chip phase: one replica per chip behind the router
# ---------------------------------------------------------------------------


def run_four_chips(smoke: Smoke, seed: int, engine_cache: str) -> None:
    import jax

    from repro.configs import quickdraw
    from repro.kernels.schedule import KernelSchedule, schedule_key
    from repro.serving import (ReplicaPool, RNNServingEngine, Router,
                               RouterPolicy)
    from repro.serving.faults import crash_replica

    def replicas(name):
        devs = jax.devices()[:4]
        cfg = quickdraw.lstm_config()
        params = make_params(cfg, seed)
        x = dataset(cfg, N_ROUTED, seed + 3)
        # six keys whose hash-ring placement gives every replica a share
        schedules = [KernelSchedule(block_batch=8),
                     KernelSchedule(reuse_factor=2),
                     KernelSchedule(reuse_factor=2, hoist_input=True),
                     KernelSchedule(reuse_factor=4),
                     KernelSchedule(reuse_factor=2, mode="pipeline"),
                     KernelSchedule(reuse_factor=4, mode="pipeline")]
        oracle = RNNServingEngine(cfg, params, impl="pallas",
                                  device=devs[0], cache_dir=engine_cache)
        want = [oracle.predict_one(x[i], schedule=schedules[i % 6])
                for i in range(N_ROUTED)]
        pool = ReplicaPool.build(cfg, params, 4, impl="pallas",
                                 cache_dir=engine_cache)
        router = Router(pool, policy=RouterPolicy(
            timeout_s=1.0, consecutive_failures=2, probe_interval_s=1e9))
        done, victim = [], None
        for i in range(N_ROUTED):
            s = schedules[i % 6]
            if i == KILL_AT:
                victim = router.place(schedule_key(
                    *router.reference_engine.resolve(s)))
                crash_replica(victim)
            done.append(router.submit(x[i], schedule=s, now=i * 1e-3))
        acc = router.verify_router_accounting()       # raises if inexact
        answered = [r for r in done if r.status == "answered"]
        err = max(max_err(r.result, want[r.req_id]) for r in answered)
        identical = all(np.array_equal(r.result, want[r.req_id])
                        for r in answered)
        winners = {rid: sum(r.winner == rid for r in answered)
                   for rid in pool.ids()}
        print(f"  {name}: {len(answered)}/{N_ROUTED} answered, killed "
              f"{victim.replica_id} at event {KILL_AT}, healthy after "
              f"{router.healthy_count()}, answers per replica {winners}, "
              f"retries {sum(c['retries'] for c in acc.values())}, "
              f"max_err vs device-0 oracle={err:.3e} (tol {PROB_ATOL:g}), "
              f"bit-identical={identical}", flush=True)
        smoke.check(name, len(answered) == N_ROUTED, "unanswered requests")
        smoke.check(name, err <= PROB_ATOL, "answers differ from the oracle")
        smoke.check(name, router.healthy_count() == 3,
                    "the killed replica was not retired")
        for i, rep in enumerate(pool):
            dev = rep.engine.device
            on_dev = {d for leaf in jax.tree.leaves(rep.engine.params)
                      for d in leaf.devices()}
            outs = {d for exes in rep.engine.executables().values()
                    for exe in exes
                    for sh in jax.tree.leaves(exe.output_shardings)
                    for d in sh.device_set}
            print(f"  {rep.replica_id}: device {dev.id}, params on "
                  f"{sorted(d.id for d in on_dev)}, results on "
                  f"{sorted(d.id for d in outs)}", flush=True)
            smoke.check(name, dev == devs[i], f"{rep.replica_id} device")
            smoke.check(name, on_dev == {dev}, f"{rep.replica_id} params")
            smoke.check(name, outs == {dev}, f"{rep.replica_id} results")
            check_compiled(smoke, name, rep.engine)
        smoke.check(name, len({rep.engine.device for rep in pool}) == 4,
                    "replicas share a device")
        check_compiled(smoke, name, oracle)

    smoke.phase("router_four_chips", replicas)


# ---------------------------------------------------------------------------


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-replica router phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax

    devs = jax.devices()
    need = 4 if args.four_chips else 1
    if devs[0].platform != "tpu" or len(devs) < need:
        print(f"chip_smoke: needs {need} TPU chip(s); JAX found "
              f"{len(devs)} {devs[0].platform} device(s). Nothing ran.",
              file=sys.stderr)
        return 2

    from repro.serving.compile_cache import enable_jax_compilation_cache

    cache_root = enable_jax_compilation_cache()
    engine_cache = os.path.join(cache_root, "engine")
    print(f"chip_smoke: {len(devs)} x {devs[0].device_kind}, JAX "
          f"{jax.__version__}, compile cache {cache_root}", flush=True)

    smoke = Smoke()
    t0 = time.perf_counter()
    if args.four_chips:
        run_four_chips(smoke, args.seed, engine_cache)
    else:
        run_one_chip(smoke, args.seed, engine_cache)
    print(f"chip_smoke: {time.perf_counter() - t0:.1f}s, "
          f"{len(smoke.failures)} failure(s)", flush=True)
    if smoke.failures:
        for f in smoke.failures:
            print(f"chip_smoke FAILED {f}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
