"""Serving driver: ``python -m repro.launch.serve --arch <id> [options]``.

RNN taggers (the paper's use case): load/train params, stand up the
RNNServingEngine, stream a synthetic request load through the micro-batcher,
report wall-clock latency/throughput alongside the analytical FPGA design
point for the same (mode, precision, reuse) — the paper's comparison.

LM archs: tiny-config LMServingEngine demo with continuous batching.
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.config import FixedPointConfig
from repro.data import (flavor_tagging_dataset, quickdraw_dataset,
                        top_tagging_dataset)
from repro.models.model import build_model
from repro.registry import get_config
from repro.serving import LMServingEngine, RNNServingEngine
from repro.serving.compile_cache import enable_jax_compilation_cache
from repro.testing import tiny_config


def serve_rnn(arch: str, mode: str, n_requests: int, fixed_point: bool,
              reuse: int):
    cfg = get_config(arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    fp = FixedPointConfig(16, 6) if fixed_point else None
    eng = RNNServingEngine(cfg, params, mode=mode, fp=fp)
    eng.warmup()

    r = cfg.rnn
    if "top-tagging" in cfg.name:
        x, _ = top_tagging_dataset(n_requests, seed=3)
    elif "flavor" in cfg.name:
        x, _ = flavor_tagging_dataset(n_requests, seed=3)
    else:
        x, _ = quickdraw_dataset(n_requests, seed=3)

    lat = []
    t0 = time.perf_counter()
    for i in range(n_requests):
        eng.batcher.submit(x[i])
        done = eng.batcher.run(eng.predict)
        lat.extend(d.latency_s for d in done)
    done = eng.batcher.drain()
    if done:
        out = eng.predict(np.stack([d.payload for d in done]))
        t = time.perf_counter()
        for i, d in enumerate(done):
            d.result, d.done_s = out[i], t
        lat.extend(d.latency_s for d in done)
    wall = time.perf_counter() - t0

    lat_ms = np.asarray(lat) * 1e3
    print(f"[serve] {arch} mode={mode} fp={'16,6' if fixed_point else 'off'}")
    print(f"  served {n_requests} requests in {wall:.2f}s "
          f"({n_requests/wall:.0f} ev/s)")
    print(f"  latency p50={np.percentile(lat_ms,50):.2f}ms "
          f"p99={np.percentile(lat_ms,99):.2f}ms")
    d = eng.fpga_design(reuse_kernel=reuse, reuse_recurrent=reuse,
                        strategy="resource" if reuse > 1 else "latency")
    print(f"  paired FPGA design point: latency {d.latency_min_us:.1f}-"
          f"{d.latency_max_us:.1f}us II={d.ii_cycles} "
          f"DSP={d.dsp} fits={d.fits} ({d.part})")
    print(f"  FPGA throughput @200MHz: {d.throughput_eps:.0f} ev/s "
          f"(batch-1; paper Sec 5.2 compares V100 batch-1 at 660 ev/s)")


def serve_lm(arch: str, n_requests: int):
    cfg = tiny_config(get_config(arch))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = LMServingEngine(cfg, params, max_batch=4, max_seq=64)
    rng = np.random.RandomState(0)
    pending = [list(rng.randint(2, cfg.vocab_size, rng.randint(2, 8)))
               for _ in range(n_requests)]
    t0 = time.perf_counter()
    finished = {}
    while pending or any(s.active for s in eng.slots):
        while pending and eng.add_request(pending[0], max_new=8) is not None:
            pending.pop(0)
        finished.update(eng.tick())
    wall = time.perf_counter() - t0
    toks = sum(len(v) for v in finished.values())
    print(f"[serve] {arch} (tiny): {len(finished)} requests, {toks} tokens "
          f"in {wall:.2f}s ({toks/wall:.0f} tok/s, continuous batching)")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="top-tagging-gru")
    ap.add_argument("--mode", default="static",
                    choices=["static", "nonstatic"])
    ap.add_argument("--requests", type=int, default=512)
    ap.add_argument("--fixed-point", action="store_true")
    ap.add_argument("--reuse", type=int, default=1)
    args = ap.parse_args()
    enable_jax_compilation_cache()
    cfg = get_config(args.arch)
    if cfg.family == "rnn":
        serve_rnn(args.arch, args.mode, args.requests, args.fixed_point,
                  args.reuse)
    else:
        serve_lm(args.arch, min(args.requests, 12))


if __name__ == "__main__":
    main()
