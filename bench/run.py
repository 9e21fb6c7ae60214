"""Run one cell of the benchmark once and print its result.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout.  The cell, its configuration, its traffic mix
and its metrics are found by name from ``BENCHMARK.json`` (``bench/spec.py``).
A run:

1. sets up: JAX on the TPU (it exits non-zero, printing no result, when
   there is none or fewer chips than the cell asks for), then, through the
   configuration's family module (``bench/families/<family>.py``), the
   weights made on the device from the seed, the input pool from the seed,
   the engines, and every shape the cell's traffic uses warmed up;
2. measures for ``--seconds``; with ``--trace 1`` it then measures for
   ``TRACE_SECONDS`` more under the profiler.  A per-layer metric whose
   ``source`` is the trace or the program's spans reads the traced window,
   any other the untraced one, so that the profiler's cost on the host
   does not reach a host-clock metric;
3. checks every answer of both windows against the family's reference
   (``bench/check.py``);
4. prints, as the last line of standard output, one JSON object:
   ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, with
   ``--trace 1`` also ``breakdown``, and last ``checks``, each number
   compared beside its limit (also the last lines of standard error).
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

from bench import check, spec, traffic  # noqa: E402

#: JAX's persistent compilation cache and the engines' AOT cache: fixed
#: paths inside the checkout, so that only a cell's first run compiles
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_DIR = os.path.join(ROOT, "bench", ".traces")
#: length of a traced run's window under the profiler
TRACE_SECONDS = 3.0
#: metric sources read from the traced window; the others read the
#: untraced one
TRACED_SOURCES = ("device_trace", "program_span")


def fail(msg: str) -> int:
    print(f"bench/run.py: {msg}", file=sys.stderr)
    return 2


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = spec.resolve(spec.load_benchmark(ROOT), args.workload, ROOT)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        return fail("the program under test (src/repro) is not in this "
                    "checkout; nothing ran")

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        return fail(f"{cell.name} needs {cell.chips} TPU chip(s); JAX found "
                    f"{len(devices)} {devices[0].platform} device(s). "
                    f"Nothing was measured.")
    peaks = spec.load_json(os.path.join(ROOT, "bench", "peaks.json"))
    if devices[0].device_kind not in peaks:
        return fail(f"no peaks for device kind {devices[0].device_kind!r} "
                    f"in bench/peaks.json")
    jax.config.update("jax_compilation_cache_dir",
                      os.path.join(CACHE_DIR, "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      devices[:cell.chips], t_start=T_START,
                      peaks=peaks[devices[0].device_kind])
    result["device"] = {"platform": devices[0].platform,
                        "kind": devices[0].device_kind,
                        "count": len(devices), **result["device"]}
    emit(result)
    return 0


def run_cell(cell, seed: int, seconds: float, trace: bool, devices,
             t_start: float, peaks, cache_root: str = CACHE_DIR) -> dict:
    """Set up, measure, check, at the matmul precision the configuration
    states.  Returns the result object; its ``device`` holds only what the
    run measured, not the device's identity.  The engines keep their
    compiled executables under ``cache_root``."""
    import jax

    with jax.default_matmul_precision(cell.config["matmul_precision"]):
        return _run_cell(cell, seed, seconds, trace, devices, t_start, peaks,
                         cache_root)


def _run_cell(cell, seed, seconds, trace, devices, t_start, peaks,
              cache_root) -> dict:
    import jax

    from bench import drive
    from bench.trace import read as read_trace

    config, family = cell.config, cell.family
    words = traffic.seed_words(seed)
    marks = [("start-up", time.perf_counter())]
    params = family.make_params(config, int(words[0]), devices[0])
    jax.block_until_ready(params)
    marks.append(("weights", time.perf_counter()))
    x = family.make_inputs(config, cell.mix, int(words[1]))
    marks.append(("input pool", time.perf_counter()))
    cache_dir = os.path.join(cache_root,
                             f"engine-{config['matmul_precision']}")
    driver = family.DRIVERS[cell.mix["entry"]](cell, params, x, words,
                                               devices, cache_dir)
    driver.warm()
    n_exe = len(driver.executables())
    # what set-up made lives on: keep it out of the collector's full
    # passes inside the window
    gc.collect()
    gc.freeze()
    marks.append(("engines and warm-up", time.perf_counter()))
    setup_s = time.perf_counter() - t_start
    print("setup: " + ", ".join(
        f"{name} {t - prev} s" for (name, t), prev in
        zip(marks, [t_start] + [t for _, t in marks[:-1]])), file=sys.stderr)

    rec = driver.window(seconds, drive.Spans(False))
    traced = None
    if trace:
        log_dir = os.path.join(TRACE_DIR, cell.name)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1      # the benchmark's spans, little else
        shutil.rmtree(log_dir, ignore_errors=True)
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        try:
            traced = driver.window(min(seconds, TRACE_SECONDS),
                                   drive.Spans(True))
        finally:
            jax.profiler.stop_trace()
    gc.unfreeze()
    memory = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in devices)
    exes = driver.executables()
    compiles = len(exes) - n_exe
    missing_kernel = (sum("tpu_custom_call" not in e.as_text() for e in exes)
                      if devices[0].platform == "tpu" else 0)
    if rec.lateness_s is not None:
        print(check.lateness_line(rec.lateness_s), file=sys.stderr)

    def as_run(record, tr=None):
        return check.Run(cell=cell, record=record, trace=tr, setup_s=setup_s,
                         peaks=peaks, device_ids=[d.id for d in devices])
    run = as_run(rec)
    traced_run = None
    if trace:
        traced_run = as_run(traced, read_trace(log_dir))
        shutil.rmtree(log_dir, ignore_errors=True)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = spec.metric_reader(m["name"])(
            traced_run if m["source"] in TRACED_SOURCES else run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    # the reference runs once the window has closed and the program's
    # state is freed, over the pool inputs that were answered
    del driver, exes
    answered = drive.joined([r for r in (rec, traced) if r is not None])
    expected = family.reference(config, params, answered, x)
    del params
    checks = check.compare(
        answered, family.compare(answered, expected, config["limits"]),
        missing_kernel=missing_kernel, compiles_in_window=compiles)
    out = {"correct": check.correct(checks),
           "attempted": int(answered.attempted),
           "failed": int(answered.failed), "metrics": metrics,
           "device": {"memory_peak_bytes": int(memory)}}
    if traced_run is not None:
        out["device"].update(check.device_busy(traced_run))
        out["breakdown"] = check.breakdown(traced_run)
    out["checks"] = checks
    return out


def emit(result: dict) -> None:
    """Each number compared beside its limit as the last lines of standard
    error; the result as the last line of standard output."""
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    sys.exit(main())
