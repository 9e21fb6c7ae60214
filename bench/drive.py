"""Drive one cell: build the system under test, warm the shapes the cell's
traffic uses, and run the measured window through the entry point the
traffic mix names (``entry``):

``predict_one``   closed loop, one client: ``RNNServingEngine.predict_one``
                  back to back over the event pool (batch 1, no batcher);
``predict``       closed loop: ``RNNServingEngine.predict`` on batches of
                  ``batch`` events, the next sent when the answers are back;
``submit_flush``  open loop: each event ``submit``-ed when it falls due
                  (``bench.traffic.arrival_times``), ``flush`` whenever the
                  batcher is ready (``max_batch`` / ``max_wait_us``);
``router``        closed loop: ``Router.submit`` over a ``ReplicaPool`` of
                  ``replicas`` engines, one per chip, cycling through the
                  mix's ``schedules``.

Every event is timed on the host clock: from when it was due (open loop)
or issued (closed loop) until its answer is back on the host as NumPy.
"""

from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

from bench import traffic


@dataclass
class Record:
    """What a measured window produced, on the host clock."""

    idx: np.ndarray                  # pool index of each answered event
    answers: np.ndarray              # [answered, n_outputs]
    attempted: int
    failed: int                      # failed, shed or never answered
    t_begin: float
    t_end: float                     # when the last answer came back
    calls: int                       # calls into the entry point
    latency_s: Optional[np.ndarray] = None
    queue_wait_s: Optional[np.ndarray] = None
    lateness_s: Optional[np.ndarray] = None
    winners: Dict[str, int] = field(default_factory=dict)


def joined(records: List[Record]) -> Record:
    """The answers of several windows as one record, for the check."""
    return Record(
        idx=np.concatenate([r.idx for r in records]),
        answers=np.concatenate([r.answers for r in records]),
        attempted=sum(r.attempted for r in records),
        failed=sum(r.failed for r in records),
        t_begin=records[0].t_begin, t_end=records[-1].t_end,
        calls=sum(r.calls for r in records))


def model_config(config: Dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.config import ModelConfig, RNNConfig

    m = config["model"]
    return ModelConfig(
        name=config["name"], family="rnn",
        rnn=RNNConfig(cell=m["cell"], hidden=m["hidden"],
                      seq_len=m["seq_len"], input_size=m["input_size"],
                      dense_sizes=tuple(m["dense_sizes"]),
                      n_outputs=m["n_outputs"],
                      output_activation=m["output_activation"]),
        param_dtype=config["param_dtype"],
        compute_dtype=config["compute_dtype"])


def kernel_schedule(d: Dict):
    from repro.kernels.schedule import KernelSchedule

    return KernelSchedule(**d)


class Spans:
    """Benchmark spans in the profiler's trace; free when not tracing."""

    def __init__(self, on: bool):
        self.on = on

    def __call__(self, name: str):
        if not self.on:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)


class Driver:
    """Base: the pool of events, the engines built, counts of what they
    compiled."""

    def __init__(self, cell, params, x: np.ndarray, words: np.ndarray,
                 devices: List, cache_dir: str):
        self.cell, self.mix, self.config = cell, cell.mix, cell.config
        self.cfg = model_config(cell.config)
        self.params, self.x, self.devices = params, x, devices
        self.order = traffic.pool_order(len(x), int(words[2]))
        self.arrival_word = int(words[3])
        self.cache_dir = cache_dir

    def engines(self) -> List:
        raise NotImplementedError

    def executables(self) -> List:
        return [exe for eng in self.engines()
                for exes in eng.executables().values() for exe in exes]

    def warm(self) -> None:
        raise NotImplementedError

    def window(self, seconds: float, span: Spans) -> Record:
        raise NotImplementedError

    def _engine(self, **kw):
        from repro.serving import RNNServingEngine

        return RNNServingEngine(
            self.cfg, self.params, impl=self.config["impl"],
            schedule=kernel_schedule(self.config["schedule"]),
            cache_dir=self.cache_dir, device=self.devices[0], **kw)


class PredictOne(Driver):
    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.eng = self._engine()

    def engines(self):
        return [self.eng]

    def warm(self):
        for k in range(self.mix["warm_calls"]):
            self.eng.predict_one(self.x[self.order[k % len(self.x)]])

    def window(self, seconds, span):
        x, order, n = self.x, self.order, len(self.x)
        idx, answers, lat = [], [], []
        clock = time.perf_counter
        t_begin = clock()
        t_stop = t_begin + seconds
        k, t1 = 0, t_begin
        with span("bench.window"):
            while t1 < t_stop:
                i = order[k % n]
                t0 = clock()
                with span("bench.call"):
                    out = self.eng.predict_one(x[i])
                t1 = clock()
                idx.append(i)
                answers.append(out)
                lat.append(t1 - t0)
                k += 1
        return Record(idx=np.asarray(idx), answers=np.stack(answers),
                      attempted=k, failed=0, t_begin=t_begin, t_end=t1,
                      calls=k, latency_s=np.asarray(lat))


class PredictBatch(Driver):
    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.eng = self._engine()
        b = self.mix["batch"]
        if len(self.x) % b:
            raise ValueError(f"pool {len(self.x)} is not a whole number of "
                             f"batches of {b}")
        # batches in the seeded order, each contiguous in host memory
        self.batch_idx = self.order.reshape(-1, b)
        self.batches = [np.ascontiguousarray(self.x[ix])
                        for ix in self.batch_idx]

    def engines(self):
        return [self.eng]

    def warm(self):
        for k in range(self.mix["warm_calls"]):
            self.eng.predict(self.batches[k % len(self.batches)])

    def window(self, seconds, span):
        idx, answers = [], []
        clock = time.perf_counter
        t_begin = clock()
        t_stop = t_begin + seconds
        k, t1 = 0, t_begin
        with span("bench.window"):
            while t1 < t_stop:
                j = k % len(self.batches)
                with span("bench.call"):
                    out = self.eng.predict(self.batches[j])
                t1 = clock()
                idx.append(self.batch_idx[j])
                answers.append(out)
                k += 1
        idx_a = np.concatenate(idx)
        return Record(idx=idx_a, answers=np.concatenate(answers),
                      attempted=len(idx_a), failed=0, t_begin=t_begin,
                      t_end=t1, calls=k)


class SubmitFlush(Driver):
    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.eng = self._engine(max_batch=self.mix["max_batch"])
        self.eng.batcher.max_wait_s = self.mix["max_wait_us"] * 1e-6

    def engines(self):
        return [self.eng]

    def warm(self):
        self.eng.prewarm()
        mb = self.mix["max_batch"]
        for k in range(self.mix["warm_calls"]):
            for j in range(mb):
                self.eng.submit(self.x[self.order[(k * mb + j) % len(self.x)]])
            self.eng.flush(force=True)

    def window(self, seconds, span):
        x, order, n = self.x, self.order, len(self.x)
        due_rel = traffic.arrival_times(self.mix, seconds, self.arrival_word)
        m = len(due_rel)
        ev = order[np.arange(m) % n]
        eng, batcher = self.eng, self.eng.batcher
        of_req: Dict[int, int] = {}
        late = np.zeros(m)
        t_done = np.full(m, np.nan)
        t_flush = np.full(m, np.nan)
        results: List = [None] * m
        failed = 0
        calls = 0
        clock = time.perf_counter
        t_begin = clock()
        due = t_begin + due_rel
        give_up = due[-1] + 60.0
        j, answered = 0, 0
        with span("bench.window"):
            while answered + failed < m:
                now = clock()
                if j < m and due[j] <= now:
                    with span("bench.submit"):
                        while j < m and due[j] <= now:
                            r = eng.submit(x[ev[j]])
                            of_req[r.req_id] = j
                            late[j] = clock() - due[j]
                            j += 1
                            now = clock()
                if batcher.ready():
                    tf = clock()
                    with span("bench.flush"):
                        done = eng.flush()
                    te = clock()
                    calls += 1
                    for r in done:
                        e = of_req.pop(r.req_id)
                        if r.status == "answered":
                            t_done[e], t_flush[e] = te, tf
                            results[e] = r.result
                            answered += 1
                        else:
                            failed += 1
                elif now > give_up:
                    break
        ok = ~np.isnan(t_done)
        return Record(
            idx=ev[ok], answers=np.stack([results[e] for e in np.flatnonzero(ok)]),
            attempted=m, failed=m - int(ok.sum()), t_begin=t_begin,
            t_end=float(np.nanmax(t_done)), calls=calls,
            latency_s=(t_done - due)[ok], queue_wait_s=(t_flush - due)[ok],
            lateness_s=late[:j])


class Routed(Driver):
    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        from repro.serving import ReplicaPool, Router

        self.pool = ReplicaPool.build(
            self.cfg, self.params, self.mix["replicas"],
            impl=self.config["impl"], cache_dir=self.cache_dir)
        self.router = Router(self.pool)
        self.schedules = [kernel_schedule(s) for s in self.mix["schedules"]]

    def engines(self):
        return [rep.engine for rep in self.pool]

    def warm(self):
        from repro.kernels.schedule import schedule_key

        ref = self.router.reference_engine
        for s in self.schedules:
            rep = self.router.place(schedule_key(*ref.resolve(s)))
            rep.engine.predict_one(self.x[0], schedule=s)
        for k in range(self.mix["warm_calls"]):
            self.router.submit(self.x[self.order[k % len(self.x)]],
                               schedule=self.schedules[k % len(self.schedules)])

    def window(self, seconds, span):
        x, order, n = self.x, self.order, len(self.x)
        sch, ns = self.schedules, len(self.schedules)
        idx, answers = [], []
        winners: Dict[str, int] = {}
        clock = time.perf_counter
        t_begin = clock()
        t_stop = t_begin + seconds
        k, t1 = 0, t_begin
        with span("bench.window"):
            while t1 < t_stop:
                i = order[k % n]
                with span("bench.call"):
                    r = self.router.submit(x[i], schedule=sch[k % ns])
                t1 = clock()
                if r.status == "answered":
                    idx.append(i)
                    answers.append(r.result)
                    winners[r.winner] = winners.get(r.winner, 0) + 1
                k += 1
        return Record(idx=np.asarray(idx), answers=np.stack(answers),
                      attempted=k, failed=k - len(idx), t_begin=t_begin,
                      t_end=t1, calls=k, winners=winners)


DRIVERS = {"predict_one": PredictOne, "predict": PredictBatch,
           "submit_flush": SubmitFlush, "router": Routed}
