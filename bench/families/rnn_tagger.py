"""The paper's RNN taggers (``"family": "rnn_tagger"``).

A configuration gives the tagger's sizes under ``model`` (``cell``,
``hidden``, ``seq_len``, ``input_size``, ``dense_sizes``, ``n_outputs``,
``output_activation``), its dtypes, the ``impl`` and kernel ``schedule`` the
engine serves with, its event generator (``events``, a name of
``bench.events.POOLS``) and its ``limits``.  An event is one tagged input:
a ``[seq_len, input_size]`` float32 tensor, answered by its class
probabilities.

Entry points (the traffic mix's ``entry``):

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

What decides ``correct`` beyond the checks every family shares
(``bench/check.py``):

``prob_max_abs_err``  widest gap between a served class probability and
                      the NumPy float32 reference's (``bench/reference.py``)
                      for the same event under the same weights, over every
                      answer of the window; its limit is the configuration's
                      (``limits``), set from on-chip readings of the program
                      and of its control as ``PERF.md`` records.
"""

from __future__ import annotations

import math
import time
from typing import Dict, List

import numpy as np

from bench import check, reference as np_reference, traffic, weights
from bench.drive import Driver, Record
from bench.events import POOLS

TOLERANCE_CHECKS = ("prob_max_abs_err",)


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


def make_params(config: Dict, word: int, device):
    """The tagger's float32 weights on ``device`` (``bench/weights.py``)."""
    return weights.make_params(config["model"], word, device)


def make_inputs(config: Dict, mix: Dict, word: int) -> np.ndarray:
    """The pool of ``mix["pool"]`` events, float32 ``[pool, T, in]``."""
    return POOLS[config["events"]](mix["pool"], word)[0].astype(np.float32)


def reference(config: Dict, params, answered: Record,
              inputs: np.ndarray) -> np.ndarray:
    """The NumPy reference's probabilities for every pool event that was
    answered (rows of the others are NaN)."""
    host_params = {k: np.asarray(v) for k, v in params.items()}
    return check.reference_for(
        answered.idx, inputs, lambda xs: np_reference.probabilities(
            config["model"], host_params, xs))


def compare(answered: Record, expected, limits: Dict) -> Dict:
    if expected is None or not len(answered.idx):
        err = math.inf
    else:
        err = float(np.abs(np.asarray(answered.answers, np.float32)
                           - expected[answered.idx]).max())
    return {"prob_max_abs_err": {"value": err if math.isfinite(err) else None,
                                 "limit": limits["prob_max_abs_err"]}}


def kernel_schedule(d: Dict):
    from repro.kernels.schedule import KernelSchedule

    return KernelSchedule(**d)


class TaggerDriver(Driver):
    """Base of the tagger's drivers: the program's configuration and the
    engine the configuration states."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.cfg = model_config(self.config)

    def _engine(self, **kw):
        from repro.serving import RNNServingEngine

        return RNNServingEngine(
            self.cfg, self.params, impl=self.config["impl"],
            schedule=kernel_schedule(self.config["schedule"]),
            cache_dir=self.cache_dir, device=self.devices[0], **kw)


class PredictOne(TaggerDriver):
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


class PredictBatch(TaggerDriver):
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


class SubmitFlush(TaggerDriver):
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


class Routed(TaggerDriver):
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
