"""Answered events over the whole window, from its start to the last answer
(host clock)."""


def read(run):
    return len(run.record.idx) / run.window_s
