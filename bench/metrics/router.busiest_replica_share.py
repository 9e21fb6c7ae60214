"""The busiest replica's share of the answered events, from
``RoutedRequest.winner`` (program counter)."""


def read(run):
    w = run.record.winners
    return None if not w else 100.0 * max(w.values()) / sum(w.values())
