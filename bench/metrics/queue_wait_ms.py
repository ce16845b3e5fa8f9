"""Serve loop: mean time from a request's due time to its admission into
an engine slot, over the requests admitted in the window (host clock,
taken by the harness's wrapper around ``engine.admit``)."""


def read(run):
    waits = [run.probe.admits[rid] - run.requests[rid]["arrival"]
             for rid in run.probe.admits if rid < len(run.requests)]
    return 1e3 * sum(waits) / len(waits) if waits else None
