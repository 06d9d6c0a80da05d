"""Milliseconds one serving round waits on the devices: for each gateway
pump (``repro/gateway/pump``) in the traced window that holds a
``repro/pool<i>/block``, the summed block time of every pool inside it;
the mean over those pumps. Pools ticked one after another wait about the
sum of their device times; pools dispatched before any sync, about the
largest."""
import spans


def read(run):
    pumps = spans.select(run, r"repro/gateway/pump")
    blocks = spans.select(run, r"repro/pool\d+/block")
    waits = [spans.seconds(inner) for inner in spans.inside(pumps, blocks)
             if inner]
    return sum(waits) / len(waits) * 1e3 if waits else None
