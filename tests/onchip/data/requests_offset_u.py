"""A requests module for tests: the scored requests, with an offset
planted in the utilities the reference compares against (the last
candidate of each request raised above all others), so that a sound
run compared with them cannot be correct."""

from benchmarks.onchip import layout

_scored = layout.request_module({})
make = _scored.make


def inputs(setup, pool, reqs):
    out = []
    for inp in _scored.inputs(setup, pool, reqs):
        u = inp["u"].copy()
        u[-1] += 10.0 * abs(u).max() + 1.0
        out.append({**inp, "u": u})
    return out
