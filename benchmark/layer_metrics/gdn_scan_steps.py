"""Linear mixer: sequential trips of the state's loop in one call of the
chunked scan, read from the compiled step's HLO: the largest trip count
among the ``while`` instructions under ``hvdt.gdn.scan`` (the forward's,
the recompute's and the backward's loops over the chunks all make
sequence / chunk trips: 256 at 16,384 tokens in chunks of 64).  A trip
count is the instruction's ``known_trip_count`` where the compiler wrote
one, else the constant its condition compares the counter with (a
``lax.scan`` counts up from 0).  A count: it repeats exactly, and a change
that lengthens the chunks or splits the sequence shows here before it
shows in time."""

import re

_KNOWN = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')
_CONDITION = re.compile(r"condition=%?([\w.\-]+)")
_CONSTANT = re.compile(r"= s32\[\][^ ]* constant\((\d+)\)")


def scan_loop_trips(hlo_text: str) -> list:
    """The trip count of every ``while`` under ``hvdt.gdn.scan``."""
    loops = [line for line in hlo_text.splitlines()
             if " while(" in line and "hvdt.gdn.scan" in line]
    trips = []
    for line in loops:
        known = _KNOWN.search(line)
        if known:
            trips.append(int(known.group(1)))
            continue
        condition = _CONDITION.search(line)
        if not condition:
            continue
        # the condition's computation: from its header to its closing brace
        head = re.search(rf"^%?{re.escape(condition.group(1))} \(.*$",
                         hlo_text, re.M)
        if not head:
            continue
        body = hlo_text[head.end():hlo_text.index("\n}", head.end())]
        bounds = _CONSTANT.findall(body)
        if bounds:
            trips.append(max(map(int, bounds)))
    return trips


def read(ctx):
    trips = scan_loop_trips(ctx.hlo_text)
    return max(trips) if trips else None
