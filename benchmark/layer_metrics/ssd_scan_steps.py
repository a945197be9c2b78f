"""Linear mixer: sequential trips of the state's loop in one call of the
chunked state-space scan, read from the compiled step's HLO: the largest
trip count among the ``while`` instructions under ``hvdt.ssd.scan`` (the
forward's, the recompute's and the backward's loops over the chunks all
make sequence / chunk trips: 32 at 8,192 tokens in chunks of 256).  A
count, read as ``gdn_scan_steps`` reads the Gated DeltaNet's: the
instruction's ``known_trip_count`` where the compiler wrote one, else the
constant its condition compares the counter with."""

import re

_KNOWN = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')
_CONDITION = re.compile(r"condition=%?([\w.\-]+)")
_CONSTANT = re.compile(r"= s32\[\][^ ]* constant\((\d+)\)")


def scope_loop_trips(hlo_text: str, scope: str) -> list:
    """The trip count of every ``while`` under ``scope``."""
    trips = []
    for line in hlo_text.splitlines():
        if " while(" not in line or scope not in line:
            continue
        known = _KNOWN.search(line)
        if known:
            trips.append(int(known.group(1)))
            continue
        condition = _CONDITION.search(line)
        # the condition's computation: from its header to its closing brace
        head = condition and re.search(
            rf"^%?{re.escape(condition.group(1))} \(.*$", hlo_text, re.M)
        if not head:
            continue
        body = hlo_text[head.end():hlo_text.index("\n}", head.end())]
        bounds = _CONSTANT.findall(body)
        if bounds:
            trips.append(max(map(int, bounds)))
    return trips


def read(ctx):
    trips = scope_loop_trips(ctx.hlo_text, "hvdt.ssd.scan")
    return max(trips) if trips else None
