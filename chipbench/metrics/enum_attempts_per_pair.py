"""Pair enumerations attempted per pair routed, in the window.

The ``pairs`` of the program's ``build/shard`` spans (one per enumeration
shard of one slack round) over the ``pairs`` of its ``build/enumerate``
spans (the reachable pairs entering the first round), both in
``core/routing.py`` ``_k_shortest_unique``.  1.0 means no pair was
enumerated twice with a larger slack.
"""


def read(ctx):
    tried = sum(s.attrs.get("pairs", 0) for s in ctx["spans"]
                if s.name == "build/shard")
    routed = sum(s.attrs.get("pairs", 0) for s in ctx["spans"]
                 if s.name == "build/enumerate")
    if not routed:
        return None
    return tried / routed
