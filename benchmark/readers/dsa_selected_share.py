"""dsa_selected_share: of the cached rows that the decode steps of the traced
window scored for live slots (every row up to a slot's frontier, a layer),
the share they then attended to — ``EngineStats.dsa_rows_selected`` over
``dsa_rows_scored``, each (L,) and accumulated on the device in the decode
block's carry, as the difference between the window's two readings. 100
while no context passes ``index_topk``. A program whose attention selects
nothing keeps no such counters and gives nothing to read."""


def read(ctx):
    t = ctx["trace"]
    if not t:
        return None
    delta = {}
    for key in ("dsa_rows_scored", "dsa_rows_selected"):
        a, b = t["c0"].get(key), t["c1"].get(key)
        if a is None or b is None:
            return None
        delta[key] = sum(b) - sum(a)
    if delta["dsa_rows_scored"] <= 0:
        return None
    return 100.0 * delta["dsa_rows_selected"] / delta["dsa_rows_scored"]
