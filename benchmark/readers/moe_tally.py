"""Two numbers from the expert layers' routing tally (``EngineStats.
moe_routed_pairs`` / ``moe_expert_hits``, each (L_moe, E), accumulated on the
device in the decode block's carry), as the difference between the traced
window's two readings:

- ``hit_share`` (%): of the (layer, expert, decode step) triples of the
  window, those in which the expert got a token of a live slot — the share
  of the expert banks a step has to read;
- ``load_max_over_mean``: the busiest expert's routed pairs over the mean
  expert's, a layer, averaged over the layers.

A program that keeps no tally gives nothing to read."""


def _delta(t, key):
    a, b = t["c0"].get(key), t["c1"].get(key)
    if a is None or b is None:
        return None
    return [[y - x for x, y in zip(ra, rb)] for ra, rb in zip(a, b)]


def read(ctx, what):
    t = ctx["trace"]
    if not t:
        return None
    if what == "hit_share":
        hits = _delta(t, "moe_expert_hits")
        steps = t["c1"]["decode_steps"] - t["c0"]["decode_steps"]
        if hits is None or steps <= 0:
            return None
        return 100.0 * sum(map(sum, hits)) / (
            steps * len(hits) * len(hits[0]))
    pairs = _delta(t, "moe_routed_pairs")
    if pairs is None or not all(sum(row) > 0 for row in pairs):
        return None
    return sum(max(row) * len(row) / sum(row) for row in pairs) / len(pairs)
