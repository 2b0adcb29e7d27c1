"""Share of the device's busy time that one program's runs take."""


def read(ctx, query):
    t = ctx["trace"] or {}
    q = t.get("queries", {}).get(query)
    if not q or q["count"] <= 0 or t["busy_s"] <= 0:
        return None
    return 100.0 * q["seconds"] / t["busy_s"]
