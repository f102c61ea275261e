"""Readings shared by several per-layer metrics."""


def idle_share(r):
    t = r["trace"]
    if not t.get("steps") or not t.get("window_s"):
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def per_step_ms(r, key):
    t = r["trace"]
    if not t.get("steps") or key not in t:
        return None
    return 1e3 * t[key] / t["steps"]
