"""Device time per decode step (ms) of the instructions in the engine's
``moe_experts`` scope (the held experts' matrix products), found through
the compiled step's scope map."""

SCOPE = "moe_experts"


def read(r):
    t, scopes = r["trace"], r["inputs"].get("scopes")
    if not t.get("steps") or not scopes:
        return None
    secs = [v for k, v in t.get("op_s", {}).items()
            if scopes.get(k.split(" ", 1)[0]) == SCOPE]
    return 1e3 * sum(secs) / t["steps"] if secs else None
