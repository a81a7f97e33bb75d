"""Live slots per step over the engine's slots, averaged over the window's
steps (`serving/engine.py`): counted from the token events each step
returned."""


def read(run):
    steps = run["window_steps"]
    slots = run["traffic"]["clients"]
    live = [len({rid for rid, _ in s.tokens}) for s in steps]
    return 100.0 * sum(live) / (slots * len(live))
