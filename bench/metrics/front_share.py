"""Share of the window in which `engine.step()` was not running: the HTTP
front's loop, SSE writes and the clients (`serving/http.py`).  From the
proxy's step spans on the host clock."""


def read(run):
    w = run["window"]
    inside = sum(w["step_s"])
    return 100.0 * (1.0 - inside / w["window_s"])
