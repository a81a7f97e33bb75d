"""Tokens the HTTP front (`serving/http.py`) has routed to a stream but not
yet written to its socket, per stream, averaged over the window's drive
steps: the window difference of the front's `backlog_token_steps` counter
over that of `drive_steps` (both carried by its `front.route` spans),
divided by the streams written to in the window; nothing where the program
counts neither."""

from bench.lib import program


def read(run):
    prog = program.of(run)
    if prog is None:
        return None
    d = program.window_counters(prog, "front.route")
    streams = {s[3].get("request_id")
               for s in program.window_spans(prog, "front.sse_write")}
    if not d or not d.get("drive_steps") or not streams:
        return None
    return d["backlog_token_steps"] / d["drive_steps"] / len(streams)
