"""Output tokens the server emitted inside the window, over the window
(host clock).  Read from the server's own token counter at the window's
open and close, so tokens of requests still running at the close count
too."""


def read(run):
    if run.kind != "serve":
        return None
    return (run.stats1["tokens"] - run.stats0["tokens"]) / run.window_s
