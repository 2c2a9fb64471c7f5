"""refresh_s: window seconds over the refreshes completed in the window —
each a patch, a warm re-solve and its reads, with the traffic's own draw
between them."""


def read(run):
    return run.window_s / len(run.requests)
