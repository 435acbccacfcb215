"""Output tokens handed out inside the window, over the window's
seconds (every token of every request, stamped when its tick returned)."""


def read(run):
    return run.tokens_in_window() / run.seconds
