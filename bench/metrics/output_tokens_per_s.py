"""output_tokens_per_s (tokens/s): output tokens that reached the client
inside the window, over the window's wall seconds."""

from harness.stats import tokens_in_window


def read(run):
    w = run.window
    return tokens_in_window(run.stamps(), w.origin, w.end) / (w.end - w.origin)
