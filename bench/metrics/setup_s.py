"""setup_s (s): process start to window open: weights made on the
device from the seed, the engine built, every shape the window will use
compiled or loaded from the persistent cache."""


def read(run):
    return run.setup_s
