"""Offline serving: the mix's whole queue waits at the window's open, more
requests than slots, so the engine runs full batches all window long."""
from bench import serving


def run(ctx):
    return serving.serve(ctx, open_loop=False)
