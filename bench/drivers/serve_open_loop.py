"""Open-loop serving: requests arrive at their scheduled times whatever the
engine is doing; latency counts from the scheduled arrival, and the run
steps on past the window until every arrival has its first token."""
from bench import serving


def run(ctx):
    return serving.serve(ctx, open_loop=True)
