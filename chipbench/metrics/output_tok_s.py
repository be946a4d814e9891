"""output_tok_s (tokens/s): every token streamed inside the window,
divided by the window's length."""

from harness import reduce


def read(run):
    return reduce.window_tokens(run) / (run.close - run.open)
