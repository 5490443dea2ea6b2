"""output_tok_s: Output tokens the host held by the window's close, per
second of the window."""


def read(run):
    return run.tokens_in_window() / run.seconds
