"""tokens_per_s: every token generated in the window over the window's
seconds, event steps included."""


def read(run):
    if not run.steps or run.window_s <= 0:
        return None
    return len(run.steps) * run.tokens_per_step / run.window_s
