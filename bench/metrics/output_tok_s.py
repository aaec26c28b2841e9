"""Tokens the client's ``on_token`` callback received in the window, over
the window's seconds (host clock)."""


def read(run):
    return run.tokens_between(run.t_open, run.t_close) / (run.t_close - run.t_open)
