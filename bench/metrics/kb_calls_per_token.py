"""Merged KB calls the window made per output token (the program's
``ContinuousResult.kb_calls`` counter)."""


def read(run):
    return run.result.kb_calls / run.tokens if run.tokens else None
