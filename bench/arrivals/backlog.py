"""Closed backlog: every slot is refilled the tick it frees, so the engine
is never short of work and requests have no due time."""

CLOSED = True


def gaps(mix: dict, n: int):
    return None
