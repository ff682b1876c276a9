"""Passes of the stack a (row, call) of the two programs ran, over the
measured window: the cache's ``loop_passes`` over its (row, call)s, as the
replica's ``stats()`` reported both (``jobs/serve_loop.py:_window``).  The
configuration's ``total_ut_steps`` while every token runs every pass (4.0 at
the published threshold 1); the number an early exit would move.  None where
the program carries no such counter."""


def read(ctx):
    f = ctx["facts"]
    if not f.get("loop_row_steps") or f.get("loop_passes") is None:
        return None
    return f["loop_passes"] / f["loop_row_steps"]
