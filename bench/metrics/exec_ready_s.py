"""Compile and cache: seconds of set-up from building the runner until
its program can run, on the host clock: the runner's construction plus
the first request's dispatch, which traces, lowers and compiles the
program or loads it from the compile cache or the executable store.
Moves ``setup_s``.
"""


def read(ctx):
    return ctx.setup.get("exec_ready_s")
