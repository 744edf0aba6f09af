"""Seconds XLA spent compiling (or loading from the cache) during set-up, as
the program's recompilation watchdog counted them."""


def read(ctx):
    return ctx.watchdog.get("compile_time_s")
