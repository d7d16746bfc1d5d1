"""Compile cache (ops/aot_cache.py): seconds of set-up spent in the AOT
cache's export, deserialize and first call of every program the warm-up
met."""


def read(ctx):
    return ctx.compile_at_warm["aot_seconds"]
