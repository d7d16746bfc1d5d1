"""Compile cache: JAX's own trace + lower + backend-compile seconds during
set-up. It also sees the programs that bypass the AOT cache (plain jits such
as decompress_rows), and overlaps aot.first_call_s where a program does go
through it."""


def read(ctx):
    return sum(ctx.compile_at_warm["jax_seconds"].values())
