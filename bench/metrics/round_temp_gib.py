"""Device memory of the compiled round program as the compiler plans it:
temporaries plus arguments, in GiB (`compiled.memory_analysis()`)."""


def read(ctx):
    mem = ctx.memory
    if mem is None:
        return None
    return (mem.temp_size_in_bytes + mem.argument_size_in_bytes) / 2 ** 30
