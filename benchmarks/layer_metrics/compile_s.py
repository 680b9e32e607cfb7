"""Seconds the program spent compiling (or loading from the cache) its
device programs: the ``compiled <what> in <s>s`` lines of
``utils/env.log_compile``, summed."""


def read(spans, facts, trace, info):
    seconds = info["ctx"].compile_lines.seconds
    return sum(seconds.values()) if seconds else None
